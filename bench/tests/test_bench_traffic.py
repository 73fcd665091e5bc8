"""The arrival and length generators: deterministic per seed, and true to
their stated parameters."""
import numpy as np

from bench.lib.arrivals import lengths, mmpp_times, poisson_gaps
from bench.lib.traffic import make_schedule, max_seq, rng_for

CHAT = {"kind": "open_loop", "arrivals": "poisson", "mix_seed": 7, "ramp_s": 2,
        "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                          "min": 64, "max": 1536},
        "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                          "min": 16, "max": 512}}
DOCS = {"kind": "batch", "mix_seed": 8, "ramp_s": 1, "backlog_per_s": 5,
        "prompt_tokens": {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                          "min": 1024, "max": 4096},
        "output_tokens": {"dist": "uniform", "min": 16, "max": 64}}


def test_lengths_match_their_parameters():
    x = lengths(CHAT["prompt_tokens"], 200_000, rng_for(1))
    assert x.min() == 64 and x.max() == 1536
    assert abs(np.median(x) - 512) < 8
    # sigma of log length, from the unclipped middle quantiles
    q25, q75 = np.percentile(np.log(x), [25, 75])
    assert abs((q75 - q25) / 1.349 - 0.8) < 0.02
    u = lengths(DOCS["output_tokens"], 100_000, rng_for(2))
    assert u.min() == 16 and u.max() == 64 and abs(u.mean() - 40) < 0.2


def test_poisson_and_mmpp_rates():
    g = poisson_gaps(200_000, 4.0, rng_for(3))
    assert abs(g.mean() - 0.25) < 0.003
    t = mmpp_times(50_000, 1.0, 10.0, 2.0, 2.0, rng_for(4))
    assert np.all(np.diff(t) >= 0)
    assert abs(len(t) / t[-1] - 5.5) < 0.3     # the two states' mean rate


def test_schedule_deterministic_per_seed():
    a = make_schedule(CHAT, {"rate_rps": 3.0}, 2 ** 40 + 3, 20, 1000)
    b = make_schedule(CHAT, {"rate_rps": 3.0}, 2 ** 40 + 3, 20, 1000)
    assert [x.due_s for x in a.items] == [x.due_s for x in b.items]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.items, b.items))
    c = make_schedule(CHAT, {"rate_rps": 3.0}, 11, 20, 1000)
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a.items, c.items))


def test_seeds_reorder_one_multiset():
    # every seed replays the one trace of the mix: same lengths, same order
    a = make_schedule(DOCS, {}, 1, 30, 1000)
    b = make_schedule(DOCS, {}, 2, 30, 1000)
    assert len(a.items) == len(b.items) == 5 * 31
    assert [(len(x.prompt), x.max_tokens) for x in a.items] == \
        [(len(x.prompt), x.max_tokens) for x in b.items]
    assert all(x.due_s == 0 for x in a.items)
    assert (a.window_start_s, a.window_end_s) == (1, 31)


def test_open_loop_window_and_ids():
    s = make_schedule(CHAT, {"rate_rps": 5.0}, 3, 30, 777)
    dues = np.array([x.due_s for x in s.items])
    assert np.all(np.diff(dues) >= 0) and dues[-1] < 32
    assert abs(len(dues) / 32 - 5.0) < 1.0
    ids = np.concatenate([x.prompt for x in s.items])
    assert ids.min() >= 0 and ids.max() < 777
    assert max_seq(CHAT, 16) == 2048 and max_seq(DOCS, 16) == 4160


def test_fixed_order_replays_one_trace_with_seeded_ids():
    a = make_schedule(CHAT, {"rate_rps": 2.0}, 1, 20, 1000)
    b = make_schedule(CHAT, {"rate_rps": 2.0}, 2, 20, 1000)
    assert [(x.due_s, len(x.prompt), x.max_tokens) for x in a.items] == \
        [(x.due_s, len(x.prompt), x.max_tokens) for x in b.items]
    assert not np.array_equal(a.items[0].prompt, b.items[0].prompt)
