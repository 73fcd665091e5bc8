"""The engine's account of the paged read's walk (``read_pages_walked``,
``read_pages_live`` in ``Engine.stats``), counted on the device inside
the decode and mixed segment programs.

Each scan step the read's grid folds, in one layer, each slot's pages up
to its bound (``PG.page_bound``), and at least one: an active slot's live
pages, page 0 alone for an inactive one. The live pages are those holding
context of an active query, and a request's contexts do not depend on how
its steps fall into segments: each prompt chunk ends at ``min(plen,
chunk x (k + 1))``, and the i-th decode step after the prompt attends to
``plen + i`` rows. So a fixed queue has a hand count of its live pages and
of its active slot-steps, and the walk is the live pages plus one page for
every inactive slot-step.
"""
import jax
import numpy as np
import pytest

from repro import kernels
from repro.configs import get_config
from repro.data import RequestQueue
from repro.kvcache import paged as PG
from repro.models import build_model
from repro.models.sampling import SamplingParams
from repro.serve import Engine, EngineConfig

MAX_SEQ, N_SLOTS, PAGE, CHUNK, SEGMENT = 48, 4, 4, 8, 4
MAX_PAGES = MAX_SEQ // PAGE
REQS = ((10, 6), (5, 9), (23, 4), (12, 11), (17, 7), (3, 5))   # (plen, n)

# greedy tokens of the six requests as served before the walk was counted
# (the same for the chunked and the non-chunked engine)
TOKENS = {
    "stablelm-1.6b": [
        [159, 235, 239, 109, 101, 239],
        [87, 225, 113, 42, 196, 87, 113, 94, 196],
        [150, 70, 200, 43],
        [179, 117, 87, 241, 225, 103, 87, 116, 116, 225, 103],
        [180, 84, 180, 38, 79, 148, 84],
        [24, 163, 120, 201, 163]],
    "qwen2-7b": [
        [24, 157, 157, 157, 91, 158],
        [245, 245, 245, 88, 50, 143, 49, 245, 49],
        [12, 228, 228, 228],
        [94, 116, 146, 253, 82, 30, 94, 116, 146, 253, 96],
        [189, 127, 101, 101, 101, 11, 245],
        [147, 94, 49, 87, 94]],
}


def _pages(rows):
    return -(-rows // PAGE)


def hand_count(chunked: bool, reqs=REQS) -> int:
    """Live pages over every step of every request: its prompt chunks
    (chunked engine only; the other prefills at admission, outside the
    scan), then its decode steps."""
    live = 0
    for plen, n in reqs:
        if chunked:
            live += sum(_pages(min(plen, CHUNK * (k + 1)))
                        for k in range(-(-plen // CHUNK)))
        live += sum(_pages(plen + i) for i in range(1, n))
    return live


def hand_steps(chunked: bool, reqs=REQS) -> int:
    """Active slot-steps over the queue: each request's prompt chunks
    (chunked engine only), then its decode steps."""
    return sum((-(-plen // CHUNK) if chunked else 0) + n - 1
               for plen, n in reqs)


def serve(arch: str, chunked: bool, attention: str = "auto", reqs=REQS):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), MAX_SEQ)
    eng = Engine.from_config(EngineConfig(
        max_seq=MAX_SEQ, n_slots=N_SLOTS, page_size=PAGE, chunked=chunked,
        chunk_size=CHUNK, segment_len=SEGMENT, ring_size=2,
        path="adaptive", attention=attention), model, params)
    q = RequestQueue()
    rng = np.random.default_rng(7)
    for plen, n in reqs:
        q.submit(rng.integers(1, cfg.vocab, size=plen).astype(np.int32),
                 params=SamplingParams(temperature=0.0, max_tokens=n))
    out = {}
    for ev in eng.serve_stream(q):
        out.setdefault(ev.req_id, []).extend(int(t) for t in ev.tokens)
    return eng, [out[i] for i in sorted(out)]


def test_hand_count_of_the_fixed_queue():
    # the counts the tests below hold the engine to, worked by hand
    # for (10, 6): chunks end at 8 and 10 rows (2 + 3 pages), decode
    # steps attend to 11..15 rows (3 + 3 + 4 + 4 + 4)
    assert hand_count(chunked=True) == 184
    assert hand_count(chunked=False) == 148
    # (10, 6): 2 chunks and 5 decode steps
    assert hand_steps(chunked=True) == 48
    assert hand_steps(chunked=False) == 36


@pytest.mark.parametrize("arch", list(TOKENS))
@pytest.mark.parametrize("chunked", [True, False])
def test_read_walk_counts_and_tokens(arch, chunked):
    eng, tokens = serve(arch, chunked)
    st = eng.stats
    kinds = {r.kind for r in eng.segment_records}
    # the chunked engine runs mixed segments, then decode; the other
    # decode segments alone
    assert kinds == ({"mixed", "decode"} if chunked else {"decode"})
    slot_steps = st["segments"] * SEGMENT * N_SLOTS
    assert st["read_pages_walked"] == (
        hand_count(chunked) + slot_steps - hand_steps(chunked))
    assert st["read_pages_live"] == hand_count(chunked)
    assert 0 < st["read_pages_live"] < st["read_pages_walked"]
    assert tokens == TOKENS[arch]


def test_read_walk_per_segment_kind(monkeypatch):
    """Each segment program adds its own steps' walk: the per-segment
    deltas of a chunked run, split by the program that ran, add up to the
    whole, and every segment walks its live pages plus at most one page
    for each of its ``segment_len x n_slots`` slot-steps."""
    from repro.serve.scheduler import BatchedServeEngine

    deltas = []
    readback = BatchedServeEngine._readback

    def keep(self, kind, stats, *rest):
        deltas.append((kind, [int(x) for x in np.asarray(stats)[4:]]))
        return readback(self, kind, stats, *rest)

    monkeypatch.setattr(BatchedServeEngine, "_readback", keep)
    eng, _ = serve("qwen2-7b", chunked=True)
    for kind in ("mixed", "decode"):
        mine = [d for k, d in deltas if k == kind]
        assert mine and all(0 < live <= w <= live + SEGMENT * N_SLOTS
                            for w, live in mine)
    assert sum(live for _, (_, live) in deltas) == eng.stats["read_pages_live"]
    assert sum(w for _, (w, _) in deltas) == eng.stats["read_pages_walked"]


def test_read_walk_in_spec_segments():
    """The speculative segment counts its verify steps' walk too; its
    contexts depend on how many drafts each round accepts, so only the
    walk is held to a number."""
    from repro.core.paths import SpecConfig

    cfg = get_config("qwen2-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), MAX_SEQ)
    eng = Engine.from_config(EngineConfig(
        max_seq=MAX_SEQ, n_slots=N_SLOTS, page_size=PAGE, chunked=True,
        chunk_size=CHUNK, segment_len=SEGMENT, ring_size=2,
        path="adaptive", spec=SpecConfig(enabled=True, k=2)), model, params)
    q = RequestQueue()
    for plen, n in REQS:
        q.submit(np.arange(1, plen + 1, dtype=np.int32),
                 params=SamplingParams(temperature=0.0, max_tokens=n))
    for _ in eng.serve_stream(q):
        pass
    st = eng.stats
    assert "spec" in {r.kind for r in eng.segment_records}
    assert 0 < st["read_pages_live"] <= st["read_pages_walked"] <= (
        st["read_pages_live"] + st["segments"] * SEGMENT * N_SLOTS)


def test_read_walk_counts_the_kernels_bound(monkeypatch):
    """The counters and the fused read take one bound: every call of the
    kernel in an engine's mixed and decode steps gets ``PG.page_bound`` of
    the step, the same for each layer, and the bounds it got, summed per
    layer, are the engine's ``read_pages_walked`` (as ``max(bound, 1)``)
    and ``read_pages_live``."""
    seen = []
    real = kernels.flash_decode_paged

    def spy(*args, **kw):
        jax.debug.callback(lambda b: seen.append(np.asarray(b)), args[6])
        return real(*args, **kw)

    monkeypatch.setattr(kernels, "flash_decode_paged", spy)
    reqs = REQS[:2]
    eng, _ = serve("stablelm-1.6b", chunked=True, attention="fused",
                   reqs=reqs)
    n_layers = get_config("stablelm-1.6b").reduced().n_layers
    st = eng.stats
    assert len(seen) == st["segments"] * SEGMENT * n_layers
    bounds = np.stack(seen).reshape(-1, n_layers, N_SLOTS)
    assert (bounds == bounds[:, :1]).all()      # one bound a step
    steps = bounds[:, 0]
    assert ((0 <= steps) & (steps <= MAX_PAGES)).all()
    assert np.maximum(steps, 1).sum() == st["read_pages_walked"]
    assert steps.sum() == st["read_pages_live"] == hand_count(True, reqs)
    assert (steps > 0).sum() == hand_steps(True, reqs)
    # and the helper's bound for a slot is its context's pages
    ctx = np.array([0, 1, PAGE, PAGE + 1, MAX_SEQ + PAGE])
    np.testing.assert_array_equal(
        PG.page_bound(ctx, ctx > 0, PAGE, MAX_PAGES),
        [0, 1, 1, 2, MAX_PAGES])
