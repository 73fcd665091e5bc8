"""The engine's account of the paged read's walk (``read_pages_walked``,
``read_pages_live`` in ``Engine.stats``), counted on the device inside
the decode and mixed segment programs.

Each scan step the read's grid visits every page of every slot's table
in one layer: ``n_slots x max_pages``. The live pages are those holding
context of an active query, and a request's contexts do not depend on how
its steps fall into segments: each prompt chunk ends at ``min(plen,
chunk x (k + 1))``, and the i-th decode step after the prompt attends to
``plen + i`` rows. So a fixed queue has a hand count.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import RequestQueue
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


def hand_count(chunked: bool) -> int:
    """Live pages over every step of every request: its prompt chunks
    (chunked engine only; the other prefills at admission, outside the
    scan), then its decode steps."""
    live = 0
    for plen, n in REQS:
        if chunked:
            live += sum(_pages(min(plen, CHUNK * (k + 1)))
                        for k in range(-(-plen // CHUNK)))
        live += sum(_pages(plen + i) for i in range(1, n))
    return live


def serve(arch: str, chunked: bool):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), MAX_SEQ)
    eng = Engine.from_config(EngineConfig(
        max_seq=MAX_SEQ, n_slots=N_SLOTS, page_size=PAGE, chunked=chunked,
        chunk_size=CHUNK, segment_len=SEGMENT, ring_size=2,
        path="adaptive"), model, params)
    q = RequestQueue()
    rng = np.random.default_rng(7)
    for plen, n in REQS:
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


@pytest.mark.parametrize("arch", list(TOKENS))
@pytest.mark.parametrize("chunked", [True, False])
def test_read_walk_counts_and_tokens(arch, chunked):
    eng, tokens = serve(arch, chunked)
    st = eng.stats
    kinds = {r.kind for r in eng.segment_records}
    # the chunked engine runs mixed segments, then decode; the other
    # decode segments alone
    assert kinds == ({"mixed", "decode"} if chunked else {"decode"})
    assert st["read_pages_walked"] == (
        st["segments"] * SEGMENT * N_SLOTS * MAX_PAGES)
    assert st["read_pages_live"] == hand_count(chunked)
    assert 0 < st["read_pages_live"] < st["read_pages_walked"]
    assert tokens == TOKENS[arch]


def test_read_walk_per_segment_kind(monkeypatch):
    """Each segment program adds its own steps' walk: the per-segment
    deltas of a chunked run, split by the program that ran, add up to the
    whole, and every segment walks ``segment_len`` steps."""
    from repro.serve.scheduler import BatchedServeEngine

    deltas = []
    readback = BatchedServeEngine._readback

    def keep(self, kind, stats, *rest):
        deltas.append((kind, [int(x) for x in np.asarray(stats)[4:]]))
        return readback(self, kind, stats, *rest)

    monkeypatch.setattr(BatchedServeEngine, "_readback", keep)
    eng, _ = serve("qwen2-7b", chunked=True)
    per_step = N_SLOTS * MAX_PAGES
    for kind in ("mixed", "decode"):
        mine = [d for k, d in deltas if k == kind]
        assert mine and all(w == SEGMENT * per_step for w, _ in mine)
        assert all(0 < live <= w for w, live in mine)
    assert sum(live for _, (_, live) in deltas) == eng.stats["read_pages_live"]


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
    assert st["read_pages_walked"] == (
        st["segments"] * SEGMENT * N_SLOTS * MAX_PAGES)
    assert 0 < st["read_pages_live"] < st["read_pages_walked"]
