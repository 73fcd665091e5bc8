"""Tracing of the serving loop (``repro.serve.trace``): the per-request
admission instant, one bounded record per scan segment naming the program
that ran, named segment programs with the KV-cache scopes in their
lowered text, and host spans that never stay open across a ``yield``."""
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.paths import SpecConfig
from repro.data import RequestQueue
from repro.models import build_model
from repro.models.sampling import SamplingParams
from repro.serve import Engine, EngineConfig
from repro.serve import trace as T

PLENS = (10, 5, 7, 12)
SPANS = {"engine.retire", "engine.admit", "engine.topup", "engine.dispatch",
         "engine.readback", "engine.emit"}


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps which spans
    are open and which were ever opened."""

    def __init__(self):
        self.open, self.seen = [], set()

    def __call__(self, name):
        spans = self

        class Span:
            def __enter__(self):
                spans.open.append(name)
                spans.seen.add(name)

            def __exit__(self, *exc):
                spans.open.remove(name)

        return Span()


def _serve(spec: bool, monkeypatch):
    """Serve four requests through ``Engine.serve_stream``; returns the
    engine, the kinds of the segment programs called, in order, the open
    span names at each event, and the spans seen."""
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0), 48)
    eng = Engine.from_config(EngineConfig(
        max_seq=48, n_slots=2, page_size=4, chunked=True, chunk_size=3,
        segment_len=4, ring_size=4, hot_threshold=1, path="adaptive",
        spec=SpecConfig(enabled=True, k=2) if spec else None),
        model, params)
    sch = eng.scheduler
    ran = []

    def spy(kind, mode, build=sch._build_segment):
        fn = build(kind, mode)

        def call(*args):
            ran.append(kind)
            return fn(*args)
        call.lower = fn.lower
        return call
    monkeypatch.setattr(sch, "_build_segment", spy)
    spans = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
    q = RequestQueue()
    for i, plen in enumerate(PLENS):
        q.submit(np.full((plen,), i + 1, np.int32),
                 params=SamplingParams(temperature=0.0, max_tokens=6))
    open_at_events = [list(spans.open) for _ in eng.serve_stream(q)]
    return eng, ran, open_at_events, spans.seen


@pytest.fixture(scope="module", params=[False, True], ids=["decode", "spec"])
def served(request):
    with pytest.MonkeyPatch.context() as mp:
        yield (request.param,) + _serve(request.param, mp)


def test_request_instants_are_ordered(served):
    _, eng, _, _, _ = served
    sch = eng.scheduler
    assert set(sch.req_admit) == set(sch.req_arrival) == set(range(len(PLENS)))
    for rid, arrival in sch.req_arrival.items():
        assert arrival <= sch.req_admit[rid] <= sch.first_token_t[rid]


def test_one_record_per_segment_naming_its_program(served):
    spec, eng, ran, _, _ = served
    recs = eng.segment_records
    assert [r.seg for r in recs] == list(range(1, eng.stats["segments"] + 1))
    assert [r.kind for r in recs] == ran
    assert set(ran) == {"mixed", "spec" if spec else "decode"}
    assert all(r.device_s > 0 and r.host_s > 0 for r in recs)


def test_no_span_is_open_across_a_yield(served):
    _, _, _, open_at_events, seen = served
    assert open_at_events and all(o == [] for o in open_at_events)
    assert seen == SPANS


def test_segment_programs_are_named_and_scoped(served):
    spec, eng, _, _, _ = served
    sch = eng.scheduler
    on = jax.numpy.ones((sch.cfg.n_slots,), bool)
    extra = {"mixed": sch.prompts, "decode": None,
             "spec": (sch.draft_params, sch.draft_cache)}
    lowered = {kind: fn.lower(sch.params, sch.cache, sch.slots,
                              sch.mon_state, on, extra[kind])
               for (kind, _), fn in sch._segments.items()}
    assert set(lowered) == {"mixed", "spec" if spec else "decode"}
    for kind, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_segment_{kind} ")
        text = low.as_text(debug_info=True)
        def scoped(scope):
            return re.search(rf'loc\("([^"]*/)?{scope}/', text) is not None

        for scope in ("kv_view", "kv_write", "attention", "mlp", "head"):
            assert scoped(scope), (kind, scope)
        # the spec segment leaves its staged writes for the next drain
        assert scoped("kv_drain") == (kind != "spec"), kind


def test_records_stay_bounded():
    log = T.SegmentLog(maxlen=3)
    for seg in range(1, 8):
        with log.phase("engine.admit"):
            pass
        log.record(seg, "decode", 0.5)
    assert [r.seg for r in log.records] == [5, 6, 7]
    assert log.records.maxlen == 3
    assert all(0 < r.host_s < 0.5 for r in log.records)
    assert T.SegmentLog().records.maxlen == T.RECORDS


def test_reset_clears_the_records(served):
    _, eng, _, _, _ = served
    assert eng.segment_records and eng.scheduler.req_admit
    assert eng.scheduler.segment_log.records.maxlen == T.RECORDS
    eng.reset()
    assert eng.segment_records == () and eng.scheduler.req_admit == {}
