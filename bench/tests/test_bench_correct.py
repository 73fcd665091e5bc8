"""A whole run of the harness at smoke widths on the CPU (the look for a
chip skipped): correct as served, not correct when a served token is
altered where the engine produces it, and not correct for the float8
control; and nothing compiles inside the window."""
import io
import json

import pytest

from bench.lib import harness
from bench.tests.tiny import TINY_LIMIT, make_root

ARGS = ["--workload", "tiny.chat", "--seconds", "2"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tinyroot"))


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the run would switch JAX's persistent cache on for the whole worker
    monkeypatch.setattr(harness, "enable_cache", lambda root: "off")


def run(root, seed, trace=0, control=False):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(ARGS + ["--seed", str(seed), "--trace", str(trace)],
                     root=root, require_chip=False, out=out, err=err,
                     control=control)
    assert rc == 0, err.getvalue()
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), out.getvalue(), err.getvalue()


def test_correct_as_served_and_checks_printed_last(root):
    res, _, err = run(root, 2 ** 35 + 1)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] <= TINY_LIMIT
    assert err.strip().splitlines()[-1].startswith("check ")


def test_altered_token_is_not_correct(root, monkeypatch):
    from repro.serve import api

    serve_stream = api.Engine.serve_stream

    def altered(self, queue, **kw):
        for ev in serve_stream(self, queue, **kw):
            if len(ev.tokens):
                ev.tokens = ev.tokens.copy()
                ev.tokens[0] = (ev.tokens[0] + 1) % self.model.cfg.vocab
            yield ev

    monkeypatch.setattr(api.Engine, "serve_stream", altered)
    res, _, _ = run(root, 7)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_control_is_not_correct(root):
    # the float8 control in the program's place, through the run's own
    # comparison: not correct, while the program's gap on the same sample
    # stays within the limit
    res, out, _ = run(root, 11, control=True)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > TINY_LIMIT
    program = float(out.split("program: logit_gap ")[1].split()[0])
    assert program <= TINY_LIMIT


def test_traced_run_compiles_nothing_in_the_window(root):
    res, _, _ = run(root, 13, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["window_compiles.chat"]["value"] == 0
    assert "staged_share.chat" in res["metrics"]


def test_rebuilt_work_matches_the_engine_counters(root, monkeypatch):
    from bench.lib import serving

    seen = {}
    accounting, pick_sample = serving.Accounting, harness.pick_sample

    def keep_acct(*a):
        seen["acct"] = accounting(*a)
        return seen["acct"]

    def keep_logs(logs, seed):
        seen["logs"] = logs
        return pick_sample(logs, seed)

    monkeypatch.setattr(serving, "Accounting", keep_acct)
    monkeypatch.setattr(harness, "pick_sample", keep_logs)
    run(root, 17)
    acct, logs = seen["acct"], seen["logs"]
    assert acct.mismatched == []
    total = acct.total(1, acct.seen_seg)
    finished = [lg for lg in logs.values() if lg.t_done is not None]
    # every finished request: its prompt rows, and one row per output token
    # but the last
    assert total.rows >= sum(lg.plen + len(lg.tokens) - 1 for lg in finished)
    assert total.logit_rows >= sum(len(lg.tokens) for lg in finished)
