"""A whole run of the harness on the committed qwen2-7b-l14 file at smoke
widths with its grouping kept, GQA 7:1 (14 query heads and 2 KV heads of
16), on the CPU: correct against the float32 reference on the direct and
the staged write path, the rebuilt work equal to the engine's counters,
and ``read_live_pages`` a share of the walk."""
import io
import json

import pytest

from bench.lib import harness, serving
from bench.tests.tiny import ROOT, TINY_LIMIT, conf, make_root

ARGS = ["--workload", "tiny.chat", "--seconds", "2", "--trace", "1"]


@pytest.fixture(scope="module", params=["direct", "staged"])
def root(request, tmp_path_factory):
    dst = make_root(tmp_path_factory.mktemp(f"qwen2-{request.param}"))
    c = conf("qwen2")
    c.update(name="tiny-qwen2-g7", hidden_size=224, num_attention_heads=14,
             num_key_value_heads=2)
    c["engine"]["path"] = request.param
    (dst / "bench" / "configs" / "tiny.json").write_text(json.dumps(c))
    return dst


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # the run would switch JAX's persistent cache on for the whole worker
    monkeypatch.setattr(harness, "enable_cache", lambda root: "off")


def test_qwen2_g7_run_is_correct_and_counted(root, monkeypatch):
    seen = {}
    accounting = serving.Accounting

    def keep_acct(*a):
        seen["acct"] = accounting(*a)
        return seen["acct"]

    monkeypatch.setattr(serving, "Accounting", keep_acct)
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(ARGS + ["--seed", str(2 ** 33 + 5)], root=root,
                     require_chip=False, out=out, err=err)
    assert rc == 0, err.getvalue()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    conf_used = json.loads((root / "bench" / "configs" / "tiny.json")
                           .read_text())
    assert (conf_used["num_attention_heads"]
            // conf_used["num_key_value_heads"]) == 7
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] <= TINY_LIMIT
    assert seen["acct"].mismatched == []
    metrics = res["metrics"]
    assert 0 < metrics["read_live_pages.chat"]["value"] <= 100
    assert metrics["window_compiles.chat"]["value"] == 0
    # the write path the configuration names is the one the rows took
    staged = metrics["staged_share.chat"]["value"]
    assert (staged > 0) if conf_used["engine"]["path"] == "staged" else (
        staged == 0)


def test_read_live_pages_reader():
    from types import SimpleNamespace

    read = harness.reader(ROOT, "read_live_pages.chat")
    ctx = SimpleNamespace(stats_window={"read_pages_walked": 512,
                                        "read_pages_live": 128})
    assert read(ctx) == 25.0
    # a program that keeps no such counters (or walked nothing): no reading
    assert read(SimpleNamespace(stats_window={"direct_writes": 3})) is None
    assert read(SimpleNamespace(stats_window={"read_pages_walked": 0,
                                              "read_pages_live": 0})) is None
