"""The harness finds each configuration, traffic mix, cell and metric
reader by name, and ``bench/run.py`` refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench.lib import harness
from bench.tests.tiny import ROOT


def test_finds_files_by_name(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "cells").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "dummy.json").write_text('{"k": 1}')
    (tmp_path / "bench" / "traffic" / "burst.json").write_text('{"t": 2}')
    (tmp_path / "bench" / "cells" / "dummy.burst.json").write_text('{"c": 3}')
    (tmp_path / "bench" / "metrics" / "foo.py").write_text(
        "def read(ctx):\n    return ctx * 2\n")
    (tmp_path / "bench" / "metrics" / "bar.x.py").write_text(
        "def read(ctx):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "dummy", "file": "bench/configs/dummy.json"}],
        "workloads": [{"name": "dummy.burst", "config": "dummy",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "foo", "unit": "s"},
                       {"name": "foo.only", "unit": "s",
                        "workloads": ["other"]}],
        "per_layer": [{"name": "bar.x", "unit": "%",
                       "workloads": ["dummy.burst"]}]}))
    c = harness.load_cell(tmp_path, "dummy.burst")
    assert (c.conf, c.traffic, c.cell) == ({"k": 1}, {"t": 2}, {"c": 3})
    assert [m["name"] for m in harness.metrics_for(c.bench, "dummy.burst",
                                                   False)] == ["foo"]
    assert [m["name"] for m in harness.metrics_for(c.bench, "dummy.burst",
                                                   True)] == ["bar.x"]
    assert harness.reader(tmp_path, "foo")(21) == 42
    assert harness.reader(tmp_path, "foo.chat")(2) == 4     # prefix reader
    assert harness.reader(tmp_path, "bar.x")(0) is None     # own file first
    with pytest.raises(harness.Refused):
        harness.reader(tmp_path, "missing")
    with pytest.raises(harness.Refused):
        harness.load_cell(tmp_path, "nope")


def test_every_committed_metric_and_cell_has_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(ROOT, m["name"]))
    for wl in bench["workloads"]:
        c = harness.load_cell(ROOT, wl["name"])
        assert c.cell["n_slots"] >= 1 and c.cell["logit_gap_limit"] > 0


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "stablelm-1.6b.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=240)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "metrics" not in p.stdout
