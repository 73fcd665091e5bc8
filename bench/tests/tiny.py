"""A benchmark root at smoke widths, for CPU tests: the real readers and
the real generator, a two-layer model and short requests."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# the program computes in float32 at these widths, so its served tokens sit
# on the float32 reference's best to rounding (~1e-6); an altered token or
# float8 arithmetic misses by 0.05 and more
TINY_LIMIT = 0.01


def conf(family: str) -> dict:
    base = "stablelm-1.6b" if family == "stablelm" else "qwen2-7b-l14"
    c = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    c.update(name=f"tiny-{family}", num_hidden_layers=2, hidden_size=64,
             num_attention_heads=4, intermediate_size=128, vocab_size=512,
             torch_dtype="float32",
             num_key_value_heads=4 if family == "stablelm" else 2,
             engine={"page_size": 8, "chunk_size": 8, "segment_len": 4,
                     "ring_size": 2, "path": "adaptive"})
    return c


def make_root(dst: Path) -> Path:
    (dst / "bench" / "configs").mkdir(parents=True)
    (dst / "bench" / "traffic").mkdir()
    (dst / "bench" / "cells").mkdir()
    shutil.copytree(BENCH / "metrics", dst / "bench" / "metrics")
    c = conf("stablelm")
    (dst / "bench" / "configs" / "tiny.json").write_text(json.dumps(c))
    chat = json.loads((BENCH / "traffic" / "chat.json").read_text())
    chat.update(ramp_s=0.5, prompt_tokens={
        "dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4, "max": 72},
        output_tokens={"dist": "lognormal", "median": 8, "sigma": 0.8,
                       "min": 2, "max": 24})
    (dst / "bench" / "traffic" / "chat.json").write_text(json.dumps(chat))
    (dst / "bench" / "cells" / "tiny.chat.json").write_text(json.dumps(
        {"n_slots": 4, "rate_rps": 6.0, "logit_gap_limit": TINY_LIMIT}))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "tiny", "source": "smoke widths",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "CPU tests"}]
    b["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                       "traffic": "chat", "chips": 1, "why": "CPU tests"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny.chat"] if m["name"].endswith(".chat")
                              or "ttft" in m["name"] or "tpot" in m["name"]
                              else ["none"])
    (dst / "BENCHMARK.json").write_text(json.dumps(b))
    return dst
