"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

One front door (``repro.serve.Engine``), two workload shapes:

* default — serve ``--batch`` same-length prompts concurrently (one slot
  per prompt) and report throughput: the batched-generate workload.
* ``--batched`` — continuous batching: a stream of ``--requests``
  synthetic requests admitted FIFO into ``--slots`` serving slots,
  decoded in jitted scan segments with EOS/max-len retirement between
  them (optionally ``--chunked`` mixed-phase prefill).

The write path and routing policy are registry names
(``repro.core.paths`` / ``repro.core.policy``); sampling is per-request
``SamplingParams``. The model is built at its published widths (random
weights from a seed); ``--reduced`` swaps in the tiny smoke widths the CPU
recipes use.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..data import synthetic_requests
from ..models import media_spec, needs_media
from ..models.sampling import SamplingParams
from ..serve import Engine, EngineConfig, build_model_and_params
from ..serve.scheduler import paged_capable
from .device import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny smoke widths (CPU recipes) instead of the "
                         "arch's published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--write-mode", "--path", dest="path", default="adaptive",
                    help="registered WritePath name (direct/staged/"
                         "adaptive/... — repro.core.paths)")
    ap.add_argument("--policy", default=None,
                    help="registered RoutingPolicy name (default: the "
                         "path's default policy)")
    ap.add_argument("--temperature", type=float, default=None,
                    help="sampling temperature (default: greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request sampling seed")
    ap.add_argument("--ring-size", type=int, default=8)
    ap.add_argument("--batched", action="store_true",
                    help="continuous batching over the paged KV pool")
    ap.add_argument("--requests", type=int, default=16,
                    help="(--batched) synthetic request count")
    ap.add_argument("--slots", type=int, default=8,
                    help="(--batched) serving slots")
    ap.add_argument("--segment-len", type=int, default=16,
                    help="(--batched) decode steps per scan segment")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunked", action="store_true",
                    help="(--batched) admit immediately, prefill prompts "
                         "in chunks inside the decode scan (DESIGN.md §5)")
    ap.add_argument("--chunk-size", type=int, default=16,
                    help="(--chunked) prompt tokens per prefill chunk")
    ap.add_argument("--long-prompt-len", type=int, default=0,
                    help="(--batched) if > 0, every 4th request carries a "
                         "prompt of this length (mixed workload)")
    args = ap.parse_args()

    cfg, model, params = build_model_and_params(args.arch, args.max_seq,
                                                reduced=args.reduced)

    path = args.path
    if path != "direct" and not paged_capable(model):
        print(f"[serve] {cfg.name}: lanes layout is direct-only; "
              f"downgrading --write-mode {path} -> direct")
        path = "direct"
    sp = SamplingParams(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        seed=args.seed, max_tokens=args.gen_len,
    )

    if args.batched:
        media_shape = None
        if needs_media(cfg):
            media_shape = media_spec(cfg, 1, jnp.float32).shape[1:]
        plens = args.prompt_len
        if args.long_prompt_len:
            plens = [args.long_prompt_len] + [args.prompt_len] * 3
        queue = synthetic_requests(
            args.requests, plens, cfg.vocab, args.gen_len,
            media_shape=media_shape, params=sp,
        )
        eng = Engine.from_config(EngineConfig(
            max_seq=args.max_seq, n_slots=args.slots,
            segment_len=args.segment_len, path=path, policy=args.policy,
            page_size=args.page_size, ring_size=args.ring_size,
            chunked=args.chunked, chunk_size=args.chunk_size,
        ), model, params)
        t0 = time.perf_counter()
        outputs = eng.serve(queue)
        dt = time.perf_counter() - t0
        n_toks = sum(len(t) for t in outputs.values())
        mode = f"{eng.layout}, chunked" if args.chunked else eng.layout
        print(f"[{mode}] served {len(outputs)} requests / {n_toks} "
              f"tokens in {dt:.2f}s ({n_toks / dt:.1f} tok/s)")
        if eng.ttft:
            ms = sorted(v * 1e3 for v in eng.ttft.values())
            print(f"ttft: mean {sum(ms) / len(ms):.1f} ms, "
                  f"max {ms[-1]:.1f} ms")
        print(f"write-path stats: {eng.stats}")
        return

    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab, size=args.prompt_len)
               for _ in range(args.batch)]
    media = None
    if needs_media(cfg):
        media = [np.asarray(jax.random.normal(
            jax.random.key(2), media_spec(cfg, 1, jnp.float32).shape[1:]))
            for _ in range(args.batch)]

    eng = Engine.from_config(EngineConfig(
        max_seq=args.max_seq, n_slots=args.batch, path=path,
        policy=args.policy, ring_size=args.ring_size,
        page_size=args.page_size,
    ), model, params)
    t0 = time.perf_counter()
    comps = eng.generate(prompts, sp, media=media)
    dt = time.perf_counter() - t0
    n_toks = sum(c.n_tokens for c in comps)
    print(f"generated {len(comps)} x {args.gen_len} tokens in {dt:.2f}s "
          f"({n_toks / dt:.1f} tok/s)")
    print(f"write-path stats: {eng.stats}")


if __name__ == "__main__":
    main()
