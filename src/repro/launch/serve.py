"""Serving launcher: continuous batching with the TLMAC serve linears.

    PYTHONPATH=src python -m repro.launch.serve --arch codeqwen1.5-7b
    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-350m --smoke

Paged-capable archs (``lm.supports_paged``) are served by
``PagedServeLoop`` — the production path: paged KV pool, fixed-shape
chunked prefill, prefix cache.  Archs whose state cannot be paged
(recurrent, enc-dec) fall back to the dense-cache ``ServeLoop``.
Weights are random, made from ``--seed``; so are the prompts.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve.loop import Request, ServeLoop
from repro.serve.paged import PagedServeLoop

# (min prompt, max prompt, s_max, prefill chunk) at full size / --smoke.
# Full size: prompts of 128-512 tokens in a 1024-token slot, prefilled
# in 256-token chunks, so longer prompts take two chunks and the later
# one attends to pages the first wrote.
FULL_SHAPE = (128, 512, 1024, 256)
SMOKE_SHAPE = (4, 16, 64, 16)


def make_requests(cfg, n: int, min_len: int, max_len: int, max_new: int,
                  seed: int) -> List[Request]:
    """``n`` random prompts with lengths uniform in [min_len, max_len]."""
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab, size=int(
                    rng.integers(min_len, max_len + 1))).astype(np.int32),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def build_loop(params, cfg, *, slots: int, s_max: int, page_size: int = 16,
               chunk: int = 16):
    """The serve loop for ``cfg``: paged where every block can page."""
    if lm.supports_paged(cfg):
        return PagedServeLoop(params, cfg, batch_slots=slots, s_max=s_max,
                              page_size=page_size, chunk=chunk)
    return ServeLoop(params, cfg, batch_slots=slots, s_max=s_max)


def init_params(cfg, seed: int):
    """Random serve-path weights for ``cfg`` from ``seed``, built in one
    compiled program (eager init dispatches every op of every layer)."""
    return jax.jit(lambda key: lm.init_lm(key, cfg, purpose="serve")[0])(
        jax.random.PRNGKey(seed))


def serve(loop, requests: List[Request]) -> Tuple[List[Request], float]:
    """Serve ``requests`` to completion: (finished, wall seconds)."""
    for r in requests:
        loop.submit(r)
    t0 = time.perf_counter()
    done = loop.run()
    return done, time.perf_counter() - t0


def main(argv: Optional[List[str]] = None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's smoke_config widths (CPU-sized)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve-impl", default=None,
                    choices=[None, "dense", "int8", "tlmac"])
    args = ap.parse_args(argv)

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.serve_impl:
        cfg = dataclasses.replace(cfg, serve_impl=args.serve_impl)
    min_len, max_len, s_max, chunk = SMOKE_SHAPE if args.smoke else FULL_SHAPE

    params = init_params(cfg, args.seed)
    loop = build_loop(params, cfg, slots=args.slots, s_max=s_max,
                      chunk=chunk)
    done, dt = serve(loop, make_requests(cfg, args.requests, min_len,
                                         max_len, args.max_new, args.seed))
    total_new = sum(len(r.output) for r in done)
    print(f"{type(loop).__name__}: served {len(done)} requests, "
          f"{total_new} tokens, {dt:.2f}s "
          f"({total_new / max(dt, 1e-9):.1f} tok/s, impl={cfg.serve_impl})")
    for r in done[:4]:
        print(f"  req {r.rid}: prompt[{len(r.prompt)}] -> {r.output[:8]}...")


if __name__ == "__main__":
    main()
