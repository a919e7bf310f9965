"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --smoke --steps 50 --ckpt-dir /tmp/ckpt

On a real TPU slice the same entrypoint builds the production mesh and
shards params/optimizer via the per-arch axes rules; on this CPU
container use ``--smoke`` (reduced config, 1 device).  Fault tolerance:
``--preempt-at`` simulates preemptions; the runner restarts from the
latest checkpoint (see repro/train/ft.py).
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import SHAPES, get_config, smoke_config
from repro.data.pipeline import SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.train.ft import FaultTolerantRunner, PreemptionSchedule
from repro.train.trainer import TrainConfig, TrainLoop


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--preempt-at", type=int, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.name == "minicpm-2b" and args.schedule == "cosine":
        args.schedule = "wsd"  # the arch's own schedule

    data = SyntheticLMData(
        vocab=cfg.vocab, seq_len=args.seq_len, global_batch=args.batch,
        seed=args.seed,
        enc_len=args.seq_len // 2 if cfg.family == "audio" else 0,
        frontend=cfg.frontend, frontend_len=cfg.frontend_len,
    )
    tc = TrainConfig(
        lr=args.lr, total_steps=args.steps, schedule=args.schedule,
        accum_steps=args.accum, compress=args.compress_grads,
        adamw=AdamWConfig(state_dtype=cfg.opt_state_dtype),
    )
    loop = TrainLoop(cfg, tc, data, ckpt_dir=args.ckpt_dir,
                     ckpt_interval=args.ckpt_interval)

    if args.preempt_at and args.ckpt_dir:
        runner = FaultTolerantRunner(loop, args.ckpt_dir)
        hook = PreemptionSchedule(args.preempt_at)
        params, opt = runner.run(args.steps, seed=args.seed, step_hook=hook)
        print(f"finished with {runner.restarts} restarts")
    else:
        params, opt = loop.init(args.seed)
        params, opt = loop.run(params, opt, num_steps=args.steps)

    for m in loop.metrics_log[:: max(len(loop.metrics_log) // 20, 1)]:
        print(f"step {m['step']:5d} loss {m['loss']:.4f} "
              f"lr {m['lr']:.2e} |g| {m['gnorm']:.3f} {m['wall_s']*1e3:.0f}ms")
    if loop.metrics_log:
        first, last = loop.metrics_log[0], loop.metrics_log[-1]
        print(f"loss: {first['loss']:.4f} -> {last['loss']:.4f}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(loop.metrics_log, f)


if __name__ == "__main__":
    main()
