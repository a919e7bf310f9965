"""Kernel micro-bench: lookup GEMM impls vs dense int matmul (wall time
on CPU is illustrative only; the structural counts are the deliverable).

Two shapes of the same compiled layer are timed:
- 'decode'  (M=8)  — the paper's regime: static weights, repeated
                     small-batch MACs (ServeLoop decodes at the slot
                     count); this is the headline row
- 'prefill' (M=64) — the larger-batch end of the serve path

``impl='auto'`` exercises the shape-keyed autotuner (kernels/autotune.py):
the first call on each shape tunes on the concrete operands and
persists the winner, subsequent calls dispatch from the cache.  The
headline ``speedup_auto_vs_xla`` is measured with interleaved A/B reps
(common.ab_ratio) so shared-runner load noise cancels.  ``run(json_path
=...)`` emits machine-readable ``BENCH_kernels.json`` so the perf
trajectory is tracked across PRs.

The **roofline scenario** records bytes-moved for the two serving hot
kernels — the fused TLMAC megakernel and the paged flash-decode — as
(a) a compulsory-traffic model (each operand/output touched exactly
once; for flash decode only the LIVE pages count, the block table's
whole point) and (b) XLA's measured ``bytes accessed`` from compiled
cost analysis.  The ratio is the kernel's traffic multiplier over the
roofline floor: the number the paper's scalability argument budgets
against, now tracked per PR in BENCH_kernels.json.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import ab_ratio, csv_row, provenance, timer
from repro.core.tlmac import compile_layer
from repro.kernels import autotune, ops

BENCH_SHAPE = dict(B_w=3, B_a=3, G=4, K=256, N=256, d_p=64)
BATCHES = {"decode": 8, "prefill": 64}
# 'pallas-onehot' is excluded: its MXU-only addressing measures ~300
# ms/call vs 1-4 ms for everything else, so benching it burns ~2 min of
# wall-clock on a row that never wins.  It stays dispatchable via an
# explicit impl= (and joins via REPRO_TLMAC_BENCH_ONEHOT=1).
IMPLS = ("auto", "xla", "xla-kscan", "xla-flat", "pallas", "fused")


def _measured_bytes(fn, *args) -> float:
    """XLA's ``bytes accessed`` for one compiled call of ``fn`` (CPU
    cost analysis returns a list of per-computation dicts)."""
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    d = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(d.get("bytes accessed", float("nan")))


def _model_bytes(fn, *args) -> int:
    """Compulsory-traffic floor: every operand read once, every output
    written once — the roofline denominator."""
    out = jax.eval_shape(fn, *args)
    return int(sum(x.nbytes for x in args)
               + sum(o.size * o.dtype.itemsize
                     for o in jax.tree.leaves(out)))


def _roofline(plan, B_a, G, K, N, quiet):
    """Bytes-moved accounting for the two serving hot kernels (module
    docstring): model floor vs measured, per kernel."""
    from repro.kernels.flash_decode import flash_decode

    rng = np.random.default_rng(2)
    doc = {}

    # -- TLMAC megakernel (fused lookup GEMM), decode batch --
    a = jnp.asarray(rng.integers(0, 2**B_a, size=(BATCHES["decode"], K)))
    t = jnp.asarray(plan.table)
    e = jnp.asarray(plan.exec_idx)
    c = jnp.asarray(plan.step_cluster)
    fn = lambda a_, t_, e_, c_: ops.tlmac_matmul(
        a_, t_, e_, c_, B_a=B_a, G=G, N=N, impl="fused")
    model = _model_bytes(fn, a, t, e, c)
    meas = _measured_bytes(fn, a, t, e, c)
    doc["tlmac_megakernel"] = {
        "model_bytes": model, "measured_bytes": meas,
        "traffic_ratio": meas / model,
    }

    # -- paged flash-decode at uneven per-slot lengths --
    B, KV, rep, hd, P, MB = 4, 2, 4, 64, 16, 8
    n_pages = B * MB + 1
    kp = jnp.asarray(rng.normal(size=(n_pages, P, KV, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, P, KV, hd)), jnp.float32)
    bt = jnp.asarray(np.stack(
        [1 + b * MB + np.arange(MB) for b in range(B)]).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(B, KV, rep, hd)), jnp.float32)
    lens = np.array([24, 70, 128, 9], np.int32)
    fd = lambda q_, kp_, vp_, bt_, l_: flash_decode(
        q_, kp_, vp_, bt_, l_, n_splits=2)
    largs = (q, kp, vp, bt, jnp.asarray(lens))
    # the model floor counts only LIVE pages' K/V traffic — the block
    # table's decoupling of capacity from traffic is the claim
    live_pages = int(sum(-(-int(l) // P) for l in lens))
    page_bytes = P * KV * hd * 4
    out_sh = jax.eval_shape(fd, *largs)
    model = int(q.nbytes + 2 * live_pages * page_bytes + bt.nbytes
                + lens.nbytes
                + sum(o.size * o.dtype.itemsize
                      for o in jax.tree.leaves(out_sh)))
    meas = _measured_bytes(fd, *largs)
    doc["paged_flash_decode"] = {
        "model_bytes": model, "measured_bytes": meas,
        "traffic_ratio": meas / model,
        "live_pages": live_pages, "total_pages": n_pages,
    }
    if not quiet:
        csv_row("roofline", "model_bytes", "measured_bytes", "ratio")
        for k, v in doc.items():
            csv_row(k, v["model_bytes"], f"{v['measured_bytes']:.0f}",
                    f"{v['traffic_ratio']:.2f}x")
    return doc


def run(quiet=False, json_path=None):
    autotune.reset_stats()   # counters below reflect THIS run only
    rng = np.random.default_rng(0)
    B_w, B_a, G = BENCH_SHAPE["B_w"], BENCH_SHAPE["B_a"], BENCH_SHAPE["G"]
    K, N = BENCH_SHAPE["K"], BENCH_SHAPE["N"]
    w = rng.integers(-4, 4, size=(K, N))
    plan = compile_layer(w, B_w=B_w, B_a=B_a, G=G,
                         d_p=BENCH_SHAPE["d_p"], anneal_iters=500)
    t = jnp.asarray(plan.table)
    e = jnp.asarray(plan.exec_idx)
    c = jnp.asarray(plan.step_cluster)
    out = {"us_per_call": {}, "speedup_auto_vs_xla": {}}
    if not quiet:
        csv_row("impl", "us_per_call")
    for label, M in BATCHES.items():
        a = jnp.asarray(rng.integers(0, 2**B_a, size=(M, K)))
        us = {}
        _, us["dense_int"] = timer(
            lambda: ops.dense_int_matmul(a, jnp.asarray(w)).block_until_ready()
        )
        _, us["bitserial"] = timer(
            lambda: ops.bitserial_matmul(
                a, jnp.asarray(w), B_a).block_until_ready()
        )
        impls = IMPLS + (
            ("pallas-onehot",)
            if os.environ.get("REPRO_TLMAC_BENCH_ONEHOT") == "1" else ()
        )
        # 'auto' first: its warmup call runs the tuner once and persists
        # the winner; the timed reps then measure the cached dispatch.
        for impl in impls:
            _, us[impl] = timer(
                lambda impl=impl: ops.tlmac_matmul(
                    a, t, e, c, B_a=B_a, G=G, N=N, impl=impl
                ).block_until_ready(),
                reps=9,
            )
        # headline: autotuned dispatch vs the previous hard-coded
        # default, interleaved so load noise hits both equally
        us_auto, us_xla = ab_ratio(
            lambda: ops.tlmac_matmul(
                a, t, e, c, B_a=B_a, G=G, N=N, impl="auto"
            ).block_until_ready(),
            lambda: ops.tlmac_matmul(
                a, t, e, c, B_a=B_a, G=G, N=N, impl="xla"
            ).block_until_ready(),
        )
        speedup = us_xla / us_auto
        out["us_per_call"][label] = us
        out["speedup_auto_vs_xla"][label] = speedup
        if not quiet:
            for k, v in us.items():
                csv_row(f"{k}[{label} M={M}]", f"{v:.0f}")
            csv_row(f"speedup_auto_vs_xla[{label}]", f"{speedup:.2f}x")
    roofline = _roofline(plan, B_a, G, K, N, quiet)
    out["roofline"] = roofline
    if json_path:
        cfgs = {}
        for label, M in BATCHES.items():
            key = autotune.shape_key(
                M, K, N, B_a=B_a, G=G, D_p=int(plan.exec_idx.shape[1]),
                R=int(np.prod(plan.table.shape[:-1])),
            )
            cfgs[label] = autotune.lookup(key)
        doc = {
            "provenance": provenance(),
            "shape": BENCH_SHAPE,
            "batches": BATCHES,
            "us_per_call": out["us_per_call"],
            "speedup_auto_vs_xla": out["speedup_auto_vs_xla"],
            "roofline": roofline,
            "auto_config": cfgs,
            # no absolute cache path here: the artifact is git-tracked
            # and machine-local paths would churn it per contributor
            "autotune_cache_overridden": bool(os.environ.get(
                autotune.CACHE_ENV)),
            # WHICH keys this run re-tuned (vs served from the cache):
            # "overridden: true" alone left CI artifacts undiagnosable —
            # a cold cache re-sweeps every shape, a restored one should
            # show zero tuned_keys and pure hits
            "autotune": autotune.snapshot_stats(),
        }
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        if not quiet:
            csv_row("json", json_path)
    return out


def main():
    run(json_path="BENCH_kernels.json")


if __name__ == "__main__":
    main()
