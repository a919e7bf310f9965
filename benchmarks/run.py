"""Benchmark entrypoint: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV blocks per benchmark.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer annealing iterations (CI mode)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--bench-json", default="BENCH_kernels.json",
                    help="machine-readable kernel-bench output "
                         "(impl -> us/call + auto-vs-xla speedup)")
    ap.add_argument("--serve-json", default="BENCH_serve.json",
                    help="machine-readable serve-bench output (paged vs "
                         "dense decode latency + compile counts)")
    args = ap.parse_args()

    from benchmarks import (
        fig5_weight_redundancy,
        fig6_annealing,
        fig8_full_model,
        kernel_bench,
        roofline,
        serve_bench,
        table1_block_area,
        tlmac_memory,
    )

    iters = 300 if args.fast else None
    benches = [
        ("fig5_weight_redundancy", lambda: fig5_weight_redundancy.run(
            anneal_iters=iters or 1500)),
        ("fig6_annealing", lambda: fig6_annealing.run(
            anneal_iters=iters or 20000)),
        ("table1_block_area", lambda: table1_block_area.run(
            anneal_iters=iters or 4000)),
        ("fig8_full_model", lambda: fig8_full_model.run(
            anneal_iters=iters or 1500)),
        ("tlmac_memory", tlmac_memory.run),
        ("kernel_bench", lambda: kernel_bench.run(json_path=args.bench_json)),
        ("serve_bench", lambda: serve_bench.run(json_path=args.serve_json,
                                                fast=args.fast)),
        ("roofline", roofline.run),
    ]
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        print(f"\n==== {name} ====", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
            print(f"name={name},us_per_call={int((time.perf_counter()-t0)*1e6)},derived=ok")
        except Exception as e:
            print(f"name={name},us_per_call=-1,derived=ERROR:{e}")
            raise


if __name__ == "__main__":
    main()
