"""One traced window of a cell, read by the serve loop's own host spans.

    python bench/tools/host_spans.py --workload <cell> --seed <n> \
        --seconds <s> [--keep DIR]

On the cell's chips: the set-up and the traced window of
``bench/run.py --trace 1`` (the same functions), then the window's trace
reduced twice: by ``bench/trace.py`` (busy time, the model-step and
lookup-GEMM scopes) and by ``bench/spans.py`` (the ``repro.serve.*``
spans, the host's own milliseconds per step, the idle gaps labelled by
those spans, the capture's clock against ``time.monotonic``).  Then the
cost of one ``repro.serve.*`` annotation with no profiler session and
with one.  Prints one JSON object; ``--keep`` copies the ``.xplane.pb``
there.  No correctness check runs: ``bench/run.py`` is the benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as bench_run  # noqa: E402

SCOPES = ("repro.lm.decode_step_paged", "repro.lm.prefill_chunk",
          "repro.lookup_gemm")
SPANS = tuple("repro.serve." + s for s in (
    "step", "admit", "prefill_chunk", "decode_step", "verify_step",
    "cow_copy", "swap_gather", "swap_scatter", "sync"))


def annotation_ns(n: int) -> float:
    """Mean ns of one empty ``repro.serve.*`` span, as the loop opens
    them (``repro.serve.step`` with its argument)."""
    from repro.serve.telemetry import annotate

    t = time.perf_counter()
    for _ in range(n):
        with annotate("repro.serve.step", monotonic_s=time.monotonic()):
            pass
    return (time.perf_counter() - t) / n * 1e9


def annotation_cost() -> dict:
    import jax

    off = annotation_ns(200_000)
    out = tempfile.mkdtemp(prefix="bench_spans_cost_")
    jax.profiler.start_trace(out)
    try:
        on = annotation_ns(20_000)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(out, ignore_errors=True)
    return {"no_session_ns": off, "session_ns": on}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = bench_run.find_cell(json.load(f), args.workload)
    chips = int(cell["chips"])
    bench_run.check_devices(chips)
    import jax
    import numpy as np

    from bench import spans, trace

    bench_run.enable_caches()
    ctx = bench_run.prepare(cell, args.seed)
    loop, drv = ctx.loop, ctx.drv
    drv.setup()
    jax.block_until_ready(loop.caches)
    dec0, run0 = loop.decode_steps, loop.prefill_tokens_run
    t0, t1, path, tmp = bench_run.traced_window(drv, args.seconds)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        shutil.copy(path, os.path.join(
            args.keep, f"{args.workload}.{args.seed}.xplane.pb"))
    red = trace.reduce(path, chips=chips, scopes=SCOPES)
    host = spans.reduce(path, chips=chips, spans=SPANS)
    shutil.rmtree(tmp, ignore_errors=True)
    waits = [tr.req.queue_wait_s for tr in drv.tracked.values()
             if tr.in_window and tr.req.queue_wait_s is not None]
    result = {
        "workload": args.workload, "seed": args.seed,
        "window_s": t1 - t0,
        "output_tok_per_s": drv.window_tokens(t0, t1) / (t1 - t0),
        "decode_steps": loop.decode_steps - dec0,
        "prefill_chunks": (loop.prefill_tokens_run - run0) // loop.chunk,
        "queue_wait_p95_s": float(np.percentile(waits, 95))
        if waits else None,
        "trace": {k: red[k] for k in ("window_s", "busy_s", "scope_s")},
        "device_ops": red["breakdown"]["device_ops"],
        "lookup_gemm_share_of_busy": red["scope_s"][SCOPES[2]]
        / red["busy_s"],
        "host": host,
        "annotation": annotation_cost(),
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
