"""The readings a cell's limits are set from, in one process on the chip.

    python bench/tools/readings.py <cell> <seconds> <seed> [<seed> ...] \
        [--fault <name> <n>]

For every seed, a run as ``bench/run.py`` makes it (set-up, a window of
``seconds``, the sampled requests against the reference), and beside the
program's numbers the control's: the reference in the configuration's
``control`` precision, put in the program's place on the same positions.
With ``--fault name n`` the first n seeds run again with that fault of
``bench/tools/faults.py`` planted in the program.  One JSON line per
reading goes to standard output.
"""

from __future__ import annotations

import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run  # noqa: E402
from bench.tools import faults  # noqa: E402


def one(cell: dict, seed: int, seconds: float, variant="sound") -> dict:
    import jax

    ctx = run.prepare(cell, seed)
    ctx.drv.setup()
    t0, t1 = ctx.drv.window(seconds)
    jax.block_until_ready(ctx.loop.caches)
    ok, nums, _, ctl = run.correctness(
        ctx.drv, ctx.plain, ctx.dims, ctx.ref_mod, ctx.spec, seed,
        control=True)
    out = {"cell": cell["name"], "seed": seed, "variant": variant,
           "window_s": t1 - t0, "correct": ok, "program": nums,
           "control": ctl}
    del ctx
    gc.collect()
    return out


def main() -> int:
    args = sys.argv[1:]
    fault = None
    if "--fault" in args:
        i = args.index("--fault")
        fault, n_fault = args[i + 1], int(args[i + 2])
        del args[i:i + 3]
    name, seconds, seeds = args[0], float(args[1]), [int(s) for s in args[2:]]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = run.find_cell(json.load(f), name)
    run.check_devices(int(cell["chips"]))
    run.enable_caches()
    for seed in seeds:
        print(json.dumps(one(cell, seed, seconds)), flush=True)
    if fault:
        faults.FAULTS[fault](lambda owner, attr, value: setattr(
            owner, attr, value))
        for seed in seeds[:n_fault]:
            print(json.dumps(one(cell, seed, seconds, fault)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
