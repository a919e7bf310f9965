"""Rehearse ``bench/run.py`` end to end on the CPU, at smoke sizes.

    JAX_PLATFORMS=cpu python bench/tools/rehearse.py [cell ...]

Each cell runs through ``run.main`` as the chip would run it, with three
things changed here and nowhere else: the platform a run requires is the
CPU; each configuration runs at its program's smoke widths (two layers,
d_model 64-72, vocabulary 256-257); and, unless ``FULL_LENGTHS`` is set,
the deployment has 4 slots of 256 tokens and 64-token chunks and the
traffic's lengths are cut to fit them.  With ``FULL_LENGTHS`` the cell's
own deployment and traffic run at smoke widths.  A smoke-size run
measures overheads, and no number it prints is a device number.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import model, run  # noqa: E402

SMOKE_SERVING = {"slots": 4, "s_max": 256, "page_size": 16, "chunk": 64}
# the cell's own deployment and traffic lengths, at smoke widths
FULL_LENGTHS = False


def _smoke_config(load, to_program):
    def load_config(root, name):
        spec = load(root, name)
        if not FULL_LENGTHS:
            spec["serving"] = dict(spec["serving"], **SMOKE_SERVING)
            spec["correct"] = dict(spec["correct"], sample_tokens=40)
        return spec

    def program_config(spec):
        from repro.configs import smoke_config

        to_program(spec)            # the file still has to agree
        cfg = smoke_config(spec["program_config"])
        return dataclasses.replace(
            cfg, serve_kv_dtype=spec["serving"]["kv_dtype"])

    return load_config, program_config


# smoke lengths: prompts, outputs, shared prefix
LENGTHS = {"prompt": (4, 100, 40), "output": (2, 24, 8), "prefix": 128}


def _smoke_traffic(load):
    def load_traffic(name):
        t = json.loads(json.dumps(load(name)))
        if FULL_LENGTHS:
            return t
        for part in ("prompt", "output"):
            lo, hi, med = LENGTHS[part]
            t[part] = dict(t[part], min=lo, max=hi)
            if t[part]["dist"] == "lognormal":
                t[part]["median"] = med
        if "shared_prefix" in t:
            t["shared_prefix"] = dict(t["shared_prefix"],
                                      length=LENGTHS["prefix"])
        return t

    return load_traffic


def install() -> None:
    """The three changes, once per process."""
    if run.REQUIRED_PLATFORM == "cpu":
        return
    run.REQUIRED_PLATFORM = "cpu"
    model.load_config, model.program_config = _smoke_config(
        model.load_config, model.program_config)
    run.load_traffic = _smoke_traffic(run.load_traffic)


def run_cell(cell: str, seed: int, seconds: float = 4.0) -> dict:
    """One ``--trace 0`` run of ``cell``; returns its result object."""
    import contextlib
    import io

    install()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"])
    if rc:
        raise RuntimeError(f"{cell}: run.main returned {rc}")
    return json.loads(out.getvalue().strip().splitlines()[-1])


def main() -> int:
    install()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = sys.argv[1:] or [c["name"] for c in json.load(f)["workloads"]]
    rc = 0
    for cell in cells:
        rc |= run.main(["--workload", cell, "--seed", str(2 ** 31 + 11),
                        "--seconds", "4", "--trace", "0"])
    return rc


if __name__ == "__main__":
    sys.exit(main())
