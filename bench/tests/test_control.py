"""The control comes out as not correct, and the program as correct.

The control is the reference in the configuration's ``control``
precision (the one below the bfloat16 the program computes in), put in
the program's place on the same served positions.  Each case runs the
readings of ``bench/tools/readings.py`` on the CPU at smoke widths with
the cell's own deployment and traffic lengths, on three seeds, and holds
both against the configuration's own limits.  The readings at the cells'
own sizes, on the chip, are in PERF.md.
"""

import json
import os

import pytest

from bench import check, model
from bench.tools import readings, rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = json.load(f)["workloads"]


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_control_is_not_correct(cell, monkeypatch):
    rehearse.install()
    monkeypatch.setattr(rehearse, "FULL_LENGTHS", True)
    spec = model.load_config(os.path.join(ROOT, "bench"), cell["config"])
    limits = spec["correct"]["limits"]
    for seed in (2 ** 31 + 101, 2 ** 31 + 102, 2 ** 31 + 103):
        out = readings.one(cell, seed, 4.0)
        assert check.verdict(out["program"], limits), out["program"]
        assert not check.verdict(out["control"], limits), out["control"]
