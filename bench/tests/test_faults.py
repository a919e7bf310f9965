"""A run with the timed path broken underneath reads ``correct: false``.

Each case drives ``run.main`` end to end on the CPU at smoke widths (the
rehearsal's changes), once sound and once with a fault of
``bench/tools/faults.py`` planted in the program, on one of two traffics:

- ``cell_lengths``: the cell's own deployment and traffic lengths;
- ``decode_heavy``: the smoke deployment with prompts of 2-4 tokens and
  100-180 decoded, so that decoded tokens are most of what a later token
  attends to.

A token altered where it is produced is read at the cell's own lengths.
A decode step that returns its KV state unchanged is read on the
decode-heavy traffic: at the cell's own lengths (8-32 decoded over
1056-1280 tokens of context) it reads within the model's own rounding
noise on some samples, so the check does not see it there reliably (the
readings are in PERF.md).  The cells run on one chip, so there is no
exchange between chips to leave out, and serving takes no mean over a
batch.
"""

import json
import os

import pytest

from bench.tools import faults, rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [c["name"] for c in json.load(f)["workloads"]]
SEED = 2 ** 31 + 29


def _cell_lengths(monkeypatch):
    monkeypatch.setattr(rehearse, "FULL_LENGTHS", True)


def _decode_heavy(monkeypatch):
    monkeypatch.setitem(rehearse.LENGTHS, "prompt", (2, 4, 3))
    monkeypatch.setitem(rehearse.LENGTHS, "output", (100, 180, 140))
    monkeypatch.setitem(rehearse.LENGTHS, "prefix", 16)


TRAFFIC = {"cell_lengths": _cell_lengths, "decode_heavy": _decode_heavy}
CASES = [("state_unchanged", "decode_heavy"),
         ("token_altered", "cell_lengths")]


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, traffic, monkeypatch):
    TRAFFIC[traffic](monkeypatch)
    assert rehearse.run_cell(cell, SEED)["correct"] is True


@pytest.mark.parametrize("fault,traffic", CASES,
                         ids=[f for f, _ in CASES])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, traffic, monkeypatch):
    TRAFFIC[traffic](monkeypatch)
    faults.FAULTS[fault](monkeypatch.setattr)
    result = rehearse.run_cell(cell, SEED)
    assert result["correct"] is False, result["checks"]
