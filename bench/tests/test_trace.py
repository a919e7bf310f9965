"""The trace reduction on a small trace recorded on a TPU v5e.

``data/probe.xplane.pb`` was recorded by ``bench/tools/record_trace.py``:
a one-layer cut of minicpm-2b-l10 (vocabulary 4096) serving three short
requests through ``PagedServeLoop`` with the Pallas flash-decode kernel,
each ``step()`` inside a ``bench.step`` annotation.
"""

import os

import pytest

from bench import trace

PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "probe.xplane.pb")
DECODE, CHUNK = "repro.lm.decode_step_paged", "repro.lm.prefill_chunk"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(PROBE, scopes=(DECODE, CHUNK),
                        kernels=("flash_decode",))


def test_window_and_busy(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_scopes_and_kernel(reduced):
    s = reduced["scope_s"]
    assert s[DECODE] > 0 and s[CHUNK] > 0
    assert s[DECODE] + s[CHUNK] <= reduced["busy_s"] * 1.0001
    # the kernel runs inside the decode step
    assert 0 < reduced["kernel_s"]["flash_decode"] < s[DECODE]


def test_breakdown(reduced):
    b = reduced["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    idle = sum(v for _, v in b["idle_gaps"])
    busy_plus_idle = reduced["busy_s"] + idle
    assert abs(busy_plus_idle - reduced["window_s"]) < 1e-6 * max(
        1.0, reduced["window_s"]) or len(b["idle_gaps"]) == 10


def test_unknown_kernel_is_an_error():
    with pytest.raises(ValueError, match="want exactly one operation"):
        trace.reduce(PROBE, kernels=("no_such_kernel",))
