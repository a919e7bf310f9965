"""The serve loop's host spans and the lookup GEMM's scope in a trace, and
the readers of the metrics that read the program's new instrumentation.

``data/probe_spans.xplane.pb`` was recorded by
``bench/tools/record_trace.py`` on a TPU v5e, as ``data/probe.xplane.pb``
was, from a program that emits the ``repro.serve.*`` spans and the
``repro.lookup_gemm`` scope: a one-layer cut of minicpm-2b-l10
(vocabulary 4096) serving three short requests through
``PagedServeLoop``, each ``step()`` inside a ``bench.step`` annotation,
compiled afresh (an empty compilation cache: a cached executable keeps
the op names of the code that compiled it).  Its ``/host:metadata``
plane, the compiled modules that nothing here reads, was dropped to
keep the file small; both reductions read the same with and without it.
``data/probe_reduce.json`` holds what ``trace.reduce`` returned on the
older probe before these spans existed.
"""

import json
import os
import types

import pytest

from bench import counting, spans, trace
from bench.clients import Tracked
from bench.metrics import lookup_gemm_roofline, queue_wait_p95_s
from repro.serve.loop import Request

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROBE = os.path.join(DATA, "probe.xplane.pb")
PROBE_SPANS = os.path.join(DATA, "probe_spans.xplane.pb")
DECODE, CHUNK = "repro.lm.decode_step_paged", "repro.lm.prefill_chunk"
GEMM = "repro.lookup_gemm"
SERVE = tuple("repro.serve." + s for s in (
    "step", "admit", "prefill_chunk", "decode_step", "sync"))
REQUESTS = 3          # the probe's traced serve() submits three


def test_reduce_unchanged_on_old_probe():
    with open(os.path.join(DATA, "probe_reduce.json")) as f:
        want = json.load(f)
    got = {"plain": trace.reduce(PROBE),
           "asked": trace.reduce(PROBE, scopes=(DECODE, CHUNK),
                                 kernels=("flash_decode",))}
    assert json.loads(json.dumps(got)) == want


def test_old_probe_has_no_serve_spans():
    r = spans.reduce(PROBE, spans=SERVE)
    assert r["steps"] == 0
    assert r["step_host_ms"] is None and r["clock_ns"] is None
    assert all(v == 0 for v in r["span_n"].values())
    # its gaps read as the benchmark's own labels did
    assert {k for k, _ in r["idle_gaps"]} <= {"bench.step", "bench.client",
                                              "bench.submit", "host"}


@pytest.fixture(scope="module")
def probe():
    return spans.reduce(PROBE_SPANS, spans=SERVE)


@pytest.fixture(scope="module")
def events():
    return spans.host_events(PROBE_SPANS)


def test_one_serve_step_per_bench_step(probe, events):
    bench_steps = [e for e in events if e[2] == "bench.step"]
    serve_steps = [e for e in events if e[2] == spans.STEP]
    assert probe["steps"] == probe["span_n"][spans.STEP] == len(bench_steps)
    for a, b, _, _ in serve_steps:
        assert any(s <= a and b <= e for s, e, _, _ in bench_steps)


def test_admissions_chunks_and_syncs(probe):
    n = probe["span_n"]
    assert n["repro.serve.admit"] == REQUESTS
    assert n["repro.serve.prefill_chunk"] >= REQUESTS
    # one fetch per admission (its first token), one per decode forward
    assert n[spans.SYNC] == REQUESTS + n["repro.serve.decode_step"]
    s = probe["span_s"]
    assert s["repro.serve.admit"] <= s[spans.STEP] <= probe["window_s"]


def test_step_host_time_and_clock(probe, events):
    mean_step_ms = 1e3 * probe["span_s"][spans.STEP] / probe["steps"]
    assert 0 < probe["step_host_ms"] < mean_step_ms
    # every step maps monotonic time onto the capture alike
    offs = [a - args["monotonic_s"] * 1e9 for a, _, name, args in events
            if name == spans.STEP]
    assert max(offs) - min(offs) < 1e6
    assert abs(probe["clock_ns"] - offs[0]) < 1e6


def test_idle_gaps_labelled_by_serve_spans(probe):
    labels = {k for k, _ in probe["idle_gaps"]}
    assert any(k.startswith("repro.serve.") for k in labels), labels
    total = sum(v for _, v in probe["idle_gaps"])
    busy = trace.reduce(PROBE_SPANS)["busy_s"]
    assert total <= probe["window_s"] - busy + 1e-9


def test_lookup_gemm_scope_holds_most_device_time():
    r = trace.reduce(PROBE_SPANS, scopes=(GEMM, DECODE, CHUNK))
    s = r["scope_s"]
    assert s[GEMM] >= 0.8 * r["busy_s"]
    # nested in the forwards, never outside them
    assert s[GEMM] <= s[DECODE] + s[CHUNK]


# ---------------------------------------------------------------------------
# readers, on hand-built runs
# ---------------------------------------------------------------------------

PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_lookup_gemm_cost_of_one_linear_by_hand():
    # q of minicpm-2b: [32, 2304] x [2304, 2304], G 4, int16 indices
    ops, nbytes = counting.lookup_gemm_cost(32, 2304, 2304)
    assert ops == 2 * 32 * 2304 * 2304 == 339_738_624
    idx = 576 * 2304 * 2          # K/G x N two-byte indices
    clus = 576 * 18               # K/G x N/d_p one-byte cluster ids
    tables = 4 * 4096 * 16 * 4    # n_clus x N_arr x 2^G int32
    codes, out = 32 * 2304, 2 * 32 * 2304
    assert nbytes == idx + clus + tables + codes + out == 3_934_336


def _run(dims, t, decode_steps=0, chunks=0, slots=32, chunk=256):
    return types.SimpleNamespace(
        trace=None if t is None else {"scope_s": {GEMM: t}},
        peaks=PEAKS, dims=dims, slots=slots, chunk=chunk,
        decode_steps=decode_steps, prefill_tokens_run=chunks * chunk)


def test_lookup_gemm_roofline_of_one_linear():
    # one layer whose only nonzero linear is a [2304, 2304] one
    dims = {"d_model": 2304, "n_heads": 36, "head_dim": 64, "n_kv": 0,
            "d_ff": 0, "n_layers": 1}
    assert [kn for kn in lookup_gemm_roofline.linears(dims)
            if 0 not in kn] == [(2304, 2304), (2304, 2304)]
    ops, nbytes = lookup_gemm_roofline.forward_cost(dims, 32)
    one = counting.lookup_gemm_cost(32, 2304, 2304)
    zero = [counting.lookup_gemm_cost(32, K, N)
            for K, N in lookup_gemm_roofline.linears(dims) if 0 in (K, N)]
    assert (ops, nbytes) == (2 * one[0],
                             2 * one[1] + sum(b for _, b in zero))
    # bandwidth-bound at 32 rows: 10 forwards over 1 ms of device time
    least = max(ops / PEAKS["int8_ops_per_s"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    assert least == nbytes / PEAKS["hbm_bytes_per_s"]
    got = lookup_gemm_roofline.read(_run(dims, 1e-3, decode_steps=10))
    assert got == pytest.approx(100.0 * 10 * least / 1e-3)


def test_lookup_gemm_roofline_counts_decode_and_chunks():
    dims = {"d_model": 2304, "n_heads": 36, "head_dim": 64, "n_kv": 36,
            "d_ff": 5760, "n_layers": 10}
    dec = lookup_gemm_roofline.read(_run(dims, 1.0, decode_steps=25))
    chk = lookup_gemm_roofline.read(_run(dims, 1.0, chunks=38))
    both = lookup_gemm_roofline.read(_run(dims, 1.0, 25, 38))
    assert both == pytest.approx(dec + chk)
    # ~0.49 ms least per 32-row decode forward, ~0.80 ms per chunk
    assert dec / 100 / 25 == pytest.approx(0.49e-3, rel=0.02)
    assert chk / 100 / 38 == pytest.approx(0.795e-3, rel=0.02)


def test_lookup_gemm_roofline_silent_without_scope():
    dims = {"d_model": 8, "n_heads": 1, "head_dim": 8, "n_kv": 1,
            "d_ff": 8, "n_layers": 1}
    assert lookup_gemm_roofline.read(_run(dims, None, 1)) is None
    assert lookup_gemm_roofline.read(_run(dims, 0.0, 1)) is None
    assert lookup_gemm_roofline.scopes(None) == (GEMM,)


def _clients(waits, in_window=True):
    tracked = {}
    for rid, w in enumerate(waits):
        req = Request(rid=rid, prompt=None)
        if w is not None:
            req.queue_wait_s = w
        tracked[rid] = Tracked(req, 0.0, in_window)
    return types.SimpleNamespace(tracked=tracked)


def test_queue_wait_p95_over_admitted_window_requests():
    waits = [0.1 * i for i in range(20)]
    clients = _clients(waits + [None])            # one not admitted
    before = _clients([50.0, 60.0], in_window=False)
    clients.tracked.update({100 + k: v for k, v in before.tracked.items()})
    got = queue_wait_p95_s.read(types.SimpleNamespace(clients=clients))
    # numpy's linear p95 of 0.0 .. 1.9: 0.95 x 19 steps of 0.1
    assert got == pytest.approx(1.805)


def test_queue_wait_silent_without_the_field():
    clients = _clients([None, None])
    assert queue_wait_p95_s.read(
        types.SimpleNamespace(clients=clients)) is None
    # a program whose Request has no such field reads the same
    bare = types.SimpleNamespace(req=types.SimpleNamespace(), in_window=True)
    assert queue_wait_p95_s.read(types.SimpleNamespace(
        clients=types.SimpleNamespace(tracked={0: bare}))) is None
