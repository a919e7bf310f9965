"""Serve-loop observability (serve/telemetry.py + the instrumented
paged loop).

The contract under test has three legs:

1. **Bounded metrics.**  Histogram summaries are exact while the
   reservoir holds every sample and stay within [min, max] bounds past
   it; memory is O(cap) at any observation volume (the fix for the
   loop's previously unbounded TTFT/queue-wait lists).
2. **Lifecycle tracing.**  Every request's event sequence parses
   against the ``LIFECYCLE`` grammar — including forced
   preemption/recompute-resume and speculative decoding — and ends in
   ``finished`` on a drained loop.
3. **Zero interference.**  Telemetry on vs off produces bit-identical
   outputs, the same compile set (``check_compiled`` green both ways),
   and the unified ``metrics()`` document agrees with the legacy
   per-subsystem stats dicts it supersedes.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - fallback sweep
    from _hypothesis_fallback import given, settings, st

from repro.configs import smoke_config
from repro.models import lm
from repro.serve import telemetry
from repro.serve.loop import Request
from repro.serve.paged import PagedServeLoop
from repro.serve.telemetry import (LIFECYCLE, NULL, Histogram,
                                   MetricsRegistry, Telemetry, Tracer,
                                   validate_lifecycle)

ARCH = "minicpm-2b" if False else "minicpm_2b"


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(ARCH)
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, purpose="serve")
    return params, cfg


# ---------------------------------------------------------------------------
# histogram / registry
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 200),
       cap=st.integers(4, 64))
def test_histogram_quantile_bounds(seed, n, cap):
    """Quantiles always lie within [min, max]; count/sum/min/max are
    exact at any volume; while count <= cap the reservoir is the full
    sample and quantiles equal np.percentile over the raw data."""
    rng = np.random.default_rng(seed)
    xs = rng.exponential(1.0, n)
    h = Histogram(cap=cap, tail_cap=8)
    for x in xs:
        h.observe(x)
    s = h.summary()
    assert s["count"] == n
    assert np.isclose(s["sum"], xs.sum())
    assert np.isclose(s["min"], xs.min())
    assert np.isclose(s["max"], xs.max())
    for q in ("p50", "p90", "p99"):
        assert s["min"] - 1e-12 <= s[q] <= s["max"] + 1e-12
    assert s["p50"] <= s["p90"] <= s["p99"]
    if n <= cap:
        for q, v in ((50, s["p50"]), (90, s["p90"]), (99, s["p99"])):
            assert np.isclose(v, np.percentile(xs, q))
    # bounded memory: reservoir never exceeds cap, tail never tail_cap
    assert len(h.reservoir) <= cap
    assert len(h.tail) <= 8
    assert list(h.tail) == list(xs[-min(n, 8):])


def test_histogram_bounded_at_volume():
    h = Histogram(cap=32, tail_cap=4)
    for i in range(10_000):
        h.observe(float(i))
    assert len(h.reservoir) == 32
    assert h.count == 10_000
    assert h.vmin == 0.0 and h.vmax == 9999.0
    h.reset()
    assert h.count == 0 and h.reservoir == [] and len(h.tail) == 0
    assert np.isnan(h.summary()["mean"])


def test_registry_snapshot_roundtrips_json():
    r = MetricsRegistry()
    r.inc("hits")
    r.inc("hits", 2)
    r.set_gauge("depth", np.int64(7))        # numpy scalars must coerce
    r.observe("lat_s", np.float32(0.5))
    snap = r.snapshot()
    assert snap["counters"]["hits"] == 3
    assert snap["gauges"]["depth"] == 7
    assert snap["histograms"]["lat_s"]["count"] == 1
    json.dumps(snap)                         # strictly JSON-serialisable
    assert r.get_counter("nope") == 0
    r.reset()
    assert r.snapshot() == {"counters": {}, "gauges": {},
                            "histograms": {}}


# ---------------------------------------------------------------------------
# lifecycle grammar + tracer
# ---------------------------------------------------------------------------


def _ev(name, rid):
    return {"name": name, "rid": rid, "ts": 0.0, "dur": 0.0}


def test_validate_lifecycle_accepts_and_rejects():
    ok = [_ev(n, 0) for n in
          ("submit", "queued", "admitted", "prefill_chunk", "decode",
           "verify", "preempted", "queued", "resumed", "prefill_chunk",
           "decode", "finished")]
    seqs = validate_lifecycle(ok)
    assert seqs[0][-1] == "finished"
    # non-lifecycle rid events are ignored, loop-track events skipped
    seqs = validate_lifecycle(ok + [_ev("grow_page", 0),
                                    _ev("cow_copy", None)])
    assert len(seqs) == 1
    with pytest.raises(AssertionError):
        validate_lifecycle([_ev("queued", 1)])          # no submit
    with pytest.raises(AssertionError):
        validate_lifecycle([_ev(n, 2) for n in
                            ("submit", "queued", "admitted", "decode")])
    with pytest.raises(AssertionError):                 # never finished
        validate_lifecycle([_ev(n, 3) for n in ("submit", "queued")])
    validate_lifecycle([_ev(n, 3) for n in ("submit", "queued")],
                       require_finished=False)
    # every grammar state is reachable from the start
    reachable, frontier = set(), {None}
    while frontier:
        nxt = {n for s in frontier for n in LIFECYCLE.get(s, set())}
        frontier = nxt - reachable
        reachable |= nxt
    assert reachable == {n for s in LIFECYCLE.values() for n in s}


def test_tracer_exports(tmp_path):
    tr = Tracer(max_events=4)
    tr.event("submit", 0, prompt_tokens=5)
    with tr.span("queued", 0):
        pass
    tr.event("finished", 0, tokens=np.int64(3))
    tr.event("overflow", 1)
    tr.event("dropped_one", 1)
    assert len(tr.events) == 4 and tr.dropped == 1
    jp, cp = tmp_path / "t.jsonl", tmp_path / "t.json"
    assert tr.export_jsonl(str(jp)) == 4
    lines = jp.read_text().splitlines()
    head = json.loads(lines[0])
    assert head["events"] == 4 and head["dropped"] == 1
    assert [json.loads(ln)["name"] for ln in lines[1:]] == \
        ["submit", "queued", "finished", "overflow"]
    tr.export_chrome(str(cp))
    doc = json.loads(cp.read_text())
    evs = doc["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert phases == {"M", "i", "X"}        # metadata, instants, spans
    # one named track per request + the serve-loop track
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"serve-loop", "req 0", "req 1"} <= names
    tids = {e["tid"] for e in evs if e["ph"] != "M"}
    assert tids == {1, 2}                   # rid + 1; no loop-track events


def test_null_telemetry_is_inert():
    assert not NULL.enabled
    NULL.inc("x")
    NULL.observe("y", 1.0)
    NULL.set_gauge("z", 2.0)
    NULL.event("submit", 0)
    assert NULL.now() == 0.0 and NULL.rel(123.4) == 0.0
    with NULL.span("a"):
        pass
    # the profiler spans are not part of the facade: always on
    assert not hasattr(NULL, "annotate")
    assert not hasattr(Telemetry(), "annotate")
    assert NULL.export(chrome_path="/nonexistent/x.json") == \
        {"events": 0, "dropped": 0}


def test_telemetry_annotate_is_jax_trace_annotation():
    from jax.profiler import TraceAnnotation
    assert isinstance(telemetry.annotate("region"), TraceAnnotation)
    with telemetry.annotate("region", monotonic_s=1.5):
        pass


# ---------------------------------------------------------------------------
# instrumented serve loop
# ---------------------------------------------------------------------------


def _loop(params, cfg, tel, n_pages, spec_k=0, **kw):
    return PagedServeLoop(params, cfg, batch_slots=3, s_max=64,
                          page_size=8, chunk=8, n_pages=n_pages,
                          spec_k=spec_k, telemetry=tel,
                          check_invariants=True, **kw)


def _submit_all(loop, cfg, n_req=5, max_new=10, seed=3):
    rng = np.random.default_rng(seed)
    for r in range(n_req):
        p = rng.integers(1, cfg.vocab,
                         int(rng.integers(4, 20))).astype(np.int32)
        loop.submit(Request(rid=r, prompt=p, max_new_tokens=max_new,
                            priority=r % 2))


def test_lifecycle_valid_under_preemption_and_spec(setup):
    """Forced preemption (tiny pool) + speculative decoding: the traced
    run must parse the grammar end to end, and the preempted requests'
    tracks must show preempted -> queued -> resumed."""
    params, cfg = setup
    loop = _loop(params, cfg, tel=True, n_pages=10, spec_k=2)
    _submit_all(loop, cfg, max_new=14)
    loop.run()
    loop.check_compiled()
    assert loop.preemptions > 0, "workload did not force preemption"
    assert loop.spec_steps > 0, "workload never took the verify path"
    seqs = validate_lifecycle(loop.tel.tracer.events)
    assert len(seqs) == 5
    preempted = [s for s in seqs.values() if "preempted" in s]
    assert preempted, "no request track recorded its preemption"
    for s in preempted:
        i = s.index("preempted")
        assert s[i + 1:i + 3] == ["queued", "resumed"]
    assert any("verify" in s for s in seqs.values())


def test_tracing_onoff_bit_identical_same_compile_set(setup):
    params, cfg = setup
    outs, shapes = {}, {}
    for tel in (True, False):
        loop = _loop(params, cfg, tel=tel, n_pages=10, spec_k=2)
        _submit_all(loop, cfg, max_new=8)
        done = loop.run()
        loop.check_compiled()
        outs[tel] = {r.rid: np.asarray(r.output) for r in done}
        shapes[tel] = loop.compiled_shapes()
        if not tel:
            assert loop.tel is NULL
    assert shapes[True] == shapes[False]
    assert set(outs[True]) == set(outs[False])
    for r in outs[True]:
        np.testing.assert_array_equal(outs[True][r], outs[False][r])


def test_metrics_agree_with_legacy_stats(setup):
    params, cfg = setup
    loop = _loop(params, cfg, tel=True, n_pages=16, spec_k=2)
    _submit_all(loop, cfg)
    loop.run()
    m = loop.metrics()
    assert set(m) == {"pool", "prefix_cache", "spec", "quant",
                      "scheduler", "swap", "tenants", "faults",
                      "autotune", "telemetry"}
    # the unified document and the legacy dicts are the same source
    spec = loop.spec_stats()
    for k, v in spec.items():
        assert m["spec"][k] == v
    assert m["scheduler"] == telemetry.jsonable(loop.sched_stats())
    assert m["swap"] == loop.swap_stats() == {"enabled": False}
    assert m["tenants"] == loop.tenant_stats()
    assert m["faults"] == {"enabled": False}
    assert m["prefix_cache"] == loop.prefix.stats()
    assert m["pool"]["in_use"] == loop.pages.in_use
    assert m["pool"]["cow_copies"] == loop.cow_copies
    assert m["quant"]["kv_dtype"] == "fp"
    assert m["quant"]["pool_bytes"] == loop.kv_pool_bytes()
    from repro.kernels import autotune
    assert m["autotune"] == autotune.snapshot_stats()
    # phase histograms cover the paths this workload exercised; the
    # admission's runs to its first token's sync, so it holds the
    # prefill's device time (dispatch-only phases are gone)
    hists = m["telemetry"]["histograms"]
    assert "phase.admit_s" in hists
    assert "phase.reserve_s" in hists
    assert hists["phase.admit_s"]["count"] == loop.sched.queue_wait_s.count
    assert "phase.prefill_chunk_s" not in hists
    assert "phase.cow_s" not in hists
    json.dumps(m)                          # exportable as-is


def test_sched_stats_bounded_summaries(setup):
    """Satellite: ttft_s / queue_wait_s are summaries with a capped
    tail, not per-request lists that grow without bound."""
    params, cfg = setup
    loop = _loop(params, cfg, tel=False, n_pages=16)
    _submit_all(loop, cfg, n_req=4, max_new=4)
    loop.run()
    ss = loop.sched_stats()
    for key in ("ttft_s", "queue_wait_s"):
        s = ss[key]
        assert set(s) == {"count", "sum", "mean", "min", "max",
                          "p50", "p90", "p99", "tail"}
        assert s["count"] == 4
        assert len(s["tail"]) <= telemetry.TAIL_CAP
        assert s["min"] <= s["p50"] <= s["p99"] <= s["max"]
    assert isinstance(loop.ttft_s, Histogram)
    assert not hasattr(loop, "queue_wait_s")   # lives on the Scheduler


def test_trace_export_from_loop(setup, tmp_path):
    params, cfg = setup
    chrome = tmp_path / "trace.json"
    loop = _loop(params, cfg, tel=True, n_pages=16,
                 trace_path=str(chrome))
    _submit_all(loop, cfg, n_req=3, max_new=4)
    loop.run()                              # auto-exports on drain
    doc = json.loads(chrome.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"submit", "queued", "admitted", "prefill_chunk",
            "decode", "finished"} <= names
    lines = (tmp_path / "trace.jsonl").read_text().splitlines()
    assert json.loads(lines[0])["events"] == len(lines) - 1
    # off-loop export is a no-op
    off = _loop(params, cfg, tel=False, n_pages=16)
    assert off.export_trace(str(tmp_path / "off.json")) == {}
    assert not (tmp_path / "off.json").exists()
