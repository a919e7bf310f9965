"""The serve loop's profiler spans and the lookup GEMM's scope.

- Every lookup GEMM of the serve graph lowers under the
  ``repro.lookup_gemm`` named scope, on the autotuned TP path and on the
  fused N-tile path alike, so a device trace finds it by one name
  whatever implements it.
- The loop's host spans (``repro.serve.step``, ``.admit``, ``.sync`` ...)
  are emitted with telemetry off, one ``step`` per ``step()``, every
  ``admit`` inside a step, one ``sync`` per host fetch of a device value.
- ``repro.serve.step`` carries ``time.monotonic()`` at its start, which
  maps the program's own stamps (the tracer's events) onto the capture's
  clock.
- ``Request.queue_wait_s`` is the scheduler's own queue-wait
  observation at the first admission, kept across a resume.
- A profiler session changes no output and no compiled shape.
"""

import dataclasses
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import smoke_config
from repro.models import lm
from repro.serve.loop import Request
from repro.serve.paged import PagedServeLoop

SCOPE = "repro.lookup_gemm"
STEP, ADMIT, SYNC = ("repro.serve.step", "repro.serve.admit",
                     "repro.serve.sync")
DECODE = "repro.serve.decode_step"

# the dense family runs the autotuned TP path; a MoE config sends every
# serve linear down the fused N-tile path
CFGS = {
    "tp": lambda: smoke_config("minicpm-2b"),
    "fused": lambda: dataclasses.replace(
        smoke_config("deepseek-v3-671b"), attn_kind="gqa",
        name="ds-moe-gqa"),
}
# the jitted GEMM each path calls (the CPU resolves 'auto' to the
# k-chunk scan default)
GEMM_JIT = {"tp": "jit(tlmac_matmul_xla_kscan)",
            "fused": "jit(tlmac_matmul_xla)"}


@pytest.fixture(scope="module")
def dense():
    cfg = CFGS["tp"]()
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, purpose="serve")
    return params, cfg


def _lowered(loop, entry: str) -> str:
    B = loop.B
    if entry == "decode":
        low = loop._decode.lower(
            loop.params, loop.caches, jnp.zeros((B, 1), jnp.int32),
            jnp.zeros(B, jnp.int32), jnp.asarray(loop.block_table))
    else:
        low = loop._prefill_chunk.lower(
            loop.params, loop.caches, jnp.zeros((1, loop.chunk), jnp.int32),
            jnp.int32(0), jnp.asarray(loop.block_table[0]), jnp.int32(0))
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("entry", ["decode", "chunk"])
@pytest.mark.parametrize("path", sorted(CFGS))
def test_lookup_gemm_scope_in_lowered_forwards(path, entry):
    cfg = CFGS[path]()
    assert (cfg.n_experts > 0) == (path == "fused")
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, purpose="serve")
    loop = PagedServeLoop(params, cfg, batch_slots=2, s_max=32,
                          page_size=8, chunk=8)
    names = set(re.findall(r'loc\("([^"]*)"', _lowered(loop, entry)))
    scoped = {n for n in names if SCOPE + "/" in n}
    # the GEMM itself, whatever jit implements it, sits in the scope
    assert any(n.endswith(SCOPE + "/" + GEMM_JIT[path]) for n in scoped), \
        sorted(scoped)
    # and so does the dequant scale that follows it
    assert any(n.endswith(SCOPE + "/mul") for n in scoped), sorted(scoped)


# ---------------------------------------------------------------------------
# host spans under a capture
# ---------------------------------------------------------------------------


def _submit(loop, cfg, n_req=5, max_new=6, seed=3):
    rng = np.random.default_rng(seed)
    for r in range(n_req):
        p = rng.integers(1, cfg.vocab,
                         int(rng.integers(4, 20))).astype(np.int32)
        loop.submit(Request(rid=r, prompt=p, max_new_tokens=max_new))


def _host_events(tmp):
    """[(start_ns, end_ns, name, stats)] of the serve loop's spans."""
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro.serve."):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats)))
    return sorted(out)


def _captured_run(params, cfg, tmp, telemetry):
    """Drain a small workload step by step under a profiler session."""
    loop = PagedServeLoop(params, cfg, batch_slots=3, s_max=64,
                          page_size=8, chunk=8, telemetry=telemetry)
    _submit(loop, cfg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    steps = 0
    try:
        while loop.step():
            steps += 1
        steps += 1
    finally:
        jax.profiler.stop_trace()
    return loop, steps, _host_events(str(tmp))


@pytest.fixture(scope="module")
def captured(dense, tmp_path_factory):
    params, cfg = dense
    return _captured_run(params, cfg, tmp_path_factory.mktemp("off"),
                         telemetry=False)


def _named(events, name):
    return [e for e in events if e[2] == name]


def test_step_span_per_step_with_telemetry_off(captured):
    loop, steps, events = captured
    assert not loop.tel.enabled
    spans = _named(events, STEP)
    assert len(spans) == steps
    # every step states its monotonic start, in order
    stamps = [s[3]["monotonic_s"] for s in spans]
    assert all(isinstance(t, float) for t in stamps)
    assert stamps == sorted(stamps)


def test_admit_spans_nested_in_steps(captured):
    loop, _, events = captured
    steps = _named(events, STEP)
    admits = _named(events, ADMIT)
    # no pool pressure: every admission attempt popped its request
    assert len(admits) == loop.sched.queue_wait_s.count == 5
    for a, b, _, _ in admits:
        assert any(s <= a and b <= e for s, e, _, _ in steps)
    # each admission holds its prefill chunks and its first token's sync
    chunks = _named(events, "repro.serve.prefill_chunk")
    assert chunks and all(any(s <= a and b <= e for s, e, _, _ in admits)
                          for a, b, _, _ in chunks)


def test_sync_span_per_host_fetch(captured):
    loop, _, events = captured
    syncs = _named(events, SYNC)
    # the first token's argmax at each admission, then one argmax per
    # decode forward: the loop's only host fetches on this workload
    fetches = loop.sched.queue_wait_s.count + loop.decode_steps
    assert loop.spec_steps == 0 and loop.swap is None
    assert len(syncs) == fetches
    steps = _named(events, STEP)
    assert all(any(s <= a and b <= e for s, e, _, _ in steps)
               for a, b, _, _ in syncs)


def test_tracer_events_map_onto_capture_clock(dense, tmp_path):
    """The tracer's ``decode`` events, moved onto the capture's clock
    through their step's ``monotonic_s``, start within 1 ms of the
    ``repro.serve.decode_step`` spans they time."""
    params, cfg = dense
    loop, _, events = _captured_run(params, cfg, tmp_path, telemetry=True)
    steps = _named(events, STEP)
    spans = _named(events, DECODE)
    tracer = loop.tel.tracer
    starts = sorted({ev["ts"] for ev in tracer.events
                     if ev["name"] == "decode"})
    assert len(starts) == len(spans) == loop.decode_steps > 0
    for ts, (a, _, _, _) in zip(starts, spans):
        s0, _, _, st = max((s for s in steps if s[0] <= a),
                           key=lambda s: s[0])
        mono = tracer.t0 + ts
        at_ns = s0 + (mono - st["monotonic_s"]) * 1e9
        assert abs(at_ns - a) < 1e6, (at_ns - a) / 1e6


def test_trace_exports_state_monotonic_epoch(dense, tmp_path):
    params, cfg = dense
    loop = PagedServeLoop(params, cfg, batch_slots=3, s_max=64,
                          page_size=8, chunk=8, telemetry=True)
    _submit(loop, cfg, n_req=2, max_new=3)
    loop.run()
    chrome = tmp_path / "t.json"
    loop.export_trace(str(chrome))
    doc = json.loads(chrome.read_text())
    assert doc["otherData"]["trace_epoch_monotonic_s"] == loop.tel.tracer.t0
    head = json.loads((tmp_path / "t.jsonl").read_text().splitlines()[0])
    assert head["trace_epoch_monotonic_s"] == loop.tel.tracer.t0


# ---------------------------------------------------------------------------
# queue wait per request
# ---------------------------------------------------------------------------


def _pops(loop):
    """Record every (rid, wait) the scheduler observes at a pop."""
    seen = []
    pop = loop.sched.pop

    def recording(ent):
        wait = pop(ent)
        seen.append((ent.req.rid, wait))
        return wait

    loop.sched.pop = recording
    return seen


@pytest.fixture(scope="module")
def preempted(dense):
    """A pool small enough that speculation and growth force
    preemption and recompute-resume."""
    params, cfg = dense
    loop = PagedServeLoop(params, cfg, batch_slots=3, s_max=64,
                          page_size=8, chunk=8, n_pages=10, spec_k=2,
                          check_invariants=True)
    seen = _pops(loop)
    rng = np.random.default_rng(3)
    for r in range(5):
        p = rng.integers(1, cfg.vocab,
                         int(rng.integers(4, 20))).astype(np.int32)
        loop.submit(Request(rid=r, prompt=p, max_new_tokens=14,
                            priority=r % 2))
    loop.run()
    return loop, seen


def test_request_queue_wait_matches_scheduler(preempted):
    loop, seen = preempted
    # the histogram holds every pop's observation, in order
    assert [w for _, w in seen] == loop.sched.queue_wait_s.reservoir
    first = {}
    for rid, w in seen:
        first.setdefault(rid, w)
    assert {r.rid for r in loop.done} == set(first)
    for req in loop.done:
        assert req.queue_wait_s == first[req.rid]


def test_resumed_request_keeps_first_queue_wait(preempted):
    loop, seen = preempted
    assert loop.resumes > 0, "workload did not force a resume"
    by_rid = {}
    for rid, w in seen:
        by_rid.setdefault(rid, []).append(w)
    resumed = {rid: ws for rid, ws in by_rid.items() if len(ws) > 1}
    assert resumed
    done = {r.rid: r for r in loop.done}
    for rid, ws in resumed.items():
        assert done[rid].queue_wait_s == ws[0]


def test_queue_wait_unset_until_admitted(dense):
    params, cfg = dense
    loop = PagedServeLoop(params, cfg, batch_slots=1, s_max=64,
                          page_size=8, chunk=8)
    _submit(loop, cfg, n_req=2, max_new=4)
    reqs = [e.req for e in loop.sched.queued()]
    assert all(r.queue_wait_s is None for r in reqs)
    loop.step()                         # one slot: the first admits
    assert reqs[0].queue_wait_s is not None and reqs[0].queue_wait_s >= 0
    assert reqs[1].queue_wait_s is None


# ---------------------------------------------------------------------------
# zero interference
# ---------------------------------------------------------------------------


def test_capture_leaves_outputs_and_compile_set_unchanged(dense, captured):
    params, cfg = dense
    traced, _, _ = captured
    plain = PagedServeLoop(params, cfg, batch_slots=3, s_max=64,
                           page_size=8, chunk=8, telemetry=False)
    _submit(plain, cfg)
    plain.run()
    for loop in (traced, plain):
        loop.check_compiled()
    assert traced.compiled_shapes() == plain.compiled_shapes()
    want = {r.rid: r.output for r in plain.done}
    got = {r.rid: r.output for r in traced.done}
    assert set(got) == set(want)
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
