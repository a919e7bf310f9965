"""Unified serve-loop observability: metrics registry + lifecycle tracer.

The paper's central claim is an *accounting* argument — LUT reuse and
logic utilisation measured precisely enough to prove scalability — and
the serving stack needs the same discipline: six interacting subsystems
(paged pool, prefix cache, speculative decode, quantised KV, scheduler,
autotuner) whose behaviour under load must be *attributable*, not
inferred from four ad-hoc stats dicts read once at the end of a run.
This module supplies the shared vocabulary:

- **Metrics registry** (``MetricsRegistry``): named counters, gauges,
  and *bounded* histograms.  A histogram keeps running count/sum/
  min/max plus a fixed-size uniform reservoir (Vitter's algorithm R
  with a deterministic PRNG) for p50/p90/p99 quantile summaries and a
  capped most-recent tail — O(1) memory at any request volume, which
  is what fixes the serve loop's previously unbounded per-request
  TTFT/queue-wait lists.
- **Lifecycle tracer** (``Tracer``): typed span events per request —
  ``submit → queued → admitted/resumed → prefill_chunk* →
  decode/verify* → preempted → (queued → resumed → …) → finished`` —
  each with wall time and page/token attribution.  ``LIFECYCLE`` is
  the transition relation; ``validate_lifecycle`` checks a trace
  against it (tests assert it under forced preemption and speculative
  decoding).
- **Exporters**: ``export_jsonl`` (one event per line, grep-able) and
  ``export_chrome`` (Chrome trace-event JSON — load in
  ``chrome://tracing`` or https://ui.perfetto.dev: one named track per
  request plus a ``serve-loop`` track for step phases, so a full serve
  run is visually inspectable).
- **Profiler spans**: ``annotate`` opens a ``jax.profiler.TraceAnnotation``
  around a host region of the serve loop (``repro.serve.step``,
  ``.admit``, ``.prefill_chunk``, ``.decode_step``, ``.verify_step``,
  ``.cow_copy``, ``.swap_gather``, ``.swap_scatter``, and ``.sync``
  around every host fetch of a device value).  They are emitted whether
  or not telemetry is on: without a profiler session each costs one
  check in C++ and the context manager's call.  Inside a
  ``jax.profiler`` capture they sit on the profile's own clock beside
  the device operations, which carry ``jax.named_scope`` labels
  (``repro.lm.*`` in models/lm.py, ``repro.lookup_gemm`` in
  models/nn.py).
- **One clock for both**: the program stamps on ``time.monotonic``
  (the tracer's epoch, the scheduler's ``t_submit``/``t_enqueue``,
  deadlines).  ``repro.serve.step`` carries the loop's
  ``time.monotonic()`` at its start as its ``monotonic_s`` argument,
  so any capture maps a monotonic stamp ``t`` to profile time
  ``step.start + (t - step.monotonic_s)``; the exports state the
  tracer's epoch on that clock (``trace_epoch_monotonic_s``).

Everything here is host-side Python around the jitted calls: enabling
telemetry cannot change what the device computes (tracing on/off is
bit-identical by construction) and adds no jit traces (the compile-set
invariant ``check_compiled`` stays green).  When disabled
(``cfg.serve_telemetry`` off) the loop holds the shared ``NULL``
no-op facade: every hook is an attribute test or an empty method —
measured overhead is gated ≤ 3% of decode wall time in CI *with
telemetry on*; off is far below that.  The profiler spans are not part
of the facade: they are always on.
"""

from __future__ import annotations

import json
import random
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterable, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# Bounded-memory defaults.  The reservoir cap bounds quantile memory;
# below it the reservoir holds EVERY sample, so summaries agree exactly
# with np.percentile over the raw list (tests pin this).  The tail cap
# bounds the most-recent raw samples kept for debugging; the event cap
# bounds the tracer (drops are counted, never silent).
RESERVOIR_CAP = 512
TAIL_CAP = 32
MAX_EVENTS = 200_000


def jsonable(obj):
    """Recursively coerce numpy scalars/arrays so a metrics snapshot or
    trace document dumps with the stdlib ``json`` module."""
    if isinstance(obj, dict):
        return {str(k): jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, deque)):
        return [jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return [jsonable(v) for v in obj.tolist()]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, (np.bool_,)):
        return bool(obj)
    return obj


def annotate(name: str, **args) -> TraceAnnotation:
    """A host span of the serve loop on the profiler's clock (always
    on; see the module docstring).  ``args`` are recorded with the span
    only while a profiler session is active."""
    return TraceAnnotation(name, **args)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


class Histogram:
    """Streaming histogram with bounded memory.

    Running ``count``/``sum``/``min``/``max`` are exact; quantiles come
    from a fixed-size uniform reservoir (algorithm R: sample ``i`` past
    the cap replaces a random slot with probability ``cap/i``, seeded
    PRNG so a pinned workload summarises deterministically).  While
    ``count <= cap`` the reservoir IS the full sample set and
    ``quantile(q)`` equals ``np.percentile(raw, q)`` exactly.  A
    ``deque(maxlen=tail_cap)`` keeps the most recent raw samples for
    debugging (the "capped sample tail" the legacy ``ttft_s`` /
    ``queue_wait_s`` keys now return instead of an ever-growing list).
    """

    __slots__ = ("cap", "count", "total", "vmin", "vmax",
                 "reservoir", "tail", "_rng")

    def __init__(self, cap: int = RESERVOIR_CAP, tail_cap: int = TAIL_CAP,
                 seed: int = 0):
        self.cap = int(cap)
        self.tail = deque(maxlen=int(tail_cap))
        self._rng = random.Random(seed)
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.reservoir: List[float] = []

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        if len(self.reservoir) < self.cap:
            self.reservoir.append(v)
        else:
            j = self._rng.randrange(self.count)
            if j < self.cap:
                self.reservoir[j] = v
        self.tail.append(v)

    def reset(self) -> None:
        self.tail.clear()
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.reservoir = []

    def quantile(self, q: float) -> float:
        """q in [0, 100], np.percentile semantics over the reservoir
        (exact while count <= cap, an unbiased estimate past it)."""
        if not self.reservoir:
            return float("nan")
        return float(np.percentile(self.reservoir, q))

    def summary(self) -> Dict[str, Any]:
        if self.count == 0:
            return {"count": 0, "sum": 0.0, "mean": float("nan"),
                    "min": float("nan"), "max": float("nan"),
                    "p50": float("nan"), "p90": float("nan"),
                    "p99": float("nan"), "tail": []}
        return {
            "count": self.count,
            "sum": self.total,
            "mean": self.total / self.count,
            "min": self.vmin,
            "max": self.vmax,
            "p50": self.quantile(50),
            "p90": self.quantile(90),
            "p99": self.quantile(99),
            "tail": list(self.tail),
        }


class MetricsRegistry:
    """Named counters, gauges, and histograms behind one lock.

    Low-overhead by construction: ``inc``/``observe`` are a dict lookup
    and an int/float update under an uncontended lock (the serve loop
    is single-threaded; the lock exists for the autotuner, whose
    counters other threads may bump).  ``snapshot()`` returns a plain
    JSON-serialisable dict — histograms as quantile summaries, never
    raw sample lists."""

    def __init__(self, hist_cap: int = RESERVOIR_CAP,
                 tail_cap: int = TAIL_CAP):
        self._lock = threading.Lock()
        self._hist_cap = int(hist_cap)
        self._tail_cap = int(tail_cap)
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.histograms: Dict[str, Histogram] = {}

    def inc(self, name: str, v: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + v

    def set_gauge(self, name: str, v: float) -> None:
        with self._lock:
            self.gauges[name] = v

    def observe(self, name: str, v: float) -> None:
        with self._lock:
            h = self.histograms.get(name)
            if h is None:
                h = self.histograms[name] = Histogram(
                    self._hist_cap, self._tail_cap)
            h.observe(v)

    def get_counter(self, name: str) -> float:
        with self._lock:
            return self.counters.get(name, 0)

    def histogram(self, name: str) -> Histogram:
        """Get-or-create (for callers that observe without the lock's
        per-call cost — the returned Histogram is single-writer)."""
        with self._lock:
            h = self.histograms.get(name)
            if h is None:
                h = self.histograms[name] = Histogram(
                    self._hist_cap, self._tail_cap)
            return h

    def reset(self) -> None:
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return jsonable({
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "histograms": {k: h.summary()
                               for k, h in self.histograms.items()},
            })


# ---------------------------------------------------------------------------
# lifecycle tracing
# ---------------------------------------------------------------------------

# Request-lifecycle transition relation: event N+1 of a request must be
# in LIFECYCLE[event N] (None keys the start state).  ``queued`` is a
# SPAN covering the wait (emitted at admission, so it follows
# ``preempted`` in emission order on a resume); ``admitted`` marks a
# first admission, ``resumed`` a resume re-admission (recompute or
# swap-restore).  The swap tier adds two states: ``swapped_out``
# follows ``preempted`` when the victim's pages were copied to host
# RAM instead of dropped, and ``swapped_in`` follows ``queued`` when
# admission restored host pages before mapping the block table
# (``admitted`` is also legal after ``swapped_in`` — the store is
# content-addressed, so a *fresh* request can hit another request's
# swapped prefix).
LIFECYCLE: Dict[Optional[str], set] = {
    None: {"submit"},
    "submit": {"queued", "cancelled"},
    "queued": {"admitted", "resumed", "swapped_in", "cancelled"},
    "admitted": {"prefill_chunk", "cancelled"},
    "resumed": {"prefill_chunk", "cancelled"},
    "swapped_in": {"admitted", "resumed", "cancelled"},
    "prefill_chunk": {"prefill_chunk", "decode", "verify", "finished",
                      "preempted", "cancelled"},
    "decode": {"decode", "verify", "finished", "preempted", "cancelled"},
    "verify": {"decode", "verify", "finished", "preempted", "cancelled"},
    "preempted": {"queued", "swapped_out", "cancelled"},
    "swapped_out": {"queued", "cancelled"},
    "finished": set(),
    # the OTHER terminal state: client cancel or deadline/TTL expiry
    # (the event's `reason` attr distinguishes them).  Reachable from
    # every non-terminal state — a request can be cancelled while
    # queued (straight after submit), mid-prefill/decode/verify, after
    # preemption, or while its pages sit swapped out on the host.
    "cancelled": set(),
}

# Names the grammar governs.  Auxiliary rid-attributed events
# (``grow_page``: on-demand page-boundary allocations) ride the same
# request track in exports but are not lifecycle states.
LIFECYCLE_EVENTS = {n for s in LIFECYCLE.values() for n in s}


def validate_lifecycle(events: Iterable[dict],
                       require_finished: bool = True) -> Dict[int, List[str]]:
    """Check every request's event sequence (in emission order) against
    ``LIFECYCLE``.  Raises AssertionError naming the offending request
    and transition; returns ``{rid: [event names]}`` on success.
    ``require_finished`` additionally asserts every request reached a
    terminal state — ``finished`` or ``cancelled`` (set False for a
    trace cut mid-drain)."""
    seqs: Dict[int, List[str]] = {}
    for ev in events:
        rid = ev.get("rid")
        if rid is None or ev["name"] not in LIFECYCLE_EVENTS:
            continue
        seqs.setdefault(rid, []).append(ev["name"])
    for rid, names in seqs.items():
        prev: Optional[str] = None
        for n in names:
            allowed = LIFECYCLE.get(prev, set())
            assert n in allowed, (
                f"request {rid}: illegal lifecycle transition "
                f"{prev!r} -> {n!r} (full sequence: {names})"
            )
            prev = n
        if require_finished:
            assert prev in ("finished", "cancelled"), \
                f"request {rid} never reached a terminal state " \
                f"(last event {prev!r})"
    return seqs


class Tracer:
    """Append-only span/event log with wall-clock timestamps.

    Events are dicts ``{"name", "rid", "ts", "dur", ...attrs}`` with
    ``ts``/``dur`` in seconds relative to the tracer's epoch
    (``time.monotonic`` at construction; ``t_wall_epoch`` records the
    corresponding UTC time so exports are absolute-datable).  ``rid``
    is the request id for lifecycle events, None for serve-loop phase
    spans.  Capped at ``max_events``; past it events are counted in
    ``dropped``, never silently lost."""

    def __init__(self, max_events: int = MAX_EVENTS):
        self.max_events = int(max_events)
        self.events: List[dict] = []
        self.dropped = 0
        self.t0 = time.monotonic()
        self.t_wall_epoch = time.time()

    def now(self) -> float:
        return time.monotonic() - self.t0

    def event(self, name: str, rid: Optional[int] = None,
              t0: Optional[float] = None, t1: Optional[float] = None,
              **attrs) -> None:
        """Record one event.  ``t0``/``t1`` are tracer-relative seconds
        (``now()``); omitted ``t0`` stamps the current time, omitted
        ``t1`` makes it an instant (dur 0)."""
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        ts = self.now() if t0 is None else t0
        ev = {"name": name, "rid": rid, "ts": ts,
              "dur": 0.0 if t1 is None else max(0.0, t1 - ts)}
        if attrs:
            ev.update(attrs)
        self.events.append(ev)

    @contextmanager
    def span(self, name: str, rid: Optional[int] = None, **attrs):
        t0 = self.now()
        try:
            yield
        finally:
            self.event(name, rid, t0=t0, t1=self.now(), **attrs)

    def reset(self) -> None:
        self.events = []
        self.dropped = 0
        self.t0 = time.monotonic()
        self.t_wall_epoch = time.time()

    # -- exporters ----------------------------------------------------------

    def export_jsonl(self, path: str) -> int:
        """One JSON object per line (first line: epoch header).  Returns
        the number of events written."""
        with open(path, "w") as f:
            f.write(json.dumps({"trace_epoch_unix_s": self.t_wall_epoch,
                                "trace_epoch_monotonic_s": self.t0,
                                "events": len(self.events),
                                "dropped": self.dropped}) + "\n")
            for ev in self.events:
                f.write(json.dumps(jsonable(ev)) + "\n")
        return len(self.events)

    def export_chrome(self, path: str) -> int:
        """Chrome trace-event JSON (``chrome://tracing`` / Perfetto).

        One track (tid) per request — named ``req <rid>`` — plus tid 0
        (``serve-loop``) for loop-phase spans; ``ts``/``dur`` in
        microseconds as the format requires.  Spans are complete
        events (ph 'X'); zero-duration lifecycle marks are instants
        (ph 'i', thread-scoped).  ``otherData`` states the epoch, on
        ``time.monotonic`` and in UTC, that ``ts`` counts from."""
        trace: List[dict] = [{
            "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": "repro.serve"},
        }, {
            "name": "thread_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": "serve-loop"},
        }]
        named = set()
        for ev in self.events:
            rid = ev.get("rid")
            tid = 0 if rid is None else int(rid) + 1
            if tid != 0 and tid not in named:
                named.add(tid)
                trace.append({"name": "thread_name", "ph": "M", "pid": 0,
                              "tid": tid, "args": {"name": f"req {rid}"}})
            args = {k: v for k, v in ev.items()
                    if k not in ("name", "rid", "ts", "dur")}
            base = {"name": ev["name"], "pid": 0, "tid": tid,
                    "ts": ev["ts"] * 1e6, "cat": "serve",
                    "args": jsonable(args)}
            if ev["dur"] > 0.0:
                base.update(ph="X", dur=ev["dur"] * 1e6)
            else:
                base.update(ph="i", s="t")
            trace.append(base)
        with open(path, "w") as f:
            json.dump({"traceEvents": trace,
                       "displayTimeUnit": "ms",
                       "otherData": {
                           "trace_epoch_monotonic_s": self.t0,
                           "trace_epoch_unix_s": self.t_wall_epoch}}, f)
        return len(self.events)


# ---------------------------------------------------------------------------
# facade
# ---------------------------------------------------------------------------


class _NullContext:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullContext()


class Telemetry:
    """The enabled facade: registry + tracer, bundled so
    instrumentation sites need one handle."""

    enabled = True

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        self.registry = registry or MetricsRegistry()
        self.tracer = tracer or Tracer()

    # time / registry
    def now(self) -> float:
        return self.tracer.now()

    def rel(self, t_monotonic: float) -> float:
        """Convert an absolute ``time.monotonic()`` stamp (e.g. a
        scheduler entry's enqueue time) to tracer-relative seconds."""
        return t_monotonic - self.tracer.t0

    def inc(self, name: str, v: float = 1) -> None:
        self.registry.inc(name, v)

    def set_gauge(self, name: str, v: float) -> None:
        self.registry.set_gauge(name, v)

    def observe(self, name: str, v: float) -> None:
        self.registry.observe(name, v)

    # tracer
    def event(self, name: str, rid: Optional[int] = None,
              t0: Optional[float] = None, t1: Optional[float] = None,
              **attrs) -> None:
        self.tracer.event(name, rid, t0=t0, t1=t1, **attrs)

    def span(self, name: str, rid: Optional[int] = None, **attrs):
        return self.tracer.span(name, rid, **attrs)

    def export(self, chrome_path: Optional[str] = None,
               jsonl_path: Optional[str] = None) -> Dict[str, Any]:
        out: Dict[str, Any] = {"events": len(self.tracer.events),
                               "dropped": self.tracer.dropped}
        if chrome_path:
            self.tracer.export_chrome(chrome_path)
            out["chrome"] = chrome_path
        if jsonl_path:
            self.tracer.export_jsonl(jsonl_path)
            out["jsonl"] = jsonl_path
        return out


class _NullTelemetry:
    """Shared no-op facade: every hook is an empty method or a reused
    null context manager, so a telemetry-off serve loop pays one
    attribute load + call per hook site — nothing allocates, nothing
    reads the clock."""

    enabled = False
    registry = None
    tracer = None
    __slots__ = ()

    def now(self) -> float:
        return 0.0

    def rel(self, t_monotonic: float) -> float:
        return 0.0

    def inc(self, name: str, v: float = 1) -> None:
        pass

    def set_gauge(self, name: str, v: float) -> None:
        pass

    def observe(self, name: str, v: float) -> None:
        pass

    def event(self, name: str, rid: Optional[int] = None,
              t0: Optional[float] = None, t1: Optional[float] = None,
              **attrs) -> None:
        pass

    def span(self, name: str, rid: Optional[int] = None, **attrs):
        return _NULL_CTX

    def export(self, chrome_path: Optional[str] = None,
               jsonl_path: Optional[str] = None) -> Dict[str, Any]:
        return {"events": 0, "dropped": 0}


NULL = _NullTelemetry()
