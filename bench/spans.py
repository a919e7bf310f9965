"""The serve loop's own host spans in a profiler trace.

The program opens a ``jax.profiler.TraceAnnotation`` named
``repro.serve.<phase>`` around each phase of ``PagedServeLoop.step()``
(``step``, ``admit``, ``prefill_chunk``, ``decode_step``, ``cow_copy``
...) and ``repro.serve.sync`` around every host fetch of a device value;
``repro.serve.step`` carries the loop's ``time.monotonic()`` at its start
as its ``monotonic_s`` argument.  This module reads them, beside the
benchmark's ``bench.*`` annotations and the device operations
(``bench/trace.py``), over the same window ``trace.reduce`` takes (first
``bench.step`` to the end of the last):

- ``span_s`` and ``span_n``: for each asked-for span name, the seconds
  inside the window and the number of spans that start in it.  A span
  is matched by its name, without the annotation's arguments;
- ``step_host_ms``: per ``repro.serve.step`` in the window, its length
  less the ``repro.serve.sync`` spans inside it, averaged: the host's
  own work per step, which bounds a step once the device is fast;
- ``idle_gaps``: every device idle gap longer than
  ``trace.SHORT_GAP_NS``, labelled by the innermost ``bench.*`` or
  ``repro.serve.*`` span open at its middle (``host`` where none is);
- ``clock_ns``: the capture's clock less ``time.monotonic`` (ns), the
  median over the window's steps: a program stamp ``t`` (the tracer's
  epoch plus an event's ``ts``, a scheduler's ``t_enqueue``) sits at
  ``t * 1e9 + clock_ns`` on the capture.

A trace of a program without these spans reads ``step_host_ms`` and
``clock_ns`` as None and every span as 0.  ``bench/run.py`` does not call
this module: ``bench/tools/host_spans.py`` does.
"""

from __future__ import annotations

import statistics
from collections import defaultdict

from bench import trace, xplane

PREFIXES = ("bench.", "repro.serve.")
STEP, SYNC = "repro.serve.step", "repro.serve.sync"


def host_events(path: str):
    """[(start_ns, end_ns, name, args)] of the host annotations whose
    name starts with one of ``PREFIXES``, in start order."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats)))
    return sorted(out, key=lambda e: (e[0], -e[1]))


def _inside(spans, a, b):
    return [s for s in spans if a <= s[0] and s[1] <= b]


def reduce(path: str, chips: int = 1, spans=()) -> dict:
    """The host-span numbers of one traced window (see the module's
    docstring); ``spans`` names what to total."""
    events = host_events(path)
    steps = [e for e in events if e[2] == "bench.step"]
    if not steps:
        raise ValueError("the trace holds no bench.step annotation")
    w0 = min(e[0] for e in steps)
    w1 = max(e[1] for e in steps)
    span_s = defaultdict(float)
    span_n = defaultdict(int)
    for a, b, name, _ in events:
        if name in spans and w0 <= a < w1:
            span_s[name] += (min(b, w1) - a) * 1e-9
            span_n[name] += 1
    loop_steps = [e for e in events if e[2] == STEP and w0 <= e[0] < w1]
    syncs = [e for e in events if e[2] == SYNC]
    host_ms = [((b - a) - sum(s[1] - s[0] for s in _inside(syncs, a, b)))
               * 1e-6 for a, b, _, _ in loop_steps]
    clock = [a - args["monotonic_s"] * 1e9 for a, _, _, args in loop_steps
             if "monotonic_s" in args]
    label = trace._Labeller([(a, b, n) for a, b, n, _ in events])
    planes = trace.device_ops(xplane.load(path))[:chips]
    idle_by = defaultdict(float)
    for ops in planes:
        merged = trace._union([(max(a, w0), min(b, w1))
                               for a, b, _, _ in ops if b > w0 and a < w1])
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b - a > trace.SHORT_GAP_NS:
                idle_by[label((a + b) / 2)] += b - a
    n = len(planes)
    top = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "span_s": {s: span_s[s] for s in spans},
        "span_n": {s: span_n[s] for s in spans},
        "steps": len(loop_steps),
        "step_host_ms": statistics.fmean(host_ms) if host_ms else None,
        "clock_ns": statistics.median(clock) if clock else None,
        "idle_gaps": [[k, v / n * 1e-9] for k, v in top],
    }
