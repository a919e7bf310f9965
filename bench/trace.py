"""The reduction from a profiler trace to the numbers the per-layer metrics
read.

A ``--trace 1`` run records its window with ``jax.profiler`` (Python
tracer off).  The trace holds, on the host plane, the benchmark's own
annotations (``bench.step`` around each ``PagedServeLoop.step()``,
``bench.client`` around the clients' bookkeeping, ``bench.submit`` around
each submission) and, on each TPU plane's ``XLA Ops`` line, every device
operation with its start, duration and metadata (``bench/xplane.py``).
The reduction:

- the traced window runs from the first ``bench.step`` to the end of the
  last, on the trace's clock;
- busy time is the union of the device operations' intervals inside it,
  averaged over the chips used; idle is the rest;
- an operation whose interval holds the next one (a ``while`` around its
  body) is a container: its time is its children's, so it counts only
  toward busy time;
- a scope's device time is the sum of the durations of the operations
  whose ``tf_op`` (the ``jax.named_scope`` path) carries the scope, such
  as ``repro.lm.decode_step_paged``;
- a kernel's device time is the same sum over the operations named for
  it (``flash_decode`` matches ``flash_decode.2``); a name that matches
  nothing, or more than one distinct operation, is an error;
- every idle gap longer than ``SHORT_GAP_NS`` is labelled by the
  innermost host annotation open at its middle, or ``host`` where none
  is;
- ``breakdown`` lists the ten operation kinds (their scope path without
  the loop plumbing) that took most device time, and the ten host labels
  under which the device sat idle longest.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench import xplane

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
# gaps shorter than this are the device's own launch gaps, not host work
SHORT_GAP_NS = 20_000
_PLUMBING = re.compile(r"(while/body/|closed_call/|jit\(<lambda>\)/|:$)")


def _kind(name: str, tf_op: str) -> str:
    """What an operation is, for the breakdown: its scope path without
    the loop plumbing, or its name without the numeric suffix."""
    if tf_op:
        return _PLUMBING.sub("", tf_op)
    return re.sub(r"\.\d+$", "", name)


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def host_spans(space):
    """The benchmark's host annotations: [(start_ns, end_ns, name)]."""
    spans = []
    for plane in space.planes:
        if not plane.name.startswith("/host"):
            continue
        md = xplane.metadata(plane)
        for line in plane.lines:
            for a, b, m in xplane.events(line):
                name = md.get(m, ("", {}))[0]
                if name.startswith(HOST_PREFIX):
                    spans.append((a, b, name))
    return spans


def device_ops(space):
    """Per TPU plane: [(start_ns, end_ns, op name, tf_op)]."""
    planes = []
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        md = xplane.metadata(plane)
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for a, b, m in xplane.events(line):
                    name, stats = md.get(m, ("", {}))
                    ops.append((a, b, name, stats.get("tf_op", "")))
        planes.append(ops)
    if not any(planes):
        raise ValueError("the trace holds no operation on a TPU")
    return planes


class _Labeller:
    """The innermost benchmark annotation open at a time."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [s[0] for s in self.spans]

    def __call__(self, t: float) -> str:
        best = None
        for a, b, name in self.spans[:bisect.bisect_right(self.starts, t)]:
            if a <= t <= b and (best is None or b - a < best[1] - best[0]):
                best = (a, b, name)
        return best[2] if best else "host"


def reduce(path: str, chips: int = 1, scopes=(), kernels=()) -> dict:
    """The numbers of one traced window (see the module's docstring).
    ``scopes`` and ``kernels`` name what to total; the metric readers
    ask for them, this module names none."""
    space = xplane.load(path)
    spans = host_spans(space)
    steps = [s for s in spans if s[2] == HOST_PREFIX + "step"]
    if not steps:
        raise ValueError("the trace holds no bench.step annotation")
    w0 = min(s[0] for s in steps)
    w1 = max(s[1] for s in steps)
    label = _Labeller(spans)
    planes = device_ops(space)[:chips]
    busy_ns = 0.0
    scope_ns = defaultdict(float)
    kernel_ns = defaultdict(float)
    kernel_names = defaultdict(set)
    kind_ns = defaultdict(float)
    idle_by = defaultdict(float)
    for ops in planes:
        inside = sorted((max(a, w0), min(b, w1), n, t) for a, b, n, t in ops
                        if b > w0 and a < w1)
        merged = _union([(a, b) for a, b, _, _ in inside])
        busy_ns += sum(b - a for a, b in merged)
        leaves = [op for op, nxt in zip(inside, inside[1:] + [None])
                  if nxt is None or nxt[1] > op[1]]
        for a, b, name, tf_op in leaves:
            kind_ns[_kind(name, tf_op)] += b - a
            for s in scopes:
                if s in tf_op:
                    scope_ns[s] += b - a
            for k in kernels:
                if name == k or name.startswith(k + "."):
                    kernel_ns[k] += b - a
                    kernel_names[k].add(name)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b - a > SHORT_GAP_NS:
                idle_by[label((a + b) / 2)] += b - a
            elif b > a:
                idle_by["between device ops"] += b - a
    for k in kernels:
        if len(kernel_names[k]) != 1:
            raise ValueError(f"kernel {k!r} matched {sorted(kernel_names[k])}"
                             " in the trace; want exactly one operation")
    n = len(planes)
    top_ops = sorted(kind_ns.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns / n * 1e-9,
        "scope_s": {s: scope_ns[s] / n * 1e-9 for s in scopes},
        "kernel_s": {k: kernel_ns[k] / n * 1e-9 for k in kernels},
        "breakdown": {
            "device_ops": [[k, v / n * 1e-9] for k, v in top_ops],
            "idle_gaps": [[k, v / n * 1e-9] for k, v in top_idle],
        },
    }
