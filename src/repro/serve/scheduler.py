"""SLO-aware admission queue + preemption policy for the paged loop.

The paper's core move is treating a fixed soft-logic budget as the
binding constraint and engineering the mapping/scheduling around it;
the serving analogue is the fixed KV page pool.  Once admission stops
reserving worst-case pages (``cfg.serve_on_demand_pages``), mid-decode
pool exhaustion becomes a *normal* event rather than an impossibility,
and this module supplies the machinery that makes it survivable:

- **Typed admission errors.**  ``AdmissionError`` fails a ``submit``
  fast (empty prompt, prompt past ``s_max``, prompt pages past the
  whole pool, backpressure queue limit) instead of surfacing later as
  a shape error or a serve loop that can never drain.
  ``PoolExhaustedError`` is the runtime counterpart: the pool cannot
  cover even a lone request's growth and no victim exists.  The
  degradation taxonomy extends it: ``DeadlineExceededError`` (TTL
  spent — at the door or mid-flight), ``QuotaExceededError`` (a
  tenant's queued-request share is full), and ``CancelledError``
  (client cancel; attached to the request, never raised by the loop).
- **Per-tenant fairness.**  ``Request.tenant`` labels work;
  ``peek(tenant_load=...)`` breaks effective-priority ties toward the
  tenant holding the fewest pool pages (load-weighted aging: a burst
  from one tenant cannot FIFO-starve an equal-priority peer), and
  ``peek(eligible=...)`` lets the loop pass over tenants sitting at
  their page quota while under-quota work waits — soft quotas, so a
  lone tenant still gets the whole pool (work-conserving).
- **Priority queue with aging.**  ``submit`` order is a *hint*; the
  queue is drained best-first by ``priority`` (higher = sooner), with
  FIFO among equals and a starvation-avoidance aging rule: an entry
  waiting ``aging`` scheduler ticks gains one effective priority
  level, so a steady stream of high-priority arrivals can delay but
  never permanently starve a low-priority request.
- **Preemption victims.**  On exhaustion the loop asks
  ``select_victim`` to pick the live slot to park: lowest priority
  first, then most pages held (frees the most), then least progress
  (wastes the least generated work).  ``policy='never'`` disables
  preemption — exhaustion then raises ``PoolExhaustedError``.
- **Recompute-vs-swap policy.**  With the host-RAM swap tier enabled
  (``cfg.serve_swap``), ``SwapPolicy`` decides per victim whether to
  copy its KV pages to host RAM (zero token replay at resume, pays
  PCIe/ICI transfer twice) or fall back to recompute-resume, from
  EMA-measured prefill tokens/s and copy bytes/s.
- **Recompute-resume bookkeeping.**  A preempted slot is parked as a
  ``SchedEntry`` whose ``tokens`` hold the prompt *plus every token
  generated so far*; re-admission replays them through the ordinary
  chunked-prefill path (bit-identical to the decode steps it replaces
  — the chunk and decode attention entry points compute the same
  masked contraction), so a resumed request continues exactly where an
  uninterrupted run would be.  The entry keeps the original submit
  time (TTFT is measured from first submission) and a preemption
  count.

The scheduler is pure host-side metadata — a few dozen entries scanned
per admission round; never the hot path.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterable, List, Optional, Sequence, Tuple

from repro.serve.telemetry import Histogram


class AdmissionError(ValueError):
    """A request that can never be served as submitted: reject at
    ``submit`` (fail fast) rather than hang or crash the drain."""


class DeadlineExceededError(AdmissionError):
    """The request's deadline/TTL budget is spent.  Raised by
    ``submit`` for an already-expired budget (load shedding at the
    door); attached as ``Request.error`` when the loop sheds a queued
    or live request whose deadline passed mid-flight."""


class QuotaExceededError(AdmissionError):
    """A per-tenant quota refused the request at ``submit`` (the
    tenant's queued-request share is full).  Page quotas are enforced
    softly at admission instead — see ``PagedServeLoop``."""


class CancelledError(RuntimeError):
    """The request was cancelled (client disconnect / injected cancel).
    Never raised by the loop — attached as ``Request.error`` so the
    caller gets a typed reason next to the partial output."""


class PoolExhaustedError(RuntimeError):
    """The page pool cannot cover required growth and no preemption
    victim exists (or ``serve_preempt_policy='never'`` forbids one)."""


def tenant_of(req) -> str:
    """A request's tenant label (``Request.tenant``; unset/None maps to
    the shared 'default' tenant, so single-tenant deployments never
    see quota machinery)."""
    return getattr(req, "tenant", None) or "default"


@dataclasses.dataclass
class SchedEntry:
    """One queued unit of work: a fresh request, or a preempted one
    parked for recompute-resume.

    ``tokens`` is what admission prefills — the prompt for a fresh
    request; prompt + generated-so-far for a resume (the last token's
    chunk logits then seed decoding exactly where the preempted run
    stopped).  ``out`` carries the tokens already emitted so finish
    accounting (``max_new_tokens``, eos) spans the interruption."""

    req: object                  # serve.loop.Request
    priority: int
    tokens: object               # np.ndarray [L] int32
    out: List[int]
    seq: int                     # FIFO tiebreak among equal priority
    enqueue_tick: int            # scheduler tick at (re-)enqueue (aging)
    t_submit: float              # original submit time (TTFT anchor)
    t_enqueue: float             # latest enqueue time (queue-wait stats)
    preemptions: int = 0
    deadline_s: Optional[float] = None  # TTL from t_submit (None = no
                                 # deadline); enforced by the loop at
                                 # step boundaries, survives requeues
    swap_blocks: int = 0         # full blocks this parked entry may
                                 # hold in the host SwapStore (set at
                                 # swap-out, cleared at re-admission):
                                 # cancelling/expiring the entry purges
                                 # exactly these keys so a never-
                                 # resumed victim cannot strand host
                                 # pages until LRU pressure


class Scheduler:
    """Priority-ordered admission queue + preemption victim policy."""

    POLICIES = ("priority", "never")

    def __init__(self, policy: str = "priority", aging: int = 64,
                 default_priority: int = 0):
        if policy not in self.POLICIES:
            raise ValueError(
                f"serve_preempt_policy {policy!r} not in {self.POLICIES}")
        self.policy = policy
        self.aging = int(aging)
        self.default_priority = int(default_priority)
        self._q: List[SchedEntry] = []
        self._seq = 0
        self.ticks = 0
        # stats
        self.submitted = 0
        self.requeued = 0        # preemption re-entries
        self.removed = 0         # cancels / deadline sheds while queued
        self.peak_queue = 0
        # bounded per-admission queue-wait accounting (observed at
        # ``pop``): running quantile summary + capped sample tail, O(1)
        # memory at any request volume — never a raw per-request list
        self.queue_wait_s = Histogram()

    # -- queue --------------------------------------------------------------

    def __len__(self) -> int:
        return len(self._q)

    def push(self, req, priority: Optional[int] = None) -> SchedEntry:
        """Enqueue a fresh request (``priority=None`` takes the
        configured default)."""
        prio = self.default_priority if priority is None else int(priority)
        now = time.monotonic()
        ent = SchedEntry(req=req, priority=prio, tokens=req.prompt,
                         out=[], seq=self._seq, enqueue_tick=self.ticks,
                         t_submit=now, t_enqueue=now)
        self._seq += 1
        self._q.append(ent)
        self.submitted += 1
        self.peak_queue = max(self.peak_queue, len(self._q))
        return ent

    def requeue(self, ent: SchedEntry) -> None:
        """Re-enqueue a preempted entry for recompute-resume.  It keeps
        its priority and original submit time but takes a fresh seq —
        behind same-priority FIFO peers — and a fresh aging clock."""
        ent.seq = self._seq
        self._seq += 1
        ent.enqueue_tick = self.ticks
        ent.t_enqueue = time.monotonic()
        ent.preemptions += 1
        self._q.append(ent)
        self.requeued += 1
        self.peak_queue = max(self.peak_queue, len(self._q))

    def tick(self) -> None:
        """One scheduling round (the aging clock)."""
        self.ticks += 1

    def effective_priority(self, ent: SchedEntry) -> int:
        """Priority plus the aging boost earned while waiting."""
        if self.aging <= 0:
            return ent.priority
        return ent.priority + (self.ticks - ent.enqueue_tick) // self.aging

    def peek(self, eligible=None,
             tenant_load: Optional[dict] = None) -> Optional[SchedEntry]:
        """Best admission candidate: highest effective priority, FIFO
        among equals.  Strictly best-first — a blocked best entry is
        never bypassed by a smaller lower-priority one (no head-of-line
        overtaking; aging bounds how long anything waits).

        ``tenant_load`` (tenant -> pages currently held) weights the
        tie-break only: among entries of equal effective priority the
        lightest-loaded tenant goes first, so aging works *per tenant*
        under contention — a burst from one tenant cannot FIFO-starve
        another at the same priority.  ``eligible`` restricts the
        candidate set (the loop passes the under-page-quota predicate);
        returns None when nothing qualifies."""
        cands = self._q if eligible is None \
            else [e for e in self._q if eligible(e)]
        if not cands:
            return None
        if tenant_load:
            return max(cands, key=lambda e: (
                self.effective_priority(e),
                -tenant_load.get(tenant_of(e.req), 0), -e.seq))
        return max(cands,
                   key=lambda e: (self.effective_priority(e), -e.seq))

    def pop(self, ent: SchedEntry) -> float:
        """Remove an entry the loop is admitting; records and returns
        its queue wait (time since the latest enqueue — a resume's wait
        counts from its requeue, not first submission; TTFT covers
        that)."""
        self._q.remove(ent)
        wait = time.monotonic() - ent.t_enqueue
        self.queue_wait_s.observe(wait)
        return wait

    def remove(self, ent: SchedEntry) -> None:
        """Drop a queued entry without admitting it (cancel / deadline
        shed).  No queue-wait observation — that histogram measures
        waits that ended in admission."""
        self._q.remove(ent)
        self.removed += 1

    # -- preemption ---------------------------------------------------------

    def select_victim(
        self, candidates: Iterable[Tuple[int, int, int, int]],
    ) -> Optional[int]:
        """Pick the live slot to preempt from ``(slot, priority, pages,
        progress)`` tuples: lowest priority, then most pages held (the
        park frees the most pool), then least progress (least generated
        work to recompute), then the latest-admitted slot.  Returns the
        slot id, or None when the policy forbids preemption or there
        are no candidates."""
        cands = list(candidates)
        if self.policy == "never" or not cands:
            return None
        return min(cands, key=lambda c: (c[1], -c[2], c[3], -c[0]))[0]

    # -- introspection ------------------------------------------------------

    def queued(self) -> Sequence[SchedEntry]:
        return tuple(self._q)

    def stats(self) -> dict:
        return {
            "policy": self.policy,
            "aging": self.aging,
            "queued": len(self._q),
            "submitted": self.submitted,
            "requeued": self.requeued,
            "removed": self.removed,
            "peak_queue": self.peak_queue,
            "ticks": self.ticks,
            "queue_wait_s": self.queue_wait_s.summary(),
        }

    def check(self) -> None:
        """Structural invariants (the ``serve_check_invariants`` hook):
        unique seqs, non-negative waits, no entry enqueued in the
        future."""
        seqs = [e.seq for e in self._q]
        assert len(set(seqs)) == len(seqs), "duplicate scheduler seq"
        for e in self._q:
            assert e.enqueue_tick <= self.ticks, "entry from the future"
            assert len(e.tokens) > 0, "empty entry in queue"
            assert len(e.out) < getattr(e.req, "max_new_tokens", 1 << 30), \
                "finished entry still queued"


class SwapPolicy:
    """Per-victim recompute-vs-swap decision from measured rates.

    Swapping a victim out (and later back in) moves its pages over
    PCIe/ICI twice; recompute-resume replays its tokens through chunked
    prefill once.  Swap wins exactly when::

        2 * nbytes / copy_bytes_per_s  <  replay_tokens / prefill_tok_per_s

    Both rates are exponential moving averages of what THIS deployment
    actually measures (``observe_prefill`` wraps the loop's chunked
    prefill, ``observe_copy`` wraps the staging-ring transfers) — not
    datasheet numbers, so the crossover tracks the live model size,
    interconnect, and host load.  Until both rates exist the policy is
    *optimistic* (swaps) — the only way to learn the copy rate is to
    pay for one copy, and a wrong early guess costs one transfer, not
    correctness.

    ``mode='always'`` forces swapping (tests/benches use it to pin the
    path); ``'never'`` disables it (victims recompute — the PR 6
    behaviour); ``'auto'`` applies the rate comparison.
    """

    MODES = ("auto", "always", "never")

    def __init__(self, mode: str = "auto", alpha: float = 0.25):
        if mode not in self.MODES:
            raise ValueError(
                f"swap policy {mode!r} not in {self.MODES}")
        self.mode = mode
        self.alpha = float(alpha)
        self.prefill_tok_per_s = 0.0     # 0.0 == not yet measured
        self.copy_bytes_per_s = 0.0
        self.chose_swap = 0
        self.chose_recompute = 0

    def _ema(self, old: float, sample: float) -> float:
        return sample if old == 0.0 else \
            (1.0 - self.alpha) * old + self.alpha * sample

    def observe_prefill(self, tokens: int, dt_s: float) -> None:
        if tokens > 0 and dt_s > 0.0:
            self.prefill_tok_per_s = self._ema(
                self.prefill_tok_per_s, tokens / dt_s)

    def observe_copy(self, nbytes: int, dt_s: float) -> None:
        if nbytes > 0 and dt_s > 0.0:
            self.copy_bytes_per_s = self._ema(
                self.copy_bytes_per_s, nbytes / dt_s)

    def decide(self, replay_tokens: int, nbytes: int) -> bool:
        """True → swap this victim's pages out; False → recompute."""
        if self.mode == "never":
            swap = False
        elif self.mode == "always":
            swap = True
        elif not (self.prefill_tok_per_s and self.copy_bytes_per_s):
            swap = True                  # optimistic bootstrap: learn rates
        else:
            swap_cost_s = 2.0 * nbytes / self.copy_bytes_per_s
            replay_cost_s = replay_tokens / self.prefill_tok_per_s
            swap = swap_cost_s < replay_cost_s
        if swap:
            self.chose_swap += 1
        else:
            self.chose_recompute += 1
        return swap

    def stats(self) -> dict:
        return {
            "mode": self.mode,
            "prefill_tok_per_s": self.prefill_tok_per_s,
            "copy_bytes_per_s": self.copy_bytes_per_s,
            "chose_swap": self.chose_swap,
            "chose_recompute": self.chose_recompute,
        }
