"""Paged continuous-batching serve loop — the production serving path.

Replaces the dense loop's two dominant costs at once:

- **Memory.**  Every attention layer's K/V lives in a paged pool
  (kernels/paged.py); a request owns a list of pages recorded in a
  per-slot block-table row.  Admission allocates pages, finish frees
  them — no multi-GB cache copies, no left-padding, no shared decode
  clock (each slot advances at its own position).
- **Compiles.**  Prompts are prefilled in fixed-size chunks appended to
  the slot's pages, so the whole compile set is at most THREE forward
  shapes: one ``[1, chunk]`` prefill chunk, one ``[B, 1]`` decode
  step, and (speculation enabled) one ``[B, k+1]`` verify window — for
  *any* mix of prompt lengths.  The dense loop's ``refill_quantum``
  length-quantisation workaround (and its per-length retraces) is
  gone; admission happens the moment a slot and pages are free.
- **Decode amortisation.**  Self-speculative decoding
  (``cfg.serve_spec_k`` > 0): a model-free drafter (serve/spec.py,
  prompt-lookup n-grams by default; a small-model drafter plugs into
  the same protocol) proposes up to ``k`` tokens per live slot, one
  batched verify forward scores all ``k+1`` positions through the
  same paged attention, and greedy acceptance keeps the longest draft
  prefix matching the model's own argmax chain plus one bonus token —
  1 to ``k+1`` tokens per weight pass.  Rejected rows roll back by
  simply not advancing ``lens``: their page writes sit at positions
  beyond every future mask until plain writes overwrite them, and
  padding rows of the fixed window are routed to the scratch page.
  Outputs are bit-identical to plain greedy decode at every accept
  rate (the acceptance rule replays the argmax chain exactly).
- **KV bandwidth / capacity.**  ``cfg.serve_kv_dtype`` (ctor
  ``kv_dtype``) stores the paged pool quantised — int8, or int4 packed
  two codes per byte — with per-page-slot absmax scales next to the
  codes (kernels/paged.KVQuantSpec).  Writes quantise, the attention
  readers dequantise in-kernel, so decode's KV traffic and the pool's
  bytes both shrink ~2x / ~4x — which is more live slots at a fixed
  memory budget.  The dense oracle applies the identical round-trip to
  its cache, so paged-vs-dense bit-exactness holds at equal
  quantisation; fp (the default) is byte-for-byte the old layout.
- **Recompute.**  A radix-tree prefix cache (serve/prefix_cache.py)
  keys finished prompts' pages by token content.  Admission maps the
  longest cached page-aligned prefix read-only into the slot's block
  table and prefills only the suffix — shared-system-prompt traffic
  pays O(suffix) prefill, not O(prompt).  Pages are ref-counted;
  writes that would land on a shared page copy-on-write first (fresh
  page + device page copy + block-table swap), so a cached page's
  content is immutable for as long as anything references it.
- **Concurrency.**  Page accounting at admission is *on-demand* by
  default (``cfg.serve_on_demand_pages``): admission covers only the
  padded prefill (minus prefix-cache hits, plus CoW copies), and
  decode pages are allocated lazily at page-boundary crossings — so
  concurrency is bounded by the *live working set*, not the sum of
  worst cases, and a quantised pool's extra slots are actually
  admissible.  The price is that mid-decode exhaustion becomes a
  normal event; serve/scheduler.py makes it survivable:

  * ``submit`` is SLO-aware and fails fast with a typed
    ``AdmissionError`` for requests that can never fit (empty prompt,
    prompt past ``s_max``, prompt pages past the whole pool) and for
    backpressure (``cfg.serve_queue_limit``); the queue drains
    best-first by priority with FIFO among equals and an aging rule
    so nothing starves.
  * On exhaustion, the loop preempts a victim slot (lowest priority,
    then most pages, then least progress): its full pages transfer
    into the prefix cache (evictable under further pressure — the
    eviction/preemption interplay), the rest free, and the request is
    parked with its generated-so-far tokens.
  * Re-admission *recomputes*: the parked prompt + generated tokens
    replay through the ordinary chunked-prefill path, whose logits
    are bit-identical to the decode steps they replace — so a
    preempt→recompute→resume run emits exactly the tokens an
    uninterrupted run would, with speculation and quantised KV on.
    (The prefix-cache transfer usually turns the replay into a
    cheap suffix prefill.)
  * With the host-RAM swap tier on (``cfg.serve_swap``), a victim's
    written pages can instead be copied device→host (codes + scales —
    quantised pools swap losslessly) and restored into fresh pages at
    resume *before* the block table maps them: zero token replay, at
    the price of two transfers.  ``scheduler.SwapPolicy`` picks
    recompute-vs-swap per victim from EMA-measured prefill and copy
    rates; the host store (serve/swap.py) is content-addressed with
    the radix tree's keys, so swapped prefixes stay shareable and the
    store may LRU-evict freely (an evicted page only costs recompute).
    Restores are bit-identical by construction: raw bytes round-trip,
    nothing is re-quantised.

  ``cfg.serve_on_demand_pages=False`` restores worst-case reservation
  (``prompt + max_new`` pages up front): mid-decode exhaustion is
  impossible by construction, concurrency is pessimistic.
  Speculative drafts never justify preemption: a draft that cannot
  get pages is truncated instead (the mandatory one-token write is
  the only growth worth preempting for).

Physical page 0 is the pool's scratch page: permanently pinned, idle
slots' decode writes land there and freed rows are reset to it, so a
stale block-table row can never alias live pages.

Supported families: every block kind must keep a paged-able cache
(``lm.supports_paged`` — gqa attention, dense or MoE FFN).  Recurrent
and enc-dec families carry O(1)/cross state instead of a KV cache and
stay on the dense ``ServeLoop``.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune
from repro.kernels.paged import PageSpec, spec_for
from repro.models import lm
from repro.serve.faults import make_injector
from repro.serve.loop import Request
from repro.serve.prefix_cache import PrefixCache
from repro.serve.scheduler import (AdmissionError, CancelledError,
                                   DeadlineExceededError,
                                   PoolExhaustedError, QuotaExceededError,
                                   SchedEntry, Scheduler, SwapPolicy,
                                   tenant_of)
from repro.serve.spec import make_drafter
from repro.serve.swap import StagingRing, SwapStore
from repro.serve.telemetry import NULL, Histogram, Telemetry, annotate


class PageManager:
    """Host-side ref-counted physical-page pool.

    Page 0 is the pool's scratch page: permanently pinned (refcount 1
    at construction, released by nobody), never handed out.  Every
    other page is either on the free list (refcount 0) or referenced
    (refcount >= 1: one per owning slot/tree entry, +1 per additional
    sharer).  ``release`` returns a page to the free list only at
    refcount 0; double-frees and frees of the scratch page raise."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self.free = deque(range(1, n_pages))
        self.refcnt = np.zeros(n_pages, np.int64)
        self.refcnt[0] = 1   # scratch page: pinned for the pool's lifetime
        self.allocs = 0      # pages handed out (stats)
        self.frees = 0       # pages returned to the free list (stats)
        self.peak = 0        # peak pages in use
        self.exhaustions = 0  # allocs that found the pool short (stats)

    @property
    def in_use(self) -> int:
        return self.n_pages - 1 - len(self.free)

    @property
    def available(self) -> int:
        return len(self.free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self.free):
            self.exhaustions += 1
            return None
        pages = [self.free.popleft() for _ in range(n)]
        for p in pages:
            if self.refcnt[p] != 0:
                raise AssertionError(
                    f"free list corrupt: page {p} has refcount "
                    f"{self.refcnt[p]}"
                )
            self.refcnt[p] = 1
        self.allocs += n
        self.peak = max(self.peak, self.in_use)
        return pages

    def retain(self, pages: List[int]) -> None:
        """One more reference per page (sharing an already-live page)."""
        for p in pages:
            if self.refcnt[p] <= 0:
                raise ValueError(f"retain of free page {p}")
            self.refcnt[p] += 1

    def release(self, pages: List[int]) -> None:
        """Drop one reference per page; a page rejoins the free list
        only when its last reference goes."""
        for p in pages:
            p = int(p)
            if p == 0:
                raise ValueError("release of scratch page 0")
            if self.refcnt[p] <= 0:
                raise ValueError(f"double free of page {p}")
            self.refcnt[p] -= 1
            if self.refcnt[p] == 0:
                self.free.append(p)
                self.frees += 1

    def check(self) -> None:
        """Free-list/refcount invariant: pages 1..n-1 partition exactly
        into {free, refcount 0} and {off-list, refcount >= 1}; the
        scratch page is pinned and never listed."""
        free = list(self.free)
        assert len(set(free)) == len(free), "duplicate page on free list"
        assert 0 not in free, "scratch page on free list"
        assert self.refcnt[0] >= 1, "scratch page unpinned"
        fs = set(free)
        for p in range(1, self.n_pages):
            if p in fs:
                assert self.refcnt[p] == 0, \
                    f"page {p} free with refcount {self.refcnt[p]}"
            else:
                assert self.refcnt[p] >= 1, \
                    f"page {p} leaked (off-list, refcount 0)"


class PagedServeLoop:
    """Slot-based continuous batching over a paged KV cache.

    Greedy decoding; same ``Request`` protocol as the dense loop
    (plus an optional per-request ``priority`` — higher admits
    sooner).  ``prefix_cache=None`` follows ``cfg.serve_prefix_cache``;
    ``on_demand=None`` follows ``cfg.serve_on_demand_pages``."""

    def __init__(self, params, cfg, batch_slots: int = 4, s_max: int = 128,
                 eos_id: Optional[int] = None, page_size: int = 16,
                 chunk: int = 16, n_pages: Optional[int] = None,
                 attn_impl: Optional[str] = None,
                 prefix_cache: Optional[bool] = None,
                 spec_k: Optional[int] = None, drafter=None,
                 kv_dtype: Optional[str] = None,
                 on_demand: Optional[bool] = None,
                 preempt_policy: Optional[str] = None,
                 swap: Optional[bool] = None,
                 swap_bytes: Optional[int] = None,
                 swap_policy: Optional[str] = None,
                 check_invariants: Optional[bool] = None,
                 telemetry: Optional[bool] = None,
                 trace_path: Optional[str] = None,
                 tenant_page_quota: Optional[int] = None,
                 tenant_swap_bytes: Optional[int] = None,
                 tenant_queue_limit: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 faults=None):
        if not lm.supports_paged(cfg):
            raise ValueError(
                f"config {cfg.name!r} has non-pageable block kinds; "
                "use serve.loop.ServeLoop (dense caches)"
            )
        if attn_impl is not None:
            cfg = dataclasses.replace(cfg, serve_paged_attn_impl=attn_impl)
        if kv_dtype is not None:
            # quantised KV pool (kernels/paged.KVQuantSpec): int8/int4
            # codes + per-page-slot scales, dequant fused in-kernel.
            # Validated eagerly — a bad dtype should fail construction,
            # not the first forward.
            cfg = dataclasses.replace(cfg, serve_kv_dtype=kv_dtype)
        self.kv_spec = lm.kv_qspec(cfg)
        self.params, self.cfg = params, cfg
        self.B, self.S_max = batch_slots, s_max
        self.eos_id = eos_id
        self.chunk = chunk
        self.spec: PageSpec = spec_for(s_max, batch_slots,
                                       page_size=page_size, n_pages=n_pages)
        # the padded tail of a last chunk writes up to ceil(L/C)*C - 1;
        # every such position must fall inside the slot's allocatable
        # blocks, else the block-table lookup would clamp the garbage
        # writes onto the slot's last LIVE page (silent corruption)
        padded_max = -(-s_max // chunk) * chunk
        if padded_max > self.spec.s_alloc:
            raise ValueError(
                f"chunk={chunk} pads prompts up to {padded_max} tokens, "
                f"past the block-table range {self.spec.s_alloc} "
                f"(= ceil(s_max/page_size)*page_size); pick chunk/page_size "
                "so padded prefills stay within allocatable pages"
            )
        self.pages = PageManager(self.spec.n_pages)
        self.on_demand = bool(
            getattr(cfg, "serve_on_demand_pages", True)
            if on_demand is None else on_demand)
        # validated eagerly by the Scheduler ctor (bad policy names
        # should fail construction, not the first exhaustion)
        self.sched = Scheduler(
            policy=(preempt_policy if preempt_policy is not None
                    else getattr(cfg, "serve_preempt_policy", "priority")),
            aging=getattr(cfg, "serve_sched_aging", 64),
            default_priority=getattr(cfg, "serve_priority_default", 0))
        self.queue_limit = int(getattr(cfg, "serve_queue_limit", 0))
        # per-tenant fairness knobs (0 = off).  The page quota is SOFT:
        # _next_entry passes over a tenant sitting at its quota only
        # while an under-quota tenant waits — a lone tenant still gets
        # the whole pool (work-conserving).  The queue limit is hard
        # (typed QuotaExceededError at submit).
        self.tenant_page_quota = int(
            getattr(cfg, "serve_tenant_page_quota", 0)
            if tenant_page_quota is None else tenant_page_quota)
        self.tenant_queue_limit = int(
            getattr(cfg, "serve_tenant_queue_limit", 0)
            if tenant_queue_limit is None else tenant_queue_limit)
        # default per-request TTL (Request.deadline_s overrides; 0/None
        # = no deadline).  Enforced at step boundaries, never mid-step.
        self.deadline_s = float(
            getattr(cfg, "serve_deadline_s", 0.0)
            if deadline_s is None else deadline_s)
        # seeded fault injection (serve/faults.py): None => the shared
        # inert twin, so production sites cost one attribute read.
        # Constructed before the swap store, which threads the same
        # injector through its put path.
        self.faults = make_injector(faults)
        self._injected_block = False   # an admission blocked by an
                                       # injected fault this step (the
                                       # no-live-slots exhaustion raise
                                       # must not fire on fake faults)
        # host-RAM page swap tier (serve/swap.py): preemption victims'
        # pages copy device->host and restore at resume instead of
        # recomputing from tokens; scheduler.SwapPolicy decides per
        # victim.  `swap=None` follows cfg.serve_swap; off => all three
        # attributes are None and every swap site below is one `is not
        # None` check (the telemetry-facade pattern).
        swap_on = bool(getattr(cfg, "serve_swap", False)
                       if swap is None else swap)
        if swap_on:
            self.swap: Optional[SwapStore] = SwapStore(
                page_size,
                max_bytes=int(getattr(cfg, "serve_swap_bytes", 0)
                              if swap_bytes is None else swap_bytes),
                tenant_budget=int(
                    getattr(cfg, "serve_tenant_swap_bytes", 0)
                    if tenant_swap_bytes is None else tenant_swap_bytes),
                faults=self.faults)
            self.swap_policy: Optional[SwapPolicy] = SwapPolicy(
                mode=(getattr(cfg, "serve_swap_policy", "auto")
                      if swap_policy is None else swap_policy))
            self.swap_ring: Optional[StagingRing] = StagingRing(
                width=int(getattr(cfg, "serve_swap_ring_pages", 8)))
        else:
            self.swap = None
            self.swap_policy = None
            self.swap_ring = None
        self.check_invariants = bool(
            getattr(cfg, "serve_check_invariants", False)
            if check_invariants is None else check_invariants)
        # unified observability (serve/telemetry.py): lifecycle tracer +
        # metrics registry when enabled; the shared NULL no-op facade
        # otherwise, so every instrumentation site below costs one
        # attribute lookup and a pass when off.  The jax.profiler spans
        # (telemetry.annotate) are on either way.  Purely host-side —
        # the compile set is unaffected.
        tel_on = bool(getattr(cfg, "serve_telemetry", False)
                      if telemetry is None else telemetry)
        self.tel = Telemetry() if tel_on else NULL
        self.trace_path = str(
            getattr(cfg, "serve_trace_path", "")
            if trace_path is None else trace_path)
        if prefix_cache is None:
            prefix_cache = getattr(cfg, "serve_prefix_cache", True)
        # construction-time setting: _finish keys its page-transfer
        # decision off this flag, NOT off `self.prefix is (not) None`,
        # so a mid-flight toggle of the attribute can neither divert a
        # cache-less loop's pages into a foreign tree nor change the
        # accounting of requests admitted under the original setting
        self._prefix_enabled = bool(prefix_cache)
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(page_size, self.pages,
                        max_pages=getattr(cfg, "serve_prefix_cache_pages", 0),
                        tel=self.tel)
            if prefix_cache else None
        )
        if spec_k is None:
            spec_k = getattr(cfg, "serve_spec_k", 0)
        self.spec_k = int(spec_k)
        if self.spec_k > 0:
            self.drafter = make_drafter(
                drafter if drafter is not None
                else getattr(cfg, "serve_spec_drafter", "ngram"))
        else:
            self.drafter = None
            if drafter is not None and make_drafter(drafter) is not None:
                raise ValueError(
                    "a drafter was passed but speculation is off; set "
                    "spec_k > 0 (or cfg.serve_spec_k) to enable it"
                )
        if self.drafter is not None:
            # verify attention has no impl dispatch (the flash paths
            # are single-query): it always runs the gather + _sdpa
            # oracle contraction.  Pin the decode step to the same
            # 'lax' oracle so a tuned flash winner can never mix two
            # numerically different kernels into one output stream —
            # the bit-identical-at-every-accept-rate contract must
            # hold under ANY autotune cache state.  Cheap: with a
            # drafter on, plain decode steps are the rare case.  An
            # explicitly requested conflicting impl is an error, not a
            # silent override.
            if attn_impl is not None and attn_impl != "lax":
                raise ValueError(
                    f"attn_impl={attn_impl!r} conflicts with "
                    "speculative decoding: verify attention always "
                    "runs the lax oracle contraction, so the decode "
                    "step is pinned to 'lax' to keep one output "
                    "stream on one kernel — pass attn_impl='lax' (or "
                    "None), or disable speculation"
                )
            cfg = dataclasses.replace(cfg, serve_paged_attn_impl="lax")
            self.cfg = cfg
        self.caches, _ = lm.init_caches(cfg, batch_slots, s_max,
                                        paged=self.spec)
        self.done: List[Request] = []
        # requests terminated WITHOUT completing — cancelled or past
        # deadline, each carrying a typed Request.error and its partial
        # output.  Disjoint from `done` (run() keeps its contract of
        # returning completions only).
        self.failed: List[Request] = []
        self.cancelled = 0            # client/injected cancels
        self.expired = 0              # deadline/TTL sheds
        # per-tenant terminal counters ({tenant: {completed, cancelled,
        # expired}}); live pages/queue depth are derived on demand
        self.tenant_counters: dict = {}
        self.refills = 0              # mid-decode slot admissions (stats)
        self.prefill_tokens_run = 0   # chunk tokens actually prefilled
        self.prefill_tokens_saved = 0  # chunk tokens skipped via the cache
        self.cow_copies = 0           # copy-on-write page duplications
        self.decode_steps = 0         # plain [B, 1] decode forwards
        self.spec_steps = 0           # [B, k+1] verify forwards
        self.spec_proposed = 0        # draft tokens offered to verify
        self.spec_accepted = 0        # draft tokens the argmax confirmed
        self.gen_tokens = 0           # tokens emitted by decode/verify
                                      # (prefill argmax tokens excluded)
        self.slot_steps = 0           # live-slot participations in
                                      # decode/verify forwards: plain
                                      # decode emits exactly 1 token
                                      # per slot-step, so tokens/step
                                      # is the per-slot amortisation
                                      # factor, not a batching artifact
        # scheduler / preemption stats (the SLO bench's numbers)
        self.preemptions = 0          # slots parked on pool exhaustion
        self.resumes = 0              # parked requests re-admitted
        self.resume_prefill_tokens = 0  # chunk tokens replayed at resume
        self.preempted_tokens = 0     # KV positions dropped at preempt
        # swap-tier traffic counters (the swap bench's numbers)
        self.swapped_out_pages = 0    # pages landed in the host store
        self.swapped_in_pages = 0     # host pages restored to device
        self.swap_out_bytes = 0       # device->host bytes moved
        self.swap_in_bytes = 0        # host->device bytes moved
        self.swap_restored_tokens = 0  # positions resumed WITHOUT replay
        self.grown_pages = 0          # on-demand page-boundary allocs
        self.peak_live_slots = 0      # max concurrently live slots
        # per-request time-to-first-token: bounded histogram (running
        # quantile summary + capped tail), O(1) memory at any request
        # volume.  Queue waits live on the Scheduler (observed at pop).
        self.ttft_s = Histogram()

        # host-side scheduler state (numpy; shipped to device per step)
        self.block_table = np.zeros((batch_slots, self.spec.max_blocks),
                                    np.int32)
        self.lens = np.zeros(batch_slots, np.int32)
        self.slots: List[Optional[dict]] = [None] * batch_slots

        # the ONLY jitted forward shapes the loop ever compiles: one
        # prefill chunk, one decode step, and — speculation enabled —
        # one verify window.  (The CoW page copy below is a
        # cache-to-cache device memcpy, not a forward pass; it adds
        # exactly one more trace of its own.)  Every cache-writing jit
        # donates the cache on every backend, so the pool is updated in
        # place and a stale reference to it fails loudly wherever the
        # tests run, not first on the chip.
        self._prefill_chunk = jax.jit(
            lambda p, c, t, start, bt_row, last: lm.prefill_chunk(
                p, c, t, start, bt_row, cfg, last=last),
            donate_argnums=(1,),
        )
        self._decode = jax.jit(
            lambda p, c, t, pos, bt: lm.decode_step_paged(
                p, c, t, pos, bt, cfg),
            donate_argnums=(1,),
        )
        self._verify = jax.jit(
            lambda p, c, t, pos, nw, bt: lm.verify_step_paged(
                p, c, t, pos, nw, bt, cfg),
            donate_argnums=(1,),
        ) if self.drafter is not None else None
        # a fresh lambda per loop keeps the jit cache (and its
        # _cache_size trace count) per-instance, like the two above
        self._copy_page = jax.jit(
            lambda c, src, dst: lm.cache_copy_page(c, src, dst),
            donate_argnums=(0,))
        # swap gather/scatter: fixed ring-width page moves, so exactly
        # one trace each for the loop's lifetime (asserted in
        # check_compiled; compiled_shapes() stays the three forward
        # entry points).  Built only with the tier on — an idle loop
        # carries zero extra jit state.
        if swap_on:
            self._swap_gather = jax.jit(
                lambda c, pids: lm.cache_swap_out(c, pids))
            self._swap_scatter = jax.jit(
                lambda c, s, pids: lm.cache_swap_in(c, s, pids),
                donate_argnums=(0,))
        else:
            self._swap_gather = None
            self._swap_scatter = None

    # -- admission ----------------------------------------------------------

    def submit(self, req: Request):
        """Enqueue a request, SLO-aware: anything that can *never* be
        served fails fast here with a typed ``AdmissionError`` (a
        subclass of ValueError) instead of surfacing later as a shape
        error or a drain that can never make progress.  The degradation
        taxonomy sheds load at the door too: an already-spent deadline
        raises ``DeadlineExceededError``, a tenant at its queued-share
        limit raises ``QuotaExceededError``.

        Ordering contract (regression-tested): every check runs before
        the push and the telemetry event — a rejected submit leaves
        ZERO residue in the scheduler, the counters, or the trace."""
        L = len(req.prompt)
        if not 0 < L <= self.S_max:
            raise AdmissionError(
                f"prompt length {L} outside (0, s_max={self.S_max}]"
            )
        usable = self.spec.n_pages - 1
        if self._prefill_blocks(L) > usable:
            # not mitigable by the prefix cache: even fully-cached
            # prompt blocks are distinct physical pages of this pool
            raise AdmissionError(
                f"request {req.rid} can never fit: prompt needs "
                f"{self._prefill_blocks(L)} pages, pool has {usable}"
            )
        dl = getattr(req, "deadline_s", None)
        if dl is None and self.deadline_s > 0:
            dl = self.deadline_s
        if dl is not None and dl <= 0:
            raise DeadlineExceededError(
                f"request {req.rid} submitted with a spent deadline "
                f"budget ({dl}s); shed at the door"
            )
        tenant = tenant_of(req)
        if self.tenant_queue_limit:
            n_t = sum(1 for e in self.sched.queued()
                      if tenant_of(e.req) == tenant)
            if n_t >= self.tenant_queue_limit:
                raise QuotaExceededError(
                    f"tenant {tenant!r} at serve_tenant_queue_limit="
                    f"{self.tenant_queue_limit}; retry later"
                )
        if self.queue_limit and len(self.sched) >= self.queue_limit:
            raise AdmissionError(
                f"backpressure: queue at serve_queue_limit="
                f"{self.queue_limit}; retry later"
            )
        ent = self.sched.push(req, getattr(req, "priority", None))
        ent.deadline_s = dl
        self.tel.event("submit", req.rid, prompt_tokens=L,
                       priority=ent.priority, tenant=tenant)

    def _prefill_blocks(self, L: int) -> int:
        """Blocks the padded chunk prefill of ``L`` tokens writes."""
        C, P = self.chunk, self.spec.page_size
        return -(-min(-(-L // C) * C, self.spec.s_alloc) // P)

    def _worst_blocks(self, L: int, max_new: int) -> int:
        """Block-table entries a request of ``L`` tokens could ever
        touch: the padded prefill plus decode growth.  Decode writes
        positions [L, L + max_new - 1); final length is capped at
        S_max (the loop finishes a slot at capacity).  The clamp is
        s_alloc, not S_max: the padded prefill tail may spill past
        S_max within the last allocatable block (the __init__ guard
        bounds it by s_alloc), and those writes need their page."""
        C, P = self.chunk, self.spec.page_size
        hi = min(max(-(-L // C) * C, L + max_new - 1), self.spec.s_alloc)
        return -(-hi // P)

    def _admit_blocks(self, ent: SchedEntry) -> int:
        """Blocks admission must cover for ``ent``: the padded prefill
        only (on-demand: decode pages are allocated lazily at
        page-boundary crossings) or worst-case through the remaining
        ``max_new`` budget (reserved).  For a resume, ``ent.tokens``
        already includes the generated tokens and ``ent.out`` has
        consumed part of the budget — the worst case is the same
        absolute final position as the uninterrupted run's."""
        L = len(ent.tokens)
        if self.on_demand:
            return self._prefill_blocks(L)
        return self._worst_blocks(L, ent.req.max_new_tokens - len(ent.out))

    def _plan(self, ent: SchedEntry, n_cached: int, n_swap: int = 0):
        """Admission plan given ``n_cached`` matched prefix blocks and
        ``n_swap`` consecutive host-store blocks after them.

        The first position that must still run the forward pass is
        ``p0 = min((n_cached + n_swap) * P, L - 1)`` — the last token
        always reruns (its logits seed decoding), so a fully-covered
        prompt still prefills its final chunk.  Chunks start on C
        boundaries, so the first live chunk is ``ci0 = p0 // C``; any
        *cached* block overlapping the written range ``[ci0*C, ...)``
        must be copy-on-write duplicated (the recompute rewrites part
        of it, and positions below ``ci0*C`` inside it are served by
        the copy).  Swap-restored blocks never need CoW: they land in
        freshly-allocated private pages, and a recompute overlapping
        one rewrites byte-identical KV (the replayed forward is the
        same pure function of the same tokens).  Returns
        (total_blocks, ci0, n_keep, n_cow, need, n_swap): ``n_keep``
        cached blocks stay mapped read-only, ``n_cow`` are duplicated,
        ``need`` fresh pages cover CoW copies, restored blocks, and
        all remaining blocks."""
        C, P = self.chunk, self.spec.page_size
        L = len(ent.tokens)
        total = self._admit_blocks(ent)
        n_cached = min(n_cached, total)
        n_swap = min(n_swap, total - n_cached)
        p0 = min((n_cached + n_swap) * P, L - 1)
        ci0 = p0 // C
        w0_blk = (ci0 * C) // P
        n_keep = min(n_cached, w0_blk)
        n_cow = n_cached - n_keep
        need = (total - n_cached) + n_cow
        return total, ci0, n_keep, n_cow, need, n_swap

    def _pages_needed(self, req: Request, n_cached: int = 0) -> int:
        """Fresh pages admission must allocate for a fresh ``req``.
        With a prefix-cache match, already-cached blocks are mapped,
        not reserved — only non-cached blocks plus CoW copies cost
        pool pages."""
        return self._plan(self._transient_entry(req), n_cached)[4]

    def _transient_entry(self, req: Request) -> SchedEntry:
        """A throwaway entry for planning/error paths (never queued)."""
        return SchedEntry(req=req, priority=0, tokens=req.prompt, out=[],
                          seq=-1, enqueue_tick=0, t_submit=0.0,
                          t_enqueue=0.0)

    def _match_blocks(self, ent: SchedEntry) -> int:
        """Cached full-page prefix length (blocks) for an entry,
        without taking references or stats (planning/error paths)."""
        if self.prefix is None:
            return 0
        return len(self.prefix.match(ent.tokens, record=False))

    def _tenant_pages(self) -> dict:
        """Pool pages each tenant's live slots currently reference
        (shared pages count once per referencing tenant — what matters
        for fairness is the footprint a tenant's slots pin)."""
        held: dict = {}
        for s in self.slots:
            if s is not None:
                t = tenant_of(s["req"])
                held[t] = held.get(t, 0) + len(s["blocks"])
        return held

    def _next_entry(self) -> Optional[SchedEntry]:
        """The admission head under tenant fairness: strictly
        best-first (effective priority, load-weighted tie-break, FIFO)
        — except that a tenant sitting at its page quota is passed
        over while any under-quota tenant has work queued.  Soft and
        work-conserving: with only over-quota work waiting, the best
        entry admits anyway (quotas shape contention, they never idle
        the pool)."""
        held = self._tenant_pages()
        ent = self.sched.peek(tenant_load=held)
        if (ent is not None and self.tenant_page_quota
                and held.get(tenant_of(ent.req), 0)
                >= self.tenant_page_quota):
            alt = self.sched.peek(
                eligible=lambda e: (held.get(tenant_of(e.req), 0)
                                    < self.tenant_page_quota),
                tenant_load=held)
            if alt is not None:
                ent = alt
        return ent

    def _alloc_with_evict(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages, evicting LRU unreferenced cached
        prefixes under pool pressure (locked/mapped pages are refcount
        >= 2 and can never be victims).  Eviction only runs when it can
        actually cover the shortfall — a blocked request retried every
        refill round must not strip the tree without admitting."""
        pages = self.pages.alloc(n)
        if pages is None and self.prefix is not None:
            short = n - self.pages.available
            if self.prefix.evictable() >= short:
                self.prefix.evict(short)
                pages = self.pages.alloc(n)
        return pages

    def _cow(self, src: int, dst: int) -> None:
        """Copy-on-write: duplicate physical page ``src`` into the
        freshly-allocated ``dst`` across every layer's K/V pool."""
        t0 = self.tel.now()
        with annotate("repro.serve.cow_copy"):
            self.caches = self._copy_page(self.caches, jnp.int32(src),
                                          jnp.int32(dst))
        self.tel.event("cow_copy", t0=t0, t1=self.tel.now(), src=src,
                       dst=dst)
        self.cow_copies += 1

    def _run_prefill_chunks(self, tokens, row, ci0: int, rid):
        """Prefill ``tokens`` from chunk ``ci0`` on through the slot
        whose block-table row is ``row``: every chunk padded to the one
        compiled ``[1, chunk]`` shape.  Returns the last chunk's logits
        at the last token (None when no chunk runs)."""
        tel, C, L = self.tel, self.chunk, len(tokens)
        bt_row = jnp.asarray(row)
        n_chunks = -(-L // C)
        logits = None
        for ci in range(ci0, n_chunks):
            buf = np.zeros(C, np.int32)
            seg = tokens[ci * C:(ci + 1) * C]
            buf[: len(seg)] = seg
            last = (L - 1) - ci * C if ci == n_chunks - 1 else 0
            t0c = tel.now()
            with annotate("repro.serve.prefill_chunk"):
                logits, self.caches = self._prefill_chunk(
                    self.params, self.caches, jnp.asarray(buf[None]),
                    jnp.int32(ci * C), bt_row, jnp.int32(last),
                )
            tel.event("prefill_chunk", rid, t0=t0c, t1=tel.now(),
                      chunk=ci, start=ci * C, tokens=C)
        return logits

    def _admit(self, slot_i: int) -> str:
        """Prefill the scheduler's best entry into a free slot.
        Returns 'admitted' (live slot installed), 'finished' (the
        request completed on its first token — the slot is free
        again), or 'blocked' (empty queue / pool exhausted: the best
        entry waits; lower-priority entries never overtake it).

        A resumed entry's ``tokens`` are prompt + generated-so-far:
        the replayed chunk prefill recomputes the dropped KV (minus
        whatever the prefix cache kept from the preemption transfer)
        and its last-position logits continue the argmax chain
        bit-identically to the decode step the preemption cut off."""
        ent = self._next_entry()
        if ent is None:
            return "blocked"
        if self.faults.fire("admit_stall"):
            # injected transient contention: the head waits one round
            self._injected_block = True
            return "blocked"
        with annotate("repro.serve.admit"):
            return self._admit_entry(slot_i, ent)

    def _admit_entry(self, slot_i: int, ent: SchedEntry) -> str:
        """``_admit`` for the head entry ``ent``: match, plan, alloc
        (or 'blocked'), then pop, CoW, block-table row, the prefill
        chunks and the first token's sync."""
        t_start = self.tel.now()
        tokens = ent.tokens
        L = len(tokens)
        # record=False: a blocked head re-matches every refill round;
        # stats are recorded once per ADMITTED request below
        hits = self.prefix.match(tokens, record=False) \
            if self.prefix is not None else []
        # host-store hits fill in AFTER the device hits: only a
        # consecutive run is mappable, and a block resident on device
        # is strictly cheaper than restoring its host copy
        swap_hits = self.swap.match(tokens, start_block=len(hits)) \
            if self.swap is not None else []
        total, ci0, n_keep, n_cow, need, n_swap = self._plan(
            ent, len(hits), len(swap_hits))
        hits = hits[: n_keep + n_cow]
        swap_hits = swap_hits[:n_swap]
        if hits:
            # hold the matched pages so pressure-eviction (possibly our
            # own, below) can never reclaim them out from under us
            self.prefix.lock(hits)
        if self.faults.fire("alloc"):
            # injected exhaustion: behave exactly like a real short
            # pool — drop the locks and wait (the pool is untouched)
            if hits:
                self.pages.release([n.page_id for n in hits])
            self._injected_block = True
            return "blocked"
        page_ids = self._alloc_with_evict(need)
        if page_ids is None and hits:
            # the locked hits themselves can pin the pool (their pages
            # are ineligible for eviction while we hold them): fall
            # back to a cache-less admission — drop the locks, evict,
            # and recompute the whole prompt.  Restores the dense-pool
            # liveness guarantee: a request that fits worst-case always
            # admits once every slot is free.  Host-store hits pin no
            # pool pages, so they are re-matched from block 0 — the
            # content-addressed store may now cover blocks the tree
            # served before.
            self.pages.release([n.page_id for n in hits])
            hits = []
            swap_hits = self.swap.match(tokens, start_block=0) \
                if self.swap is not None else []
            total, ci0, n_keep, n_cow, need, n_swap = self._plan(
                ent, 0, len(swap_hits))
            swap_hits = swap_hits[:n_swap]
            page_ids = self._alloc_with_evict(need)
        if page_ids is None:
            return "blocked"              # pool exhausted: request waits
        wait = self.sched.pop(ent)
        if ent.req.queue_wait_s is None:
            ent.req.queue_wait_s = wait   # first admission only
        # the entry is live again: any host-store pages it parked are
        # plain shareable cache from here on (LRU-governed), no longer
        # owned by a waiting request — cancel purges apply only while
        # swapped OUT
        ent.swap_blocks = 0
        tel, rid = self.tel, ent.req.rid
        t_adm = tel.now()
        # the queued span covers the latest (re-)enqueue; resumes show
        # preempted -> queued -> resumed on the request's track
        tel.event("queued", rid, t0=tel.rel(ent.t_enqueue), t1=t_adm,
                  preemptions=ent.preemptions)
        if swap_hits:
            tel.event("swapped_in", rid, blocks=len(swap_hits))
        tel.event("resumed" if ent.out else "admitted", rid,
                  cached_blocks=len(hits), restored_blocks=len(swap_hits),
                  fresh_pages=need, cow=n_cow)
        C, P = self.chunk, self.spec.page_size
        if self.prefix is not None:
            # one lookup record per admitted request (post-fallback:
            # if the cache-less path ran, the cache contributed nothing)
            self.prefix.record_lookup(len(hits), L // P - len(hits))

        blocks = np.zeros(total, np.int32)
        shared = np.zeros(total, bool)
        for b, node in enumerate(hits):
            blocks[b] = node.page_id
            shared[b] = True
        blocks[len(hits):] = page_ids[: total - len(hits)]
        # CoW the cached blocks the suffix prefill will write: the copy
        # carries the positions below the first live chunk that the
        # recompute does not cover, and protects the tree's page (and
        # its other readers) from this slot's writes
        cow_pool = page_ids[total - len(hits):]
        for j, b in enumerate(range(n_keep, n_keep + n_cow)):
            src, dst = int(blocks[b]), int(cow_pool[j])
            self._cow(src, dst)
            self.pages.release([src])     # drop the map reference
            blocks[b] = dst
            shared[b] = False
        if swap_hits:
            # scatter the host pages into their freshly-allocated
            # device pages BEFORE the block table maps them: every
            # position below the first live chunk must hold canonical
            # KV by the time the suffix prefill (or first decode)
            # reads it.  Restored pages are private (shared=False):
            # they cost fresh pool pages — the tier saves compute,
            # not memory — so no CoW is ever needed on them.
            lo = len(hits)
            self._swap_restore(swap_hits, blocks[lo: lo + len(swap_hits)])
            self.swap_restored_tokens += len(swap_hits) * P

        row = np.zeros(self.spec.max_blocks, np.int32)
        row[:total] = blocks
        self.block_table[slot_i] = row
        n_chunks = -(-L // C)
        # perf_counter, not tel.now(): the NULL facade's clock returns
        # 0.0, and the swap policy needs real rates with telemetry off
        t0p = time.perf_counter() if self.swap_policy is not None else 0.0
        logits = self._run_prefill_chunks(tokens, row, ci0, rid)
        run_tokens = (n_chunks - ci0) * C
        self.prefill_tokens_run += run_tokens
        self.prefill_tokens_saved += ci0 * C
        if ent.out:
            # recompute-resume: the replayed suffix is the preemption's
            # real cost (the SLO bench's recompute-overhead number)
            self.resumes += 1
            self.resume_prefill_tokens += run_tokens
        with annotate("repro.serve.sync"):
            tok0 = int(np.asarray(jnp.argmax(logits)))
        tel.observe("phase.admit_s", tel.now() - t_start)
        if self.swap_policy is not None and n_chunks > ci0:
            # the argmax force above synchronised the device, so the
            # window covers dispatch + execution of every live chunk
            self.swap_policy.observe_prefill(
                run_tokens, time.perf_counter() - t0p)
        if not ent.out:
            self.ttft_s.observe(time.monotonic() - ent.t_submit)
        self.lens[slot_i] = L
        entry = {"req": ent.req, "out": ent.out + [tok0], "cur": tok0,
                 "blocks": blocks, "shared": shared,
                 "prio": ent.priority, "sched": ent}
        # L == S_max leaves no room to write a decode token: emit the
        # prefill argmax only, exactly like the dense oracle's capacity
        # guard (decoding anyway would clamp the KV write onto the
        # slot's last live page — silent corruption, not an error)
        if self._done_now(entry) or L >= self.S_max:
            self._finish(slot_i, entry)
            return "finished"
        self.slots[slot_i] = entry
        return "admitted"

    # -- lifecycle ----------------------------------------------------------

    def _done_now(self, entry) -> bool:
        return (
            (self.eos_id is not None and entry["out"][-1] == self.eos_id)
            or len(entry["out"]) >= entry["req"].max_new_tokens
        )

    def _finish(self, slot_i: int, entry) -> None:
        req = entry["req"]
        req.output = np.asarray(entry["out"], np.int32)
        req.finish_reason = (
            "stop" if (self.eos_id is not None
                       and entry["out"][-1] == self.eos_id) else "length")
        self.done.append(req)
        self._tenant_bump(tenant_of(req), "completed")
        self.tel.event("finished", req.rid,
                       tokens=len(entry["out"]),
                       pages=len(entry["blocks"]))
        blocks = entry["blocks"]
        lens = int(self.lens[slot_i])
        # every fully-written page of prompt + GENERATED tokens
        # transfers into the radix tree (insert dedupes against
        # existing nodes and releases duplicates/map references
        # itself), keyed by the full token history — multi-turn
        # traffic replays the model's own prior response as part of
        # the next prompt, and those pages are canonical KV exactly
        # like a preemption victim's (same accounting as _preempt:
        # positions [0, lens) are written, the final out token is not)
        full = np.concatenate([
            np.asarray(entry["req"].prompt, np.int32),
            np.asarray(entry["out"], np.int32),
        ])
        assert len(full) == lens + 1, \
            f"slot {slot_i} token accounting diverged at finish: " \
            f"{len(full)} vs lens {lens} + 1"
        n_full = lens // self.spec.page_size
        if self._prefix_enabled and self.prefix is not None and n_full:
            self.prefix.insert(full, blocks[:n_full])
            rest = blocks[n_full:]
        else:
            rest = blocks
        if len(rest):
            self.pages.release(list(rest))
        self.block_table[slot_i] = 0      # scratch page: no stale aliasing
        self.lens[slot_i] = 0
        self.slots[slot_i] = None

    def _preempt(self, slot_i: int) -> None:
        """Park a live slot on pool exhaustion.  The victim's written
        full pages go one of two ways:

        - **Swap** (tier on + policy says transfer beats replay): copy
          them device→host through the staging ring, then release
          EVERY device page — the whole point is pool space now and
          zero token replay at resume (the host store serves the pages
          back, content-addressed by prompt + generated tokens).
        - **Recompute** (tier off / policy says replay is cheaper):
          transfer them into the prefix cache (same content keys, so
          the resume's suffix prefill can map them back read-only —
          and further pressure can evict them), release the rest.

        Either way the request requeues with its generated-so-far
        tokens; recompute-resume remains the universal fallback (a
        swap put refused by the host budget just replays)."""
        entry = self.slots[slot_i]
        ent: SchedEntry = entry["sched"]
        lens = int(self.lens[slot_i])
        full = np.concatenate([
            np.asarray(entry["req"].prompt, np.int32),
            np.asarray(entry["out"], np.int32),
        ])
        assert len(full) == lens + 1, \
            f"slot {slot_i} token accounting diverged: {len(full)} vs " \
            f"lens {lens} + 1"
        blocks = entry["blocks"]
        # only pages fully covered by written positions [0, lens) hold
        # canonical KV (beyond sits the padded-prefill tail / rejected
        # speculative writes): those transfer; the partial tail frees
        n_full = lens // self.spec.page_size
        swapped = 0
        if (n_full and self.swap is not None
                and self.swap_policy.decide(
                    replay_tokens=lens,
                    nbytes=n_full * self.page_bytes())):
            swapped = self._swap_out(full, blocks[:n_full],
                                     tenant=tenant_of(entry["req"]))
        parked = 0
        if swapped:
            # the host copies hold the KV: every device page frees
            # outright (shared tree pages just drop this slot's map
            # reference — the tree keeps its own)
            self.pages.release(list(blocks))
        elif self._prefix_enabled and self.prefix is not None and n_full:
            self.prefix.insert(full, blocks[:n_full])
            parked = n_full
            rest = blocks[n_full:]
            if len(rest):
                self.pages.release(list(rest))
        elif len(blocks):
            self.pages.release(list(blocks))
        self.block_table[slot_i] = 0
        self.lens[slot_i] = 0
        self.slots[slot_i] = None
        ent.tokens = full
        ent.out = list(entry["out"])
        # ownership marker for cancel/expire-while-parked: purging
        # tries every full block (puts refused mid-run leave gaps;
        # purge skips missing keys)
        ent.swap_blocks = n_full if swapped else 0
        self.sched.requeue(ent)
        self.preemptions += 1
        self.preempted_tokens += lens
        self.tel.event("preempted", entry["req"].rid,
                       tokens_dropped=lens, pages_parked=parked,
                       pages_swapped=swapped)
        if swapped:
            self.tel.event("swapped_out", entry["req"].rid,
                           pages=swapped, bytes=swapped * self.page_bytes())

    # -- cancellation / deadlines --------------------------------------------

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Terminate request ``rid`` from *any* state, releasing every
        resource it holds:

        - **decoding / mid-prefill** (live slot): written full pages
          park into the prefix cache (they hold canonical KV — free
          warm-start for a retry), the rest release, the block-table
          row resets to scratch;
        - **queued / preempted**: the scheduler entry is removed
          (without polluting the queue-wait histogram);
        - **swapped-out**: additionally purges the entry's pages from
          the host ``SwapStore`` — a never-resumed victim must not
          strand host bytes until LRU pressure.

        The request lands in ``self.failed`` with its partial output, a
        typed ``error`` (``CancelledError`` / ``DeadlineExceededError``)
        and ``finish_reason``, and emits the terminal ``cancelled``
        lifecycle event.  Returns False when ``rid`` is not in flight
        (already finished, already cancelled, or never submitted) —
        cancel is idempotent, never an error."""
        for i in range(self.B):
            e = self.slots[i]
            if e is not None and e["req"].rid == rid:
                self._terminate_slot(i, e, reason)
                return True
        for ent in self.sched.queued():
            if ent.req.rid == rid:
                self.sched.remove(ent)
                self._purge_swapped(ent)
                self._mark_terminated(ent.req, reason, ent.out)
                return True
        return False

    def _terminate_slot(self, slot_i: int, entry, reason: str) -> None:
        """Release a live slot without requeue: same page accounting
        as a recompute preemption (written full pages transfer into
        the prefix tree — canonical KV, content-keyed — the partial
        tail frees), but the request terminates instead of parking."""
        lens = int(self.lens[slot_i])
        full = np.concatenate([
            np.asarray(entry["req"].prompt, np.int32),
            np.asarray(entry["out"], np.int32),
        ])
        assert len(full) == lens + 1, \
            f"slot {slot_i} token accounting diverged at cancel: " \
            f"{len(full)} vs lens {lens} + 1"
        blocks = entry["blocks"]
        n_full = lens // self.spec.page_size
        if self._prefix_enabled and self.prefix is not None and n_full:
            self.prefix.insert(full, blocks[:n_full])
            rest = blocks[n_full:]
        else:
            rest = blocks
        if len(rest):
            self.pages.release(list(rest))
        self.block_table[slot_i] = 0      # scratch: no stale aliasing
        self.lens[slot_i] = 0
        self.slots[slot_i] = None
        self._mark_terminated(entry["req"], reason, entry["out"])

    def _purge_swapped(self, ent: SchedEntry) -> None:
        """Release a parked entry's host-store pages (the swapped-out
        arm of cancel/expire).  No-op unless the entry owns swapped
        blocks."""
        if self.swap is not None and ent.swap_blocks:
            self.swap.purge(ent.tokens, ent.swap_blocks)
            ent.swap_blocks = 0

    def _mark_terminated(self, req: Request, reason: str, out) -> None:
        """Common terminal bookkeeping for cancels and deadline sheds:
        typed reason on the request, partial output preserved, the
        ``cancelled`` lifecycle event, global + per-tenant counters."""
        req.output = np.asarray(list(out), np.int32)
        req.finish_reason = reason
        if reason == "deadline":
            req.error = DeadlineExceededError(
                f"request {req.rid} exceeded its deadline budget")
            self.expired += 1
            self._tenant_bump(tenant_of(req), "expired")
        else:
            req.error = CancelledError(f"request {req.rid} cancelled")
            self.cancelled += 1
            self._tenant_bump(tenant_of(req), "cancelled")
        self.failed.append(req)
        self.tel.event("cancelled", req.rid, reason=reason,
                       tokens=len(req.output))

    def _enforce_deadlines(self) -> None:
        """Shed every request whose TTL ran out — queued entries (with
        their swapped-out host pages purged) and live slots alike.
        Called once per step, BEFORE admissions: a doomed entry never
        wastes a prefill.  Step-boundary enforcement is deliberate —
        mid-forward aborts would buy milliseconds and cost the
        bit-exactness discipline."""
        now = time.monotonic()
        for ent in list(self.sched.queued()):
            if (ent.deadline_s is not None
                    and now - ent.t_submit >= ent.deadline_s):
                self.sched.remove(ent)
                self._purge_swapped(ent)
                self._mark_terminated(ent.req, "deadline", ent.out)
        for i in range(self.B):
            e = self.slots[i]
            if e is None:
                continue
            dl = e["sched"].deadline_s
            if dl is not None and now - e["sched"].t_submit >= dl:
                self._terminate_slot(i, e, "deadline")

    def _tenant_bump(self, tenant: str, key: str) -> None:
        d = self.tenant_counters.setdefault(tenant, {})
        d[key] = d.get(key, 0) + 1

    # -- host-RAM swap tier ---------------------------------------------------

    def page_bytes(self) -> int:
        """Bytes one physical page occupies across every layer's pool
        (codes + scale sidecars) — the swap policy's transfer-cost
        unit and the host store's per-page footprint."""
        return self.kv_pool_bytes() // self.spec.n_pages

    def _swap_out(self, full, blocks, tenant=None) -> int:
        """Copy written full pages ``blocks`` of token history ``full``
        device→host through the staging ring and put each page in the
        content-addressed store.  Returns how many pages are
        host-resident afterwards; a budget-refused put just costs
        recompute at resume, never an error.  Ring transactions are
        fixed-width (short tails pad with the scratch page, whose
        gathered garbage is sliced off before storing), so the gather
        compiles exactly once."""
        ring = self.swap_ring
        R = ring.width
        t0 = time.perf_counter()
        stored = 0
        bytes0 = self.swap_out_bytes
        for base in range(0, len(blocks), R):
            tail = [int(b) for b in blocks[base: base + R]]
            pids = np.zeros(R, np.int32)     # scratch-page padding
            pids[: len(tail)] = tail
            with annotate("repro.serve.swap_gather"):
                dev = self._swap_gather(self.caches, jnp.asarray(pids))
            for meta, host in ring.stage((base, len(tail)), dev):
                stored += self._store_staged(full, meta, host, tenant)
        for meta, host in ring.drain():
            stored += self._store_staged(full, meta, host, tenant)
        moved = self.swap_out_bytes - bytes0
        if moved:
            self.swap_policy.observe_copy(moved,
                                          time.perf_counter() - t0)
        self.swapped_out_pages += stored
        if self.tel.enabled and stored:
            self.tel.inc("swap.out_pages", stored)
            self.tel.inc("swap.out_bytes", moved)
        return stored

    def _store_staged(self, full, meta, host, tenant=None) -> int:
        """Split one matured ring transaction into per-page host copies
        and store each under its content key.  ``host`` leaves are
        ``[n_layers, R, page_size, ...]``; the per-page ``.copy()``
        decouples the page from the transaction buffer so a later
        store eviction really frees host memory."""
        base, n = meta
        stored = 0
        for j in range(n):
            page = jax.tree.map(lambda a: a[:, j].copy(), host)
            if self.swap.put(full, base + j, page, tenant=tenant):
                stored += 1
                self.swap_out_bytes += int(
                    sum(a.nbytes for a in jax.tree.leaves(page)))
        return stored

    def _swap_restore(self, host_pages, dest) -> None:
        """Scatter host pages back into freshly-allocated device pages
        ``dest``, ring-width transactions (a short tail repeats its
        last page onto scratch page 0, whose writes are dead by the
        pool contract — same one-trace discipline as the gather).
        Lossless by construction: the staged leaves are the raw bytes
        the gather took (int8/int4 codes, bf16 scales), scattered back
        with a dtype-preserving set."""
        R = self.swap_ring.width
        t0 = time.perf_counter()
        nbytes = 0
        for base in range(0, len(host_pages), R):
            tail = host_pages[base: base + R]
            pids = np.zeros(R, np.int32)
            pids[: len(tail)] = dest[base: base + len(tail)]
            padded = list(tail) + [tail[-1]] * (R - len(tail))
            staged = jax.tree.map(lambda *xs: np.stack(xs, axis=1),
                                  *[p.data for p in padded])
            with annotate("repro.serve.swap_scatter"):
                self.caches = self._swap_scatter(
                    self.caches, jax.tree.map(jnp.asarray, staged),
                    jnp.asarray(pids))
            nbytes += sum(p.nbytes for p in tail)
        # force the scatters so the observed copy rate is real (the
        # data dependency alone would already order them before the
        # first forward that reads the restored pages)
        with annotate("repro.serve.sync"):
            jax.block_until_ready(self.caches)
        self.swap_policy.observe_copy(nbytes, time.perf_counter() - t0)
        self.swapped_in_pages += len(host_pages)
        self.swap_in_bytes += nbytes
        if self.tel.enabled:
            self.tel.inc("swap.in_pages", len(host_pages))
            self.tel.inc("swap.in_bytes", nbytes)

    def _fill_free_slots(self, mid_decode: bool) -> None:
        """Admit queued requests into every free slot.  A request that
        finishes on its first generated token frees the slot again, so
        the inner loop keeps admitting (no deadlock, no lost work)."""
        for i in range(self.B):
            while self.slots[i] is None:
                status = self._admit(i)
                if status == "blocked":
                    break
                if mid_decode:
                    self.refills += 1     # 'admitted' or 'finished'
                if status == "admitted":
                    break

    def run(self):
        """Process the queue; greedy decoding.  Returns finished
        requests (same contract as the dense loop).  With telemetry on
        and ``cfg.serve_trace_path`` set, the drain auto-exports the
        Chrome trace (plus a JSONL twin) when it completes."""
        while self.step():
            pass
        if self.trace_path and self.tel.enabled:
            self.export_trace()
        return self.done

    def step(self) -> bool:
        """One scheduling round: admissions into free slots, then at
        most one decode/verify forward over the live slots (preempting
        victims first if on-demand growth exhausts the pool), then
        refill.  Returns True while work remains — a caller feeding
        arrivals submits between steps; ``run`` just drains.

        The round runs inside the ``repro.serve.step`` profiler span,
        which carries ``time.monotonic()`` at its start: the one
        reading per step that maps the program's monotonic stamps onto
        a capture's clock (serve/telemetry.py)."""
        with annotate("repro.serve.step", monotonic_s=time.monotonic()):
            return self._step()

    def _step(self) -> bool:
        self.sched.tick()
        self._injected_block = False
        if self.faults.fire("cancel"):
            # injected client disconnect: seeded pick over everything
            # in flight (live slots and queued/parked entries alike)
            rids = [s["req"].rid for s in self.slots if s is not None]
            rids += [e.req.rid for e in self.sched.queued()]
            if rids:
                self.cancel(self.faults.choice(rids))
        self._enforce_deadlines()
        mid = any(s is not None for s in self.slots)
        self._fill_free_slots(mid_decode=mid)
        live = [i for i in range(self.B) if self.slots[i] is not None]
        self.peak_live_slots = max(self.peak_live_slots, len(live))
        if not live:
            if len(self.sched):
                if self._injected_block:
                    # the blockage was an injected fault, not a real
                    # short pool: the head retries next round
                    return True
                # every slot is free and eviction has been tried, yet
                # the best entry still can't get pages: the pool is
                # simply too small for this request's plan (reserved
                # mode; submit already rejects never-fitting prompts)
                ent = self.sched.peek()
                raise PoolExhaustedError(
                    f"request {ent.req.rid} needs "
                    f"{self._plan(ent, self._match_blocks(ent))[4]} "
                    f"fresh pages; pool has {self.spec.n_pages - 1}"
                )
            if self.check_invariants:
                self._check()
            return False
        drafts = self._propose(live)
        t0r = self.tel.now()
        live, drafts = self._reserve_step(live, drafts)
        self.tel.observe("phase.reserve_s", self.tel.now() - t0r)
        freed = True        # every slot preempted => admit next round
        if live:
            if any(len(drafts[i]) for i in live):
                freed = self._verify_once(live, drafts)
            else:
                # no slot drafted anything (speculation off, n-gram
                # miss, or every slot clamped to 0): the cheap [B, 1]
                # decode shape — a verify window would pad every row
                freed = self._decode_once(live)
        if freed:
            # continuous batching: freed slots admit immediately —
            # other slots keep decoding, nobody waits for a drain
            self._fill_free_slots(mid_decode=True)
            self.peak_live_slots = max(
                self.peak_live_slots,
                sum(s is not None for s in self.slots))
        if self.check_invariants:
            self._check()
        if self.tel.enabled:
            self.tel.set_gauge("live_slots",
                               sum(s is not None for s in self.slots))
            self.tel.set_gauge("queued", len(self.sched))
            self.tel.set_gauge("pool_pages_in_use", self.pages.in_use)
        return bool(len(self.sched)
                    or any(s is not None for s in self.slots))

    # -- on-demand growth / preemption ---------------------------------------

    def _grow_to(self, slot_i: int, entry, last_blk: int) -> bool:
        """Ensure the slot's block table covers block ``last_blk``
        (on-demand page-boundary growth).  Returns False when the pool
        (plus evictable prefixes) cannot supply the next page — the
        caller preempts a victim or truncates the draft."""
        while len(entry["blocks"]) <= last_blk:
            # the injected-exhaustion site fires only when a REAL alloc
            # is due (inside the loop): a fault here implies the draft/
            # write genuinely needed a page, preserving the caller's
            # failed-grow => truncation-shrinks invariant
            if self.faults.fire("alloc"):
                return False
            pages = self._alloc_with_evict(1)
            if pages is None:
                return False
            b = len(entry["blocks"])
            entry["blocks"] = np.append(entry["blocks"],
                                        np.int32(pages[0]))
            entry["shared"] = np.append(entry["shared"], False)
            self.block_table[slot_i, b] = pages[0]
            self.grown_pages += 1
            self.tel.event("grow_page", entry["req"].rid, page=pages[0],
                           block=b)
        return True

    def _reserve_step(self, live: List[int], drafts: dict):
        """Secure this step's page writes for every live slot,
        highest-priority first.  The mandatory one-token write is
        worth preempting for: on exhaustion the policy picks a victim
        (possibly the needer itself, when it is the least important
        live work) and parks it.  Speculative drafts are best-effort —
        a draft that cannot get pages is truncated, never preempted
        for.  Returns the surviving live set and (possibly truncated)
        drafts."""
        P = self.spec.page_size
        order = sorted(live, key=lambda i: (-self.slots[i]["prio"], i))
        dropped = set()
        for i in order:
            if i in dropped:
                continue
            entry = self.slots[i]
            lens = int(self.lens[i])
            while not self._grow_to(i, entry, lens // P):
                cands = [(j, self.slots[j]["prio"],
                          len(self.slots[j]["blocks"]),
                          len(self.slots[j]["out"]))
                         for j in live if j not in dropped]
                vict = self.sched.select_victim(cands)
                if vict is None:
                    raise PoolExhaustedError(
                        f"pool exhausted growing slot {i} and "
                        f"serve_preempt_policy="
                        f"{self.sched.policy!r} allows no victim"
                    )
                self._preempt(vict)
                dropped.add(vict)
                if vict == i:
                    break
            if i in dropped:
                continue
            d = drafts.get(i)
            if d is not None and len(d):
                while len(d) and not self._grow_to(
                        i, entry, (lens + len(d)) // P):
                    # shrink to what the allocated pages can hold; the
                    # failed grow implies len(d) strictly exceeds fit,
                    # so this terminates
                    fit = len(entry["blocks"]) * P - 1 - lens
                    d = d[: max(0, fit)]
                drafts[i] = d
        return [i for i in live if i not in dropped], drafts

    def _ensure_writable(self, slot_i: int, entry, blk: int) -> None:
        """Copy-on-write guard before a decode write to block ``blk``.
        Prompt/resume prefix sharing alone never routes a decode write
        onto a shared page (shared blocks end strictly below the first
        recomputed chunk, decode writes land at positions >= L-1), but
        the guard keeps the invariant — no write ever lands on a page
        with other readers — local and future-proof."""
        if blk >= len(entry["shared"]) or not entry["shared"][blk]:
            return
        pages = self._alloc_with_evict(1)
        if pages is None:
            raise PoolExhaustedError(
                "pool exhausted during copy-on-write; admission should "
                "have reserved this page"
            )
        src, dst = int(entry["blocks"][blk]), pages[0]
        self._cow(src, dst)
        self.pages.release([src])
        entry["blocks"][blk] = dst
        entry["shared"][blk] = False
        self.block_table[slot_i, blk] = dst

    def _check(self) -> None:
        """The ``cfg.serve_check_invariants`` debug hook: structural
        checks after every drain step (page-pool partition, tree
        consistency, queue sanity) — on in CI and the bench smoke."""
        self.pages.check()
        if self.prefix is not None:
            self.prefix.check()
        if self.swap is not None:
            self.swap.check()
        self.sched.check()

    # -- speculative decoding ------------------------------------------------

    def _draft_cap(self, i: int, entry) -> int:
        """Longest draft slot ``i`` may verify this step.  Bounded by
        ``max_new`` (a full accept must not overshoot the request's
        budget: ``k`` drafts + 1 bonus <= remaining) and by ``S_max``.
        Reserved mode additionally clamps to the slot's allocated
        pages — so every *valid* verify write stays within admission's
        reservation; on-demand mode instead grows (or truncates) in
        ``_reserve_step``."""
        lens = int(self.lens[i])
        remaining = entry["req"].max_new_tokens - len(entry["out"])
        alloc_room = (self.S_max if self.on_demand
                      else len(entry["blocks"]) * self.spec.page_size)
        room = min(self.S_max, alloc_room) - 1 - lens
        return max(0, min(self.spec_k, remaining - 1, room))

    def _propose(self, live: List[int]) -> dict:
        """Per-slot draft proposals (empty arrays when not drafting)."""
        empty = np.zeros(0, np.int32)
        if self.drafter is None:
            return {i: empty for i in live}
        drafts = {}
        for i in live:
            entry = self.slots[i]
            cap = self._draft_cap(i, entry)
            if cap <= 0:
                drafts[i] = empty
                continue
            ctx = np.concatenate([
                np.asarray(entry["req"].prompt, np.int32),
                np.asarray(entry["out"], np.int32),
            ])
            d = np.asarray(self.drafter.propose(ctx, cap), np.int32)
            drafts[i] = d[:cap]
        return drafts

    def _accept(self, i: int, entry, tokens):
        """Append ``tokens`` to slot ``i`` one by one with the exact
        finish checks of a sequential decode (eos truncates the rest —
        the oracle never emits past it).  Returns ``(appended,
        finished)``: how many tokens were actually emitted and whether
        the slot finished."""
        for n, t in enumerate(tokens):
            self.lens[i] += 1
            tok = int(t)
            entry["out"].append(tok)
            entry["cur"] = tok
            self.gen_tokens += 1
            if self._done_now(entry) or self.lens[i] >= self.S_max:
                self._finish(i, entry)
                return n + 1, True
        return len(tokens), False

    def _decode_once(self, live: List[int]) -> bool:
        """One plain ``[B, 1]`` decode step.  Returns True if any slot
        finished (the caller then refills)."""
        P = self.spec.page_size
        cur = np.zeros((self.B, 1), np.int32)
        for i in live:
            self._ensure_writable(i, self.slots[i],
                                  int(self.lens[i]) // P)
            cur[i, 0] = self.slots[i]["cur"]
        tel = self.tel
        t0 = tel.now()
        with annotate("repro.serve.decode_step"):
            logits, self.caches = self._decode(
                self.params, self.caches, jnp.asarray(cur),
                jnp.asarray(self.lens), jnp.asarray(self.block_table),
            )
        self.decode_steps += 1
        self.slot_steps += len(live)
        with annotate("repro.serve.sync"):
            nxt = np.asarray(jnp.argmax(logits, -1))
        # the argmax force above synchronised the device, so t1 covers
        # dispatch + execution; events go out BEFORE _accept so a
        # finishing slot's 'finished' mark follows its decode span
        t1 = tel.now()
        tel.observe("phase.decode_s", t1 - t0)
        freed = False
        for i in live:
            tel.event("decode", self.slots[i]["req"].rid, t0=t0, t1=t1,
                      pos=int(self.lens[i]))
            _, fin = self._accept(i, self.slots[i], [int(nxt[i])])
            freed |= fin
        return freed

    def _verify_once(self, live: List[int], drafts: dict) -> bool:
        """One ``[B, k+1]`` verify step: score every slot's current
        token + draft in a single forward, then keep the longest draft
        prefix matching the model's own argmax chain plus one bonus
        token.

        Rollback of rejected rows costs nothing: ``lens`` only
        advances over accepted tokens, so the rejected rows' page
        writes sit beyond every future attention mask until later
        (valid) writes overwrite them — and rows past ``n_writes``
        were already routed to the scratch page inside the kernel.
        Shared (prefix-cached) pages are protected the same way plain
        decode protects them: ``_ensure_writable`` CoWs every block
        the window's valid writes touch before the forward runs."""
        K1 = self.spec_k + 1
        P = self.spec.page_size
        toks = np.zeros((self.B, K1), np.int32)
        n_writes = np.zeros(self.B, np.int32)
        for i in live:
            entry = self.slots[i]
            d = drafts[i]
            toks[i, 0] = entry["cur"]
            toks[i, 1: 1 + len(d)] = d
            n_writes[i] = 1 + len(d)
            lens = int(self.lens[i])
            for blk in range(lens // P, (lens + len(d)) // P + 1):
                self._ensure_writable(i, entry, blk)
        tel = self.tel
        t0 = tel.now()
        with annotate("repro.serve.verify_step"):
            logits, self.caches = self._verify(
                self.params, self.caches, jnp.asarray(toks),
                jnp.asarray(self.lens), jnp.asarray(n_writes),
                jnp.asarray(self.block_table),
            )
        self.spec_steps += 1
        self.slot_steps += len(live)
        with annotate("repro.serve.sync"):
            greedy = np.asarray(jnp.argmax(logits, -1))      # [B, K1]
        t1 = tel.now()
        tel.observe("phase.verify_s", t1 - t0)
        freed = False
        for i in live:
            entry = self.slots[i]
            d, g = drafts[i], greedy[i]
            m = 0
            while m < len(d) and g[m] == d[m]:
                m += 1
            tel.event("verify", entry["req"].rid, t0=t0, t1=t1,
                      proposed=len(d), matched=m, pos=int(self.lens[i]))
            self.spec_proposed += len(d)
            # g[:m] == the accepted draft; g[m] is the bonus token the
            # model emits after it (for m == 0 that is row 0's argmax:
            # exactly the plain decode step's token).  Accepted-draft
            # stats count only tokens actually EMITTED (eos truncation
            # mid-window discards the rest of the match)
            appended, fin = self._accept(i, entry, g[: m + 1])
            self.spec_accepted += min(appended, m)
            freed |= fin
        return freed

    def prompt_logits(self, prompt, continuation=()) -> np.ndarray:
        """f32 ``[vocab]`` logits at the last position of ``prompt`` +
        ``continuation``: ``prompt`` through ``_admit``'s chunk prefill,
        then each ``continuation`` token through one decode step — the
        serve loop's own compiled forwards — over fresh private pages
        that are freed again; nothing is admitted.  Slot rows other than
        the scored one are idle (scratch page).  With an empty
        ``continuation`` these are the logits ``_admit`` argmaxes —
        what a reference check compares."""
        tokens = np.asarray(prompt, np.int32)
        cont = [int(t) for t in continuation]
        L = len(tokens)
        if not (0 < L and L + len(cont) <= self.S_max):
            raise ValueError(f"{L} + {len(cont)} tokens outside "
                             f"(0, {self.S_max}]")
        blocks = self._alloc_with_evict(self._worst_blocks(L, len(cont) + 1))
        if blocks is None:
            raise PoolExhaustedError("no free pages to score a prompt")
        try:
            row = np.zeros(self.spec.max_blocks, np.int32)
            row[:len(blocks)] = blocks
            logits = self._run_prefill_chunks(tokens, row, 0, None)
            bt = np.zeros_like(self.block_table)
            bt[0] = row
            for n, t in enumerate(cont):
                cur = np.zeros((self.B, 1), np.int32)
                pos = np.zeros(self.B, np.int32)
                cur[0, 0], pos[0] = t, L + n
                logits, self.caches = self._decode(
                    self.params, self.caches, jnp.asarray(cur),
                    jnp.asarray(pos), jnp.asarray(bt))
                logits = logits[0]
            with annotate("repro.serve.sync"):
                return np.asarray(logits, np.float32)
        finally:
            self.pages.release(blocks)

    # -- introspection -------------------------------------------------------

    def kv_pool_bytes(self) -> int:
        """Device bytes of the whole paged KV pool (codes + scale
        sidecars, every layer) — the memory-capacity headline a
        quantised ``kv_dtype`` shrinks ~2x (int8) / ~4x (int4)."""
        return int(sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self.caches)
        ))

    def spec_stats(self) -> dict:
        """Decode-phase throughput accounting (the bench's numbers).

        ``tokens_per_step`` is per SLOT-step — tokens emitted divided
        by live-slot participations in decode/verify forwards — so
        plain greedy decode measures exactly 1.0 at any batch size and
        the number is the speculation amortisation factor alone."""
        return {
            "decode_steps": self.decode_steps,
            "spec_steps": self.spec_steps,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "accept_rate":
                self.spec_accepted / max(self.spec_proposed, 1),
            "tokens_per_step": self.gen_tokens / max(self.slot_steps, 1),
        }

    def sched_stats(self) -> dict:
        """Scheduling/preemption accounting (the SLO bench's numbers):
        preemption + recompute-resume counters, concurrency and pool
        high-water marks, and bounded TTFT / queue-wait summaries
        (count/mean/p50/p90/p99 + a capped recent-sample tail — never
        an unbounded per-request list)."""
        return {
            **self.sched.stats(),
            "on_demand": self.on_demand,
            "cancelled": self.cancelled,
            "expired": self.expired,
            "failed": len(self.failed),
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "resume_prefill_tokens": self.resume_prefill_tokens,
            "preempted_tokens": self.preempted_tokens,
            "swapped_out_pages": self.swapped_out_pages,
            "swapped_in_pages": self.swapped_in_pages,
            "swap_restored_tokens": self.swap_restored_tokens,
            "grown_pages": self.grown_pages,
            "peak_live_slots": self.peak_live_slots,
            "pool_pages_peak": self.pages.peak,
            "pool_exhaustions": self.pages.exhaustions,
            "ttft_s": self.ttft_s.summary(),
        }

    def pool_stats(self) -> dict:
        """Page-pool accounting (the ``metrics()`` pool subsystem)."""
        return {
            "n_pages": self.pages.n_pages,
            "usable": self.pages.n_pages - 1,
            "in_use": self.pages.in_use,
            "available": self.pages.available,
            "allocs": self.pages.allocs,
            "frees": self.pages.frees,
            "peak": self.pages.peak,
            "exhaustions": self.pages.exhaustions,
            "cow_copies": self.cow_copies,
            "grown_pages": self.grown_pages,
            "pool_bytes": self.kv_pool_bytes(),
        }

    def swap_stats(self) -> dict:
        """Swap-tier accounting (the ``metrics()`` swap subsystem):
        host-store occupancy, per-victim policy decisions + measured
        rates, and transfer traffic.  ``restored_tokens`` is the
        headline — positions resumed WITHOUT token replay (the bench's
        recompute-tokens-saved metric reads it against the
        recompute-only baseline's ``resume_prefill_tokens``)."""
        if self.swap is None:
            return {"enabled": False}
        return {
            "enabled": True,
            "store": self.swap.stats(),
            "policy": self.swap_policy.stats(),
            "ring_width": self.swap_ring.width,
            "ring_transactions": self.swap_ring.transactions,
            "swapped_out_pages": self.swapped_out_pages,
            "swapped_in_pages": self.swapped_in_pages,
            "swap_out_bytes": self.swap_out_bytes,
            "swap_in_bytes": self.swap_in_bytes,
            "restored_tokens": self.swap_restored_tokens,
            "page_bytes": self.page_bytes(),
        }

    def tenant_stats(self) -> dict:
        """Per-tenant fairness accounting (the ``metrics()`` tenants
        subsystem): live pool/queue footprint plus terminal counters
        per tenant, and the configured quotas.  Single-tenant
        deployments see one 'default' row and zeroed quotas."""
        held = self._tenant_pages()
        queued: dict = {}
        for e in self.sched.queued():
            t = tenant_of(e.req)
            queued[t] = queued.get(t, 0) + 1
        swap_b = self.swap.tenant_bytes if self.swap is not None else {}
        names = sorted(set(held) | set(queued)
                       | set(self.tenant_counters) | set(swap_b))
        per = {}
        for t in names:
            c = self.tenant_counters.get(t, {})
            per[t] = {
                "pages_held": held.get(t, 0),
                "queued": queued.get(t, 0),
                "completed": c.get("completed", 0),
                "cancelled": c.get("cancelled", 0),
                "expired": c.get("expired", 0),
                "swap_bytes": swap_b.get(t, 0),
            }
        return {
            "page_quota": self.tenant_page_quota,
            "queue_limit": self.tenant_queue_limit,
            "swap_budget": (self.swap.tenant_budget
                            if self.swap is not None else 0),
            "tenants": per,
        }

    def metrics(self) -> dict:
        """One snapshot covering every serving subsystem — the unified
        observability surface the per-subsystem dicts (``spec_stats``,
        ``sched_stats``, ``prefix.stats`` ...) feed into.  Always
        available; the ``telemetry`` section (registry counters/gauges/
        phase histograms + tracer depth) appears only when telemetry
        is enabled.  JSON-serialisable by construction."""
        from repro.serve.telemetry import jsonable
        doc = {
            "pool": self.pool_stats(),
            "prefix_cache": (self.prefix.stats() if self.prefix is not None
                             else {"enabled": False}),
            "spec": {**self.spec_stats(),
                     "k": self.spec_k,
                     "gen_tokens": self.gen_tokens,
                     "refills": self.refills,
                     "prefill_tokens_run": self.prefill_tokens_run,
                     "prefill_tokens_saved": self.prefill_tokens_saved},
            "quant": {"kv_dtype": str(self.kv_spec.dtype),
                      "quantised": bool(self.kv_spec.quantised),
                      "pool_bytes": self.kv_pool_bytes()},
            "scheduler": self.sched_stats(),
            "swap": self.swap_stats(),
            "tenants": self.tenant_stats(),
            "faults": self.faults.stats(),
            "autotune": autotune.snapshot_stats(),
        }
        if self.tel.enabled:
            doc["telemetry"] = {
                **self.tel.registry.snapshot(),
                "trace_events": len(self.tel.tracer.events),
                "trace_dropped": self.tel.tracer.dropped,
            }
        return jsonable(doc)

    def export_trace(self, chrome_path: Optional[str] = None,
                     jsonl_path: Optional[str] = None) -> dict:
        """Write the lifecycle trace: Chrome trace-event JSON at
        ``chrome_path`` (default ``cfg.serve_trace_path``) and a JSONL
        twin (default: same path + 'l').  No-op returning ``{}`` when
        telemetry is off or no path is available."""
        path = chrome_path or self.trace_path
        if not path or not self.tel.enabled:
            return {}
        return self.tel.export(chrome_path=path,
                               jsonl_path=jsonl_path or path + "l")

    def compiled_shapes(self) -> dict:
        """Per-jit trace counts (the compile-set invariant)."""
        out = {
            "chunk": self._prefill_chunk._cache_size(),
            "decode": self._decode._cache_size(),
        }
        if self._verify is not None:
            out["verify"] = self._verify._cache_size()
        return out

    def check_compiled(self) -> None:
        """Assert the compile-set invariant: at most one trace per
        forward entry point (chunk, decode, verify) and at most one
        for the CoW page memcpy — ANY extra shape anywhere fails."""
        for name, n in self.compiled_shapes().items():
            assert n <= 1, f"{name} forward retraced: {n} shapes"
        assert self._copy_page._cache_size() <= 1, "CoW copy retraced"
        # the swap gather/scatter are fixed ring-width moves: one trace
        # each, ever.  They live here rather than in compiled_shapes()
        # — that dict is the FORWARD compile set the bench gates at
        # exactly three shapes.
        if self._swap_gather is not None:
            assert self._swap_gather._cache_size() <= 1, \
                "swap gather retraced"
            assert self._swap_scatter._cache_size() <= 1, \
                "swap scatter retraced"
