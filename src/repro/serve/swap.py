"""Host-RAM page swap tier: preemption without recompute.

PR 6's preemption path drops a victim's device pages and replays its
tokens at resume (recompute-resume).  That is the right trade for short
contexts — prefill is fast and pool pages are the scarce resource — but
for long contexts replaying thousands of tokens costs far more than
copying the victim's KV pages over PCIe/ICI once.  This module is the
storage half of the swap tier:

``SwapStore``
    A content-addressed host-RAM page store keyed exactly like the
    radix prefix tree: page *i* of a sequence is keyed by the full
    token history ``tuple(tokens[:(i+1)*P])``.  The same key discipline
    means a swapped-out prefix stays addressable to *any* request that
    shares it, not just the original victim — swap hits compose with
    radix-tree hits (device hits are consumed first, the store serves
    the consecutive blocks after them).  Pages are stored as raw host
    copies of the pool leaves (codes + scales for quantised pools), so
    the round-trip is lossless **by construction**: int8/int4 codes and
    bf16 scales are byte-preserved, never re-quantised.

``StagingRing``
    A bounded ring of in-flight device→host staging transactions.
    Swap-out dispatches one device gather per fixed-width transaction
    and defers forcing the host copy until the ring is full (or
    drained), so device compute and D2H copies overlap up to ``depth``
    transactions.  JAX's functional arrays make the deferral safe: the
    gather closed over immutable pool values, and later pool writes
    produce *new* arrays — the staged value cannot be clobbered.

The loop-side integration (swap-aware ``_preempt``/``_admit``) lives in
``serve/paged.py``; the per-victim recompute-vs-swap policy lives in
``serve/scheduler.py`` (:class:`repro.serve.scheduler.SwapPolicy`).

Correctness note: the store is a *cache*, never the only copy of
anything irreplaceable — a preempted request always retains its token
history, so an evicted (or budget-refused) host page merely costs
recompute at resume, exactly like a radix-tree eviction.  That is what
lets ``max_bytes`` LRU-evict freely and lets swap-out release device
pages unconditionally.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.serve.faults import NULL_FAULTS
from repro.serve.telemetry import annotate

__all__ = ["HostPage", "SwapStore", "StagingRing", "page_checksum"]


def page_checksum(data) -> int:
    """CRC-32 over a host page's raw leaf bytes (codes + scales) — the
    integrity seal computed at swap-out and re-verified at swap-in.
    Byte-level, so it covers exactly what the lossless round-trip
    promises to preserve."""
    c = 0
    for a in jax.tree.leaves(data):
        c = zlib.crc32(np.ascontiguousarray(a).tobytes(), c)
    return c


class HostPage:
    """One swapped-out KV page: host copies of every pool leaf.

    ``data`` mirrors the stacked-cache structure for a single page —
    a pytree whose leaves are ``np.ndarray``s of shape
    ``[n_layers, page_size, ...]`` (codes, and scales for quantised
    pools).  ``nbytes`` is the exact host footprint used by the
    store's budget ledger; ``checksum`` seals the bytes at store time
    (``verify`` recomputes it, catching torn writes / bit rot before a
    corrupt page can ever be scattered back to device); ``tenant``
    attributes the bytes to a per-tenant budget ledger.
    """

    __slots__ = ("key", "data", "nbytes", "tick", "checksum", "tenant")

    def __init__(self, key: Tuple[int, ...], data, tick: int,
                 tenant: Optional[str] = None):
        self.key = key
        self.data = data
        self.nbytes = int(sum(a.nbytes for a in jax.tree.leaves(data)))
        self.tick = tick
        self.checksum = page_checksum(data)
        self.tenant = tenant

    def verify(self) -> bool:
        """True iff the page bytes still match the store-time seal."""
        return page_checksum(self.data) == self.checksum

    def __repr__(self):  # pragma: no cover - debug aid
        return f"HostPage(len={len(self.key)}, nbytes={self.nbytes})"


class SwapStore:
    """Content-addressed host-RAM store of swapped KV pages.

    Keys are radix-tree-compatible: ``tuple(tokens[:(i+1)*P])`` for
    block index *i* — the full token history up to and including the
    page, so identical prefixes from different requests dedupe to one
    host page and a restored prefix serves any future request that
    shares it.

    ``max_bytes == 0`` means unbounded; otherwise puts LRU-evict until
    the new page fits (a page larger than the whole budget is refused).
    ``tenant_budget`` additionally caps each tenant's resident bytes:
    a put that would exceed it first evicts that tenant's *own* LRU
    pages — one tenant's swap churn can never evict another tenant's
    pages through the shared budget.  ``faults`` threads the seeded
    chaos injector (serve/faults.py): the ``swap_put`` site models a
    budget refusal, ``swap_corrupt`` flips a byte of a just-stored page
    after its checksum seal (caught and dropped at match time).
    """

    def __init__(self, page_size: int, max_bytes: int = 0,
                 tenant_budget: int = 0, faults=None):
        self.page_size = int(page_size)
        self.max_bytes = int(max_bytes)
        self.tenant_budget = int(tenant_budget)
        self.faults = NULL_FAULTS if faults is None else faults
        self.entries: Dict[Tuple[int, ...], HostPage] = {}
        self.bytes = 0
        self.tenant_bytes: Dict[str, int] = {}
        self._tick = 0
        # counters (exported via stats())
        self.puts = 0
        self.dup_puts = 0
        self.refused_puts = 0
        self.hit_blocks = 0
        self.miss_lookups = 0
        self.evicted_pages = 0
        self.evicted_bytes = 0
        self.corrupt_dropped = 0      # checksum-failed pages dropped
        self.corrupt_dropped_bytes = 0
        self.purged_pages = 0         # cancel/deadline purges
        self.purged_bytes = 0

    def __len__(self) -> int:
        return len(self.entries)

    def _key(self, tokens, i: int) -> Tuple[int, ...]:
        return tuple(int(t) for t in tokens[: (i + 1) * self.page_size])

    # -- writes ---------------------------------------------------------

    def put(self, tokens, i: int, data, tenant: Optional[str] = None) -> bool:
        """Store host page ``data`` for block *i* of ``tokens``.

        Returns True if the page is resident after the call (including
        the dedupe case), False if the budget refused it.  Never raises
        on budget pressure — a refused put only costs recompute later.
        ``tenant`` charges the page to that tenant's byte ledger; a
        shared (deduped) page stays charged to its first putter.
        """
        if self.faults.fire("swap_put"):
            self.refused_puts += 1      # injected budget refusal
            return False
        key = self._key(tokens, i)
        self._tick += 1
        hit = self.entries.get(key)
        if hit is not None:
            hit.tick = self._tick        # refresh LRU; bytes unchanged
            self.dup_puts += 1
            return True
        page = HostPage(key, data, self._tick, tenant=tenant)
        if self.tenant_budget and tenant is not None:
            if page.nbytes > self.tenant_budget:
                self.refused_puts += 1
                return False
            self._evict_tenant_to(tenant,
                                  self.tenant_budget - page.nbytes)
        if self.max_bytes:
            if page.nbytes > self.max_bytes:
                self.refused_puts += 1
                return False
            self._evict_to(self.max_bytes - page.nbytes)
        self.entries[key] = page
        self.bytes += page.nbytes
        if tenant is not None:
            self.tenant_bytes[tenant] = \
                self.tenant_bytes.get(tenant, 0) + page.nbytes
        self.puts += 1
        if self.faults.fire("swap_corrupt"):
            # torn-write model: damage AFTER the checksum seal, so the
            # swap-in verify must catch it (and the chaos tests assert
            # corrupt pages are dropped, never scattered)
            self.faults.corrupt(page.data)
        return True

    def _drop(self, key: Tuple[int, ...]) -> HostPage:
        """Remove one entry, keeping the global and tenant byte
        ledgers exact (every removal path funnels through here)."""
        page = self.entries.pop(key)
        self.bytes -= page.nbytes
        if page.tenant is not None:
            left = self.tenant_bytes[page.tenant] - page.nbytes
            if left:
                self.tenant_bytes[page.tenant] = left
            else:
                del self.tenant_bytes[page.tenant]
        return page

    def _evict_to(self, budget: int) -> int:
        """LRU-evict whole pages until ``bytes <= budget``."""
        n = 0
        while self.bytes > budget and self.entries:
            key = min(self.entries, key=lambda k: self.entries[k].tick)
            page = self._drop(key)
            self.evicted_pages += 1
            self.evicted_bytes += page.nbytes
            n += 1
        return n

    def _evict_tenant_to(self, tenant: str, budget: int) -> int:
        """LRU-evict ``tenant``'s own pages until its ledger fits —
        per-tenant pressure never touches other tenants' pages."""
        n = 0
        while self.tenant_bytes.get(tenant, 0) > budget:
            keys = [k for k, p in self.entries.items()
                    if p.tenant == tenant]
            key = min(keys, key=lambda k: self.entries[k].tick)
            page = self._drop(key)
            self.evicted_pages += 1
            self.evicted_bytes += page.nbytes
            n += 1
        return n

    def purge(self, tokens, n_blocks: int) -> Tuple[int, int]:
        """Drop blocks ``[0, n_blocks)`` of this token history (a
        cancelled/expired swapped-out request releasing its host
        pages).  Missing blocks (LRU-evicted meanwhile, or refused at
        put) are skipped.  Deduped pages shared with another parked
        victim are dropped too — the store is a cache, so the sharer
        just recomputes (same contract as an LRU eviction).  Returns
        ``(pages, bytes)`` removed."""
        pages = nbytes = 0
        for i in range(n_blocks):
            key = self._key(tokens, i)
            if key not in self.entries:
                continue
            page = self._drop(key)
            pages += 1
            nbytes += page.nbytes
        self.purged_pages += pages
        self.purged_bytes += nbytes
        return pages, nbytes

    # -- reads ----------------------------------------------------------

    def match(self, tokens, start_block: int = 0) -> List[HostPage]:
        """Longest run of consecutively-stored blocks from ``start_block``.

        Mirrors ``PrefixCache.match``: only *consecutive* blocks are
        usable (a gap would leave an unwritten hole in the middle of
        the mapped range).  ``start_block`` lets the caller consume
        device radix-tree hits first and fill in from the store after.
        Matching refreshes LRU ticks — a hot swapped prefix should
        outlive cold ones.

        Every returned page re-verifies its checksum here: a page whose
        bytes no longer match its store-time seal is dropped (counted
        in ``corrupt_dropped``) and the run stops at it — the caller
        recomputes from there, so corrupt KV is never mapped, silently
        or otherwise.
        """
        P = self.page_size
        n_blocks = len(tokens) // P
        out: List[HostPage] = []
        for i in range(start_block, n_blocks):
            key = self._key(tokens, i)
            page = self.entries.get(key)
            if page is None:
                break
            if not page.verify():
                self._drop(key)
                self.corrupt_dropped += 1
                self.corrupt_dropped_bytes += page.nbytes
                break
            self._tick += 1
            page.tick = self._tick
            out.append(page)
        if out:
            self.hit_blocks += len(out)
        else:
            self.miss_lookups += 1
        return out

    # -- bookkeeping -----------------------------------------------------

    def stats(self) -> dict:
        return {
            "pages": len(self.entries),
            "bytes": self.bytes,
            "max_bytes": self.max_bytes,
            "tenant_budget": self.tenant_budget,
            "tenant_bytes": dict(self.tenant_bytes),
            "puts": self.puts,
            "dup_puts": self.dup_puts,
            "refused_puts": self.refused_puts,
            "hit_blocks": self.hit_blocks,
            "miss_lookups": self.miss_lookups,
            "evicted_pages": self.evicted_pages,
            "evicted_bytes": self.evicted_bytes,
            "corrupt_dropped": self.corrupt_dropped,
            "corrupt_dropped_bytes": self.corrupt_dropped_bytes,
            "purged_pages": self.purged_pages,
            "purged_bytes": self.purged_bytes,
        }

    def check(self) -> None:
        """Invariant audit (mirrors PageManager.check / PrefixCache.check).
        Does NOT re-verify checksums: an injected-corrupt page is
        legitimately resident until a match detects and drops it."""
        ledger = sum(p.nbytes for p in self.entries.values())
        assert ledger == self.bytes, \
            f"swap byte ledger drift: {self.bytes} != {ledger}"
        tled: Dict[str, int] = {}
        for p in self.entries.values():
            if p.tenant is not None:
                tled[p.tenant] = tled.get(p.tenant, 0) + p.nbytes
        assert tled == self.tenant_bytes, \
            f"tenant byte ledger drift: {self.tenant_bytes} != {tled}"
        if self.tenant_budget:
            for t, b in self.tenant_bytes.items():
                assert b <= self.tenant_budget, \
                    f"tenant {t!r} over swap budget: {b} > " \
                    f"{self.tenant_budget}"
        if self.max_bytes:
            assert self.bytes <= self.max_bytes, \
                f"swap store over budget: {self.bytes} > {self.max_bytes}"
        for key, page in self.entries.items():
            assert len(key) % self.page_size == 0 and len(key) > 0, \
                f"swap key length {len(key)} not a page multiple"
            assert page.key == key


class StagingRing:
    """Bounded ring of in-flight device→host staging transactions.

    Each transaction is ``(meta, device_tree)`` where ``device_tree``
    holds the (async-dispatched) gathered pages still on device.  The
    ring holds up to ``depth`` transactions before forcing the oldest
    to host — ``stage`` returns the matured ``(meta, host_tree)`` pairs
    (host leaves are ``np.ndarray``), ``drain`` flushes the rest.  With
    ``depth >= 2`` the gather for transaction *n+1* dispatches while
    transaction *n*'s D2H copy completes.
    """

    def __init__(self, width: int, depth: int = 2):
        assert width >= 1 and depth >= 1
        self.width = int(width)     # pages per transaction (fixed: one trace)
        self.depth = int(depth)
        self._ring: List[tuple] = []
        self.transactions = 0

    @staticmethod
    def _force(item):
        meta, dev = item
        # np.asarray blocks until the dispatched gather lands on host;
        # per-page slicing downstream copies out of this buffer.
        with annotate("repro.serve.sync"):
            return meta, jax.tree.map(np.asarray, dev)

    def stage(self, meta, device_tree) -> List[tuple]:
        """Enqueue one transaction; return any that matured to host."""
        self._ring.append((meta, device_tree))
        self.transactions += 1
        out = []
        while len(self._ring) > self.depth:
            out.append(self._force(self._ring.pop(0)))
        return out

    def drain(self) -> List[tuple]:
        out = [self._force(it) for it in self._ring]
        self._ring.clear()
        return out
