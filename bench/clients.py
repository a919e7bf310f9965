"""The clients' side of a run: set-up, the measured window, and what the
clients saw.

These clients are the serve loop's only caller.  They submit requests and
call ``PagedServeLoop.step()``; a token is stamped when the ``step()``
that produced it returns, which is what a streaming client sees.  Each
``step()``, the clients' bookkeeping and each submission run inside a
``jax.profiler.TraceAnnotation`` of their own, so a traced run can label
the device's idle gaps by what the host was doing.

The serve loop's decode forward is wrapped (``loop._decode``) to record,
per decode step, which request sat in which slot at which position, and
to keep the logits the step returned.  The records cost no device sync;
the correctness check reads the logits once the window has closed.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

from jax.profiler import TraceAnnotation

from repro.serve.loop import Request


@dataclasses.dataclass
class Tracked:
    req: Request
    sent: float                        # perf_counter at submit
    in_window: bool                    # sent inside the window
    stamps: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class DecodeRecord:
    """One decode forward: the logits it returned, and per live slot
    (slot, rid, position of the token it consumed)."""
    logits: object
    rows: List[tuple]
    in_window: bool = False


class Clients:
    def __init__(self, loop, gen, clock=time.perf_counter):
        self.loop = loop
        self.gen = gen
        self.clock = clock
        self.tracked: Dict[int, Tracked] = {}
        self.next_rid = 0
        self.failed = 0
        self.attempted = 0
        self.in_window = False
        self.decodes: List[DecodeRecord] = []
        self.window_decodes = 0
        self._done_seen = 0
        self._failed_seen = 0
        self._wrap_decode()

    def _wrap_decode(self):
        loop, inner = self.loop, self.loop._decode

        def decode(params, caches, cur, lens, block_table):
            rows = [(i, s["req"].rid, int(loop.lens[i]))
                    for i, s in enumerate(loop.slots) if s is not None]
            logits, caches = inner(params, caches, cur, lens, block_table)
            self.decodes.append(DecodeRecord(logits, rows, self.in_window))
            return logits, caches

        # the loop's compile-set check reads the jitted forward's count
        decode._cache_size = inner._cache_size
        loop._decode = decode

    # -- requests -------------------------------------------------------

    def submit(self, prompt, max_new: int) -> None:
        rid = self.next_rid
        self.next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new_tokens=int(max_new))
        if self.in_window:
            self.attempted += 1
        with TraceAnnotation("bench.submit"):
            t = self.clock()
            try:
                self.loop.submit(req)
            except ValueError:
                # AdmissionError and its kin are ValueErrors: refused
                if self.in_window:
                    self.failed += 1
                return
        self.tracked[rid] = Tracked(req, t, self.in_window)

    def _outputs(self) -> Dict[int, int]:
        """Tokens each unfinished-or-just-finished request holds now."""
        n = {}
        for s in self.loop.slots:
            if s is not None:
                n[s["req"].rid] = len(s["out"])
        done = self.loop.done
        for req in done[self._done_seen:]:
            n[req.rid] = len(req.output)
        return n

    def step(self) -> List[int]:
        """One ``step()``; stamps its tokens.  Returns the rids that
        finished (or failed) in it."""
        with TraceAnnotation("bench.step"):
            self.loop.step()
        t = self.clock()
        with TraceAnnotation("bench.client"):
            for rid, n in self._outputs().items():
                tr = self.tracked[rid]
                tr.stamps.extend([t] * (n - len(tr.stamps)))
            ended = [r.rid for r in self.loop.done[self._done_seen:]]
            self._done_seen = len(self.loop.done)
            lost = [r.rid for r in self.loop.failed[self._failed_seen:]]
            self._failed_seen = len(self.loop.failed)
            for rid in lost:
                if self.tracked[rid].in_window:
                    self.failed += 1
        return ended + lost

    # -- phases ---------------------------------------------------------

    def setup(self) -> None:
        """Warm prefixes (each sent once, to completion), then every
        client's first request, stepped until no request waits in the
        queue: every slot the traffic fills is live.  The copy-on-write
        page copy, which the window may need once a cached prefix is
        partly evicted, is compiled here too (scratch page onto itself)."""
        import jax.numpy as jnp

        self.loop.caches = self.loop._copy_page(
            self.loop.caches, jnp.int32(0), jnp.int32(0))
        for prompt in self.gen.warm_prompts():
            self.submit(prompt, 1)
            while len(self.loop.sched):
                self.step()
        for prompt, max_new in self.gen.first():
            self.submit(prompt, max_new)
        ended = []
        while len(self.loop.sched):
            ended += self.step()
        self._refill(ended)

    def _refill(self, ended) -> None:
        """Closed loop: each finished request's client sends its next."""
        for _ in ended:
            with TraceAnnotation("bench.client"):
                prompt, max_new = self.gen.next()
            self.submit(prompt, max_new)

    def window(self, seconds: float) -> tuple:
        """Run until the first ``step()`` boundary after ``seconds``.
        Returns (t0, t1)."""
        self.in_window = True
        t0 = self.clock()
        n0 = len(self.decodes)
        while True:
            ended = self.step()
            t1 = self.clock()
            if t1 - t0 >= seconds:
                break
            self._refill(ended)
        self.window_decodes = len(self.decodes) - n0
        for rec in self.decodes[n0:]:
            rec.in_window = True
        self.in_window = False
        return t0, t1

    # -- what the clients saw --------------------------------------------

    def window_tokens(self, t0: float, t1: float) -> int:
        return sum(1 for tr in self.tracked.values() for s in tr.stamps
                   if t0 < s <= t1)

    def ttfts(self, t1: float) -> List[float]:
        """Send-to-first-token of every request sent in the window; one
        still without a token at the window's end enters at its wait."""
        return [(tr.stamps[0] if tr.stamps and tr.stamps[0] <= t1 else t1)
                - tr.sent for tr in self.tracked.values() if tr.in_window]

    def prompt_tokens_admitted(self, t0: float, t1: float) -> int:
        """Real prompt tokens of the requests that got their first token
        in the window (the admissions the window paid for)."""
        return sum(len(tr.req.prompt) for tr in self.tracked.values()
                   if tr.stamps and t0 < tr.stamps[0] <= t1)

    def first_token_in(self, t0: float, t1: float) -> List[Request]:
        return [tr.req for tr in self.tracked.values()
                if tr.stamps and t0 < tr.stamps[0] <= t1]
