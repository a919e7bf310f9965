"""Dense-cache serving loop with slot-based continuous batching.

This is the *reference* loop: dense ``[B, S_max]`` caches, a shared
decode clock, left-padded prompts.  It is kept as the bit-exact oracle
the paged path is verified against, and as the fallback for block
kinds whose state cannot be paged (recurrent / enc-dec families — see
``lm.supports_paged``).  Production serving for attention families is
``serve.paged.PagedServeLoop``: paged KV pool + block tables, fixed-
shape chunked prefill, and a compile set of exactly two forward shapes
(this loop retraces its refill prefill per distinct padded length).

Static decode batch of B slots; finished sequences free their slot and
the next queued request is prefilled into it *mid-decode* — the freed
slot does not idle until the whole batch drains.  Decode runs the serve
path (TLMAC lookup GEMMs when cfg.serve_impl == 'tlmac') — the regime
the paper targets: static weights, repeated small-batch MACs.  The
lookup-GEMM impl follows ``cfg.serve_tlmac_impl`` (default 'auto': the
shape-keyed autotune cache, kernels/autotune.py).

Refill mechanics: all slots share one scalar decode position ``pos``
(prompts are left-padded).  A request admitted at decode step t is
prefilled alone, left-padded to the current length S + t, and its
prefill caches are written into the freed slot of the batch caches —
so the very next ``decode_step`` advances it together with the
still-running slots.  A queued prompt longer than the current length
waits (FIFO is preserved; the shared position grows every step, so it
is admitted as soon as it fits or at the next batch).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int = 16
    output: Optional[np.ndarray] = None  # generated tokens.  Complete
                                  # iff finish_reason is 'stop'/'length';
                                  # a cancelled/expired request carries
                                  # its PARTIAL output here (always a
                                  # prefix of what an uninterrupted run
                                  # would emit).
    priority: Optional[int] = None  # paged-loop admission priority
                                  # (higher = sooner; None = the
                                  # configured default).  The dense
                                  # loop is strictly FIFO and ignores
                                  # it.
    tenant: Optional[str] = None  # fairness label (paged loop):
                                  # per-tenant page quotas, swap-byte
                                  # budgets, and load-weighted aging
                                  # key off it.  None = the shared
                                  # 'default' tenant.  The dense loop
                                  # ignores it.
    deadline_s: Optional[float] = None  # TTL budget in seconds from
                                  # submit; the paged loop sheds the
                                  # request (typed reason, partial
                                  # output) at the first step boundary
                                  # past it.  None follows
                                  # cfg.serve_deadline_s (0 = none).
                                  # The dense loop ignores it.
    finish_reason: Optional[str] = None  # terminal state: 'stop' (eos)
                                  # | 'length' (max_new_tokens / s_max)
                                  # | 'cancelled' | 'deadline' (paged
                                  # loop; None while in flight)
    error: Optional[BaseException] = None  # the typed reason for a
                                  # non-completion: CancelledError or
                                  # DeadlineExceededError
                                  # (serve/scheduler.py); None on
                                  # success
    queue_wait_s: Optional[float] = None  # paged loop: seconds from
                                  # enqueue to the first admission (the
                                  # scheduler's queue-wait observation);
                                  # a resume keeps it.  None until
                                  # admitted; the dense loop leaves it.


class ServeLoop:
    def __init__(self, params, cfg, batch_slots: int = 4, s_max: int = 128,
                 eos_id: Optional[int] = None):
        self.params, self.cfg = params, cfg
        self.B, self.S_max = batch_slots, s_max
        self.eos_id = eos_id
        self.queue = deque()
        self.done: List[Request] = []
        self.refills = 0              # mid-decode slot refills (stats)
        self._write_jit = None
        self._decode = jax.jit(
            lambda p, c, t, pos: lm.decode_step(p, c, t, pos, cfg)
        )

    def submit(self, req: Request):
        self.queue.append(req)

    def prompt_logits(self, prompt) -> np.ndarray:
        """f32 ``[vocab]`` logits at the last position of ``prompt``:
        the prefill a solo batch of this prompt runs."""
        tokens = jnp.asarray(np.asarray(prompt, np.int32)[None])
        logits, _ = lm.prefill(self.params, {"tokens": tokens}, self.cfg,
                               S_max=self.S_max)
        return np.asarray(logits[0], np.float32)

    def run(self):
        """Process the queue; greedy decoding. Returns finished requests."""
        while self.queue:
            n = min(self.B, len(self.queue))
            batch = [self.queue.popleft() for _ in range(n)]
            self._run_batch(batch)
        return self.done

    # -- continuous batch ---------------------------------------------------

    def _finish(self, slot):
        slot["req"].output = np.asarray(slot["out"], np.int32)
        self.done.append(slot["req"])

    def _write_slot(self, caches, caches_one, i: int):
        """Copy a 1-request prefill cache into batch slot i (axis 1 of
        every [n_layers, B, ...] leaf).  Jitted with the batch caches
        donated: the update then aliases the existing buffers instead
        of copying the full multi-GB cache once per refill."""
        if self._write_jit is None:
            def write(cb, co, idx):
                def upd(c, c1):
                    return jax.lax.dynamic_update_slice_in_dim(
                        c, c1.astype(c.dtype), idx, axis=1
                    )
                return [
                    jax.tree.map(upd, b, o) for b, o in zip(cb, co)
                ]
            self._write_jit = jax.jit(write, donate_argnums=(0,))
        return self._write_jit(caches, caches_one, jnp.int32(i))

    def _try_refill(self, caches, cur_np, L: int, slot_i: int):
        """Admit the queue head into a freed slot if its prompt fits the
        current shared length L.  Every distinct L is a distinct prefill
        shape => a fresh XLA trace at request time — the retrace cost
        the paged loop's fixed-size chunks eliminate.  Returns
        (slots_entry, caches) or (None, caches)."""
        if not self.queue or len(self.queue[0].prompt) > L or L >= self.S_max:
            return None, caches
        req = self.queue.popleft()
        toks = np.zeros((1, L), np.int32)
        toks[0, L - len(req.prompt):] = req.prompt       # left-pad to L
        logits, caches_one = lm.prefill(
            self.params, {"tokens": jnp.asarray(toks)}, self.cfg,
            S_max=self.S_max,
        )
        caches = self._write_slot(caches, caches_one, slot_i)
        cur_np[slot_i, 0] = int(np.asarray(jnp.argmax(logits, -1))[0])
        self.refills += 1
        return {"req": req, "out": []}, caches

    def _run_batch(self, reqs: List[Request]):
        B = len(reqs)
        S = max(len(r.prompt) for r in reqs)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt       # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        logits, caches = lm.prefill(self.params, batch, self.cfg,
                                    S_max=self.S_max)
        slots = [{"req": r, "out": []} for r in reqs]
        cur_np = np.array(jnp.argmax(logits, -1))[:, None]
        step = 0
        while True:
            # 1) record the pending token per live slot; finish + free
            for i in range(B):
                slot = slots[i]
                if slot is None:
                    continue
                slot["out"].append(int(cur_np[i, 0]))
                hit_eos = (self.eos_id is not None
                           and slot["out"][-1] == self.eos_id)
                if hit_eos or len(slot["out"]) >= slot["req"].max_new_tokens:
                    self._finish(slot)
                    slots[i] = None
            # 2) continuous batching: refill freed slots from the queue.
            #    The next decode writes cache position S + step, so the
            #    refill prefill must cover exactly [0, S + step) and its
            #    argmax token stands at position S + step — same shared
            #    clock as the live slots.  That argmax IS the request's
            #    first generated token: record it here, symmetric with
            #    phase 1 recording the batch prefill's argmax at step 0
            #    (a refilled request must not lose its first token).
            for i in range(B):
                while slots[i] is None:
                    entry, caches = self._try_refill(
                        caches, cur_np, S + step, i
                    )
                    if entry is None:
                        break
                    tok0 = int(cur_np[i, 0])
                    entry["out"].append(tok0)
                    done_now = (
                        (self.eos_id is not None and tok0 == self.eos_id)
                        or len(entry["out"]) >= entry["req"].max_new_tokens
                    )
                    if done_now:
                        self._finish(entry)   # slot frees again: loop
                    else:
                        slots[i] = entry
            if not any(s is not None for s in slots):
                break
            if S + step >= self.S_max:
                # cache capacity exhausted: emit what we have
                for i in range(B):
                    if slots[i] is not None:
                        self._finish(slots[i])
                        slots[i] = None
                break
            # 3) one decode step for the whole batch
            logits, caches = self._decode(
                self.params, caches, jnp.asarray(cur_np), jnp.int32(S + step)
            )
            cur_np = np.array(jnp.argmax(logits, -1))[:, None]
            step += 1
