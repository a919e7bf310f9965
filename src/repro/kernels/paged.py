"""Paged KV cache primitives + paged decode attention dispatch.

The serving memory path (serve/paged.py) stores every attention layer's
K/V in fixed-size **pages** drawn from a per-layer physical pool::

    k_pages, v_pages : [n_pages, page_size, KV, hd]   (bf16)

A per-slot **block table** ``[B, max_blocks] int32`` maps logical block
``j`` of slot ``b`` to a physical page; the same table indexes every
layer's pool (all pools have identical structure).  Physical page 0 is
a *scratch* page the manager never hands out: idle slots' writes land
there and freed rows are reset to it, so a stale block-table row can
never alias a live slot's pages.

Why pages: admission/finish become page-list alloc/free (no multi-GB
cache copies), the decode compute graph is shape-stable (``max_blocks``
is fixed, so the serve loop compiles exactly one decode step), and the
flash-decode paths bound their work by the *valid* page count instead
of ``S_max`` — the O(S_max) dense-cache traffic per token the dense
path pays is gone.

``paged_attention`` impls (``dispatch_attention`` runs one):

- ``lax``        gather pages + masked softmax.  Bit-exact with the
                 dense-cache decode path (`models/attention._sdpa`):
                 identical einsum contractions, identical NEG_INF
                 masking — masked lanes contribute exact float zeros,
                 so the extra padded keys never perturb a bit.  The
                 oracle, and the trace-time fallback.
- ``flash-lax``  FlashDecoding in pure lax: online softmax over page
                 blocks with a *dynamic* trip count (``fori_loop`` up
                 to the longest live slot's block) — per-token work is
                 O(context), not O(S_max).  The production CPU path.
- ``flash``      the Pallas split-K kernel (kernels/flash_decode.py):
                 one whole page (every KV head) per grid step, per-
                 (slot, split) grid, block table via scalar prefetch.
- ``auto``       shape-keyed autotune (kernels/autotune.py): candidates
                 are verified against the ``lax`` oracle, then timed;
                 trace-time lookups are pure host-side cache reads and
                 fall back to ``lax`` on a miss.

**Quantised pools** (``KVQuantSpec``): the pool is dtype-polymorphic —
``fp`` (bf16, the historical layout, byte-for-byte unchanged), ``int8``
(one code byte per element) or ``int4`` (two codes packed per byte).
Quantised pools carry absmax scales *alongside the codes*, stored
page-structured as ``[n_pages, page_size, KV]`` — one scale per page
slot (token) per kv head, over the head dim.  Scales are per page slot,
NOT one scalar per whole page, deliberately: a whole-page scale would
have to be rescaled as later tokens land in the page, making the page's
codes a function of write *history* (chunk boundaries, decode order) —
which would break both the prefix cache's content-addressing (a cached
page must be a pure function of its token content) and the equal-
quantisation oracle discipline (the dense reference would have to
replay the paged write schedule).  Per-slot scales keep quantise ∘
write a pure per-token function, so paged-vs-dense stays bit-identical
at equal quantisation exactly the way the fp path is today, and every
composition (CoW, prefix sharing, speculative rollback) inherits it.

Quantisation happens on write (post-rotary K, raw V), dequantisation
inside each attention reader: the lax oracle dequantises its gather,
``flash-lax`` dequantises per visited page inside the online-softmax
loop, and the Pallas kernel loads code pages + their scale blocks
through the same block-table indexing and folds the scales into its
scores and probabilities (int4 contracts each nibble with its own
half of the query).  KV read/write traffic and pool bytes drop
~2x (int8) / ~4x (int4) relative to bf16; the scale sidecar costs
``2 / head_dim`` bytes per element (bf16 scales).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # matches models/attention.NEG_INF (bit-exact masking)

SCALE_DTYPE = jnp.bfloat16   # scale sidecar dtype (2 bytes / page slot / head)


@dataclasses.dataclass(frozen=True)
class PageSpec:
    """Static geometry of a paged KV pool (hashable: jit-static arg)."""

    page_size: int     # tokens per page
    n_pages: int       # physical pages per layer pool (page 0 = scratch)
    max_blocks: int    # block-table width == ceil(S_max / page_size)

    @property
    def capacity(self) -> int:
        """Allocatable tokens (scratch page excluded)."""
        return (self.n_pages - 1) * self.page_size

    @property
    def s_alloc(self) -> int:
        """Gathered sequence length: max_blocks * page_size."""
        return self.max_blocks * self.page_size


def spec_for(S_max: int, batch_slots: int, page_size: int = 16,
             n_pages: Optional[int] = None) -> PageSpec:
    """Pool geometry for a serve loop: by default capacity parity with
    the dense cache (every slot can grow to S_max) plus the scratch
    page.  Pass a smaller ``n_pages`` to oversubscribe."""
    max_blocks = -(-S_max // page_size)
    if n_pages is None:
        n_pages = batch_slots * max_blocks + 1
    return PageSpec(page_size=page_size, n_pages=n_pages,
                    max_blocks=max_blocks)


# ---------------------------------------------------------------------------
# KV quantisation
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KVQuantSpec:
    """Quantised paged-KV layout (hashable: usable as a jit-static arg).

    ``dtype``:
      fp    bf16 pool, no scales — the historical layout, unchanged.
      int8  one int8 code per element, absmax scale per (page slot,
            kv head) over the head dim.
      int4  two codes packed per int8 byte (low nibble = even element),
            same scale layout; codes span the full [-8, 7] range —
            scale ``amax / 7.5`` with the +amax endpoint clipping onto
            code 7, so the worst-case step error is ``amax / 15``
            (wasting the -8 code, as an early version did with a ±7
            clip at scale ``amax / 7``, costs ``amax / 14``).
    """

    dtype: str = "fp"

    def __post_init__(self):
        if self.dtype not in ("fp", "int8", "int4"):
            raise ValueError(
                f"serve_kv_dtype must be fp | int8 | int4, got {self.dtype!r}"
            )

    @property
    def quantised(self) -> bool:
        return self.dtype != "fp"

    @property
    def qmax(self) -> int:
        return {"int8": 127, "int4": 7}[self.dtype]

    @property
    def qlo(self) -> int:
        """Lowest representable code.  int4 uses the asymmetric -8 of
        two's complement; int8 keeps the historical symmetric -127 (its
        step error is already ~0.4% — not worth perturbing the pinned
        int8-vs-fp greedy identity for the extra half step)."""
        return {"int8": -127, "int4": -8}[self.dtype]

    @property
    def qdiv(self) -> float:
        """absmax -> scale divisor: the largest magnitude that still
        rounds into [qlo, qmax] (7.5 for int4: +amax rounds half-even
        to 8 and clips onto 7, -amax rounds to the representable -8 —
        both end up exactly half a step from their code)."""
        return {"int8": 127.0, "int4": 7.5}[self.dtype]

    @property
    def packed(self) -> bool:
        return self.dtype == "int4"

    def code_width(self, hd: int) -> int:
        """Last-axis width of the code array for head dim ``hd``."""
        if self.packed:
            if hd % 2:
                raise ValueError(f"int4 packing needs an even head dim, "
                                 f"got {hd}")
            return hd // 2
        return hd


def qspec_for(cfg) -> KVQuantSpec:
    """The serve-path KV quantisation spec a config asks for."""
    return KVQuantSpec(getattr(cfg, "serve_kv_dtype", "fp"))


def pack_int4(codes):
    """Pack int8 codes in [-8, 7] two-per-byte (low nibble = even
    element of the last axis)."""
    if codes.shape[-1] % 2:
        raise ValueError(f"int4 packing needs an even head dim, "
                         f"got {codes.shape[-1]}")
    lo = codes[..., 0::2]
    hi = codes[..., 1::2]
    return ((lo & 0x0F) | (hi << 4)).astype(jnp.int8)


def int4_nibbles(packed):
    """Sign-extended ``(low, high)`` nibbles of int8 ``[..., w]`` as
    int32 ``[..., w]``: the even and odd elements ``pack_int4`` packed."""
    p = packed.astype(jnp.int32)
    return (p << 28) >> 28, (p << 24) >> 28


def unpack_int4(packed):
    """Inverse of ``pack_int4``: int8 ``[..., w]`` -> ``[..., 2w]``
    sign-extended codes.  Lossless for codes in [-8, 7]."""
    lo, hi = int4_nibbles(packed)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], 2 * packed.shape[-1]).astype(
        jnp.int8)


def quantise_kv(x, qspec: KVQuantSpec):
    """Per-token symmetric absmax quantisation over the head dim.

    ``x [..., hd]`` float -> ``(codes [..., code_width], scales [...])``.
    The scale is a pure function of the one vector it quantises (no
    page history), computed in f32 and stored in ``SCALE_DTYPE``; codes
    round half-to-even and clip to [qlo, qmax] — int4 spans the full
    [-8, 7] two's-complement range (scale ``amax / 7.5``), int8 stays
    symmetric ±127.  An all-zero vector gets scale 1 (codes 0), never
    a 0/0."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / qspec.qdiv, 1.0).astype(SCALE_DTYPE)
    codes = jnp.clip(
        jnp.round(xf / scale.astype(jnp.float32)[..., None]),
        qspec.qlo, qspec.qmax,
    ).astype(jnp.int8)
    if qspec.packed:
        codes = pack_int4(codes)
    return codes, scale


def dequantise_kv(codes, scales, qspec: KVQuantSpec):
    """``codes [..., code_width]`` + ``scales [...]`` -> f32 ``[..., hd]``.
    The exact read-path product (f32 code x f32-cast scale) every
    reader — and the equal-quantisation dense oracle — must share."""
    if qspec.packed:
        codes = unpack_int4(codes)
    return codes.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]


def kv_roundtrip(x, qspec: KVQuantSpec):
    """quantise -> dequantise.  The dense oracle applies this to its
    cache writes so paged-vs-dense stays bit-identical at equal
    quantisation (both paths then attend over the same f32 values)."""
    codes, scales = quantise_kv(x, qspec)
    return dequantise_kv(codes, scales, qspec)


def zero_kv_pool(spec: PageSpec, KV: int, hd: int,
                 qspec: Optional[KVQuantSpec] = None) -> dict:
    """Zeroed paged pool for one attention layer.  fp keeps the
    historical two-leaf layout; quantised pools add the scale sidecars
    (``ks``/``vs``, ones: zero codes x 1.0 = exact zeros)."""
    qspec = qspec or KVQuantSpec()
    if not qspec.quantised:
        z = jnp.zeros((spec.n_pages, spec.page_size, KV, hd), jnp.bfloat16)
        return {"k": z, "v": z}
    z = jnp.zeros((spec.n_pages, spec.page_size, KV, qspec.code_width(hd)),
                  jnp.int8)
    s = jnp.ones((spec.n_pages, spec.page_size, KV), SCALE_DTYPE)
    return {"k": z, "v": z, "ks": s, "vs": s}


# ---------------------------------------------------------------------------
# page writes / reads
# ---------------------------------------------------------------------------


def _write_kv(kv: dict, pid, off, k, v, qspec: Optional[KVQuantSpec]):
    """Shared scatter for every write path: quantise-on-write when the
    pool is quantised (codes AND scales land at the same ``[pid, off]``
    page slots), plain dtype-cast stores for fp."""
    qspec = qspec or KVQuantSpec()
    if not qspec.quantised:
        return dict(kv,
                    k=kv["k"].at[pid, off].set(k.astype(kv["k"].dtype)),
                    v=kv["v"].at[pid, off].set(v.astype(kv["v"].dtype)))
    kq, ks = quantise_kv(k, qspec)
    vq, vs = quantise_kv(v, qspec)
    return dict(kv,
                k=kv["k"].at[pid, off].set(kq),
                v=kv["v"].at[pid, off].set(vq),
                ks=kv["ks"].at[pid, off].set(ks),
                vs=kv["vs"].at[pid, off].set(vs))


def write_decode_kv(kv: dict, k, v, block_table, positions,
                    qspec: Optional[KVQuantSpec] = None) -> dict:
    """Write one decode token per slot into a (possibly quantised) pool.

    k/v ``[B, 1, KV, hd]``; ``positions [B]`` is each slot's write
    position (== its current length).  Idle slots' block-table rows are
    all zeros, so their writes land in the scratch page."""
    P = kv["k"].shape[1]
    blk = positions // P
    pid = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
    off = positions % P
    return _write_kv(kv, pid, off, k[:, 0], v[:, 0], qspec)


def write_decode(k_pages, v_pages, k, v, block_table, positions):
    """Array-level fp form of ``write_decode_kv`` (kept for callers
    that carry the two pool leaves positionally)."""
    kv = write_decode_kv({"k": k_pages, "v": v_pages}, k, v, block_table,
                         positions)
    return kv["k"], kv["v"]


def write_chunk_kv(kv: dict, k, v, block_table_row, start,
                   qspec: Optional[KVQuantSpec] = None) -> dict:
    """Write one fixed-size prefill chunk into a slot's pages.

    k/v ``[1, C, KV, hd]``; ``block_table_row [max_blocks]``; ``start``
    is the chunk's first absolute position.  The padded tail of the
    last chunk writes garbage *within the slot's own allocated pages*
    (admission allocates up to the padded chunk length); those
    positions sit beyond ``len`` so every read masks them, and decode
    overwrites each one before it becomes visible.  Quantised pools
    quantise each garbage row with its own scale, so a padding row can
    never perturb a valid row's codes."""
    C = k.shape[1]
    P = kv["k"].shape[1]
    pos = start + jnp.arange(C)
    pid = block_table_row[pos // P]
    off = pos % P
    return _write_kv(kv, pid, off, k[0], v[0], qspec)


def write_chunk(k_pages, v_pages, k, v, block_table_row, start):
    """Array-level fp form of ``write_chunk_kv``."""
    kv = write_chunk_kv({"k": k_pages, "v": v_pages}, k, v,
                        block_table_row, start)
    return kv["k"], kv["v"]


def write_spec_kv(kv: dict, k, v, block_table, positions, n_writes,
                  qspec: Optional[KVQuantSpec] = None) -> dict:
    """Write a fixed-width speculative verify window per slot.

    k/v ``[B, K1, KV, hd]`` — token row ``j`` of slot ``b`` lands at
    absolute position ``positions[b] + j``.  Only the first
    ``n_writes[b]`` rows are real (the slot's current token plus its
    live draft); the remaining rows of the fixed ``K1`` window are
    padding whose writes are routed to the scratch page (page 0),
    exactly like an idle slot's decode write — so a slot drafting
    fewer than ``K1 - 1`` tokens (draft clamped near ``max_new`` /
    capacity, or an n-gram miss) can share the one compiled verify
    shape without its padding ever touching live pages.  Quantised
    pools route the padding rows' scales to the scratch page the same
    way.

    Valid rows index the block table like ``write_decode_kv``; the
    block index is clamped into table range before the gather because
    padded rows of a slot near capacity may compute ``pos // P`` one
    past the last block (their page id is overridden to scratch
    anyway)."""
    K1 = k.shape[1]
    P = kv["k"].shape[1]
    pos = positions[:, None] + jnp.arange(K1)[None, :]       # [B, K1]
    blk = jnp.minimum(pos // P, block_table.shape[1] - 1)
    pid = jnp.take_along_axis(block_table, blk, axis=1)      # [B, K1]
    valid = jnp.arange(K1)[None, :] < n_writes[:, None]
    pid = jnp.where(valid, pid, 0)                           # pad -> scratch
    off = pos % P
    return _write_kv(kv, pid, off, k, v, qspec)


def write_spec(k_pages, v_pages, k, v, block_table, positions, n_writes):
    """Array-level fp form of ``write_spec_kv``."""
    kv = write_spec_kv({"k": k_pages, "v": v_pages}, k, v, block_table,
                       positions, n_writes)
    return kv["k"], kv["v"]


def copy_page_kv(kv: dict, src, dst) -> dict:
    """Copy-on-write: duplicate physical page ``src`` into ``dst``
    across every leaf of one layer's pool — codes AND scale sidecars
    (a CoW'd quantised page must dequantise identically to its
    source, so the scales travel with the codes)."""
    return {name: leaf.at[dst].set(leaf[src]) for name, leaf in kv.items()}


def copy_page(k_pages, v_pages, src, dst):
    """Copy-on-write: duplicate physical page ``src`` into ``dst`` in
    one layer's K/V pool (``[n_pages, P, KV, hd]``).

    The prefix cache (serve/prefix_cache.py) shares pages between the
    radix tree and any number of slots; a write that would land on a
    shared page first duplicates it with this copy and swaps the
    block-table entry, so a cached page's content is immutable while
    referenced.  ``src``/``dst`` are traced scalars — one compile
    covers every CoW.  Stacked-layer caches go through
    ``models/lm.cache_copy_page``, which maps this over the tree (and,
    because it maps over every leaf, copies quantised pools' scale
    sidecars for free)."""
    return (k_pages.at[dst].set(k_pages[src]),
            v_pages.at[dst].set(v_pages[src]))


def swap_out_kv(kv: dict, page_ids) -> dict:
    """Gather ``page_ids [R]`` whole pages out of one layer's pool for
    a device→host swap: every leaf — codes AND scale sidecars — yields
    its ``[R, page_size, ...]`` page rows, so a quantised pool swaps
    losslessly (raw int8 code bytes + bf16 scales travel together; no
    dequant, no re-quant, bit-identical on restore by construction).
    ``page_ids`` is a traced vector of FIXED width — the staging-ring
    transaction size — so one compile covers every swap the serve loop
    ever performs (short transactions pad with the scratch page)."""
    return {name: leaf[page_ids] for name, leaf in kv.items()}


def swap_in_kv(kv: dict, staged: dict, page_ids) -> dict:
    """Inverse of ``swap_out_kv``: scatter staged host pages back into
    freshly-allocated physical pages.  ``staged`` leaves are
    ``[R, page_size, ...]`` in the pool leaf's own dtype; padding rows
    of a short transaction carry page id 0 and land harmlessly in the
    scratch page (whose content is never read unmasked)."""
    return {name: leaf.at[page_ids].set(staged[name].astype(leaf.dtype))
            for name, leaf in kv.items()}


def gather_kv(k_pages, v_pages, block_table):
    """Materialise per-slot K/V ``[B, s_alloc, KV, hd]`` through the
    block table (the lax paths; the flash paths never call this).

    Read-only with respect to the pool: every attention read path
    (this gather, the flash kernels' per-page loads) only loads pages,
    so block-table rows may freely alias shared prefix-cache pages —
    the write paths (``write_decode``/``write_chunk``) are the only
    ones that need the copy-on-write guard."""
    B, MB = block_table.shape
    _, P, KV, hd = k_pages.shape
    kc = k_pages[block_table].reshape(B, MB * P, KV, hd)
    vc = v_pages[block_table].reshape(B, MB * P, KV, hd)
    return kc, vc


def gather_kv_deq(kv: dict, block_table, qspec: Optional[KVQuantSpec] = None):
    """``gather_kv`` over a (possibly quantised) pool dict.

    fp pools return the bf16 pages untouched (byte-identical to the
    historical path); quantised pools gather the code pages + scale
    sidecars and dequantise to the f32 values every reader shares."""
    qspec = qspec or KVQuantSpec()
    if not qspec.quantised:
        return gather_kv(kv["k"], kv["v"], block_table)
    B, MB = block_table.shape
    _, P, KV, _ = kv["k"].shape
    kc = dequantise_kv(kv["k"][block_table], kv["ks"][block_table], qspec)
    vc = dequantise_kv(kv["v"][block_table], kv["vs"][block_table], qspec)
    return (kc.reshape(B, MB * P, KV, -1), vc.reshape(B, MB * P, KV, -1))


# ---------------------------------------------------------------------------
# attention impls
# ---------------------------------------------------------------------------


def _attend_lax(q, kv, block_table, positions, window: Optional[int],
                qspec: Optional[KVQuantSpec]):
    """Gather + masked softmax — the same contraction/mask sequence as
    models/attention._sdpa_direct, so it is bit-exact with the dense
    decode path (masked keys contribute exact zeros).  Quantised pools
    dequantise the gathered codes to the same f32 values the quantised
    dense oracle stores, so the bit-exactness contract survives
    quantisation unchanged."""
    B, Sq, H, dk = q.shape
    KV = kv["k"].shape[2]
    rep = H // KV
    kc, vc = gather_kv_deq(kv, block_table, qspec)
    S = kc.shape[1]
    j = jnp.arange(S)[None, :]
    mask = j <= positions[:, None]
    if window is not None:
        mask &= j > positions[:, None] - window
    mask = mask[:, None, None, None, :]                  # [B,1,1,1,S]
    qg = q.reshape(B, Sq, KV, rep, dk)
    scale = 1.0 / math.sqrt(dk)
    scores = jnp.einsum(
        "bqkrh,bskh->bkrqs", qg.astype(jnp.float32), kc.astype(jnp.float32)
    ) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrqs,bskh->bkrqh", w, vc.astype(jnp.float32))
    dv = vc.shape[-1]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H * dv).astype(q.dtype)


def _attend_flash_lax(q, kv, block_table, positions, window: Optional[int],
                      qspec: Optional[KVQuantSpec]):
    """FlashDecoding in pure lax: online softmax over page blocks with a
    dynamic trip count — work is O(longest live context), never
    O(s_alloc).  Fully-masked blocks are handled by zeroing masked
    probabilities (not by trusting the running max).  Quantised pools
    dequantise per visited page INSIDE the loop: the HBM traffic per
    token is the code page (+ its scale sidecar), never a dequantised
    fp copy of the context."""
    qspec = qspec or KVQuantSpec()
    B, Sq, H, dk = q.shape
    k_pages, v_pages = kv["k"], kv["v"]
    _, P, KV, _ = k_pages.shape
    hd = dk
    rep = H // KV
    qg = q.reshape(B, KV, rep, dk).astype(jnp.float32)
    scale = 1.0 / math.sqrt(dk)
    n_blocks = jnp.max(positions) // P + 1               # dynamic bound

    def body(i, carry):
        m, l, acc = carry
        pid = block_table[:, i]                          # [B]
        if qspec.quantised:
            kb = dequantise_kv(k_pages[pid], kv["ks"][pid], qspec)
            vb = dequantise_kv(v_pages[pid], kv["vs"][pid], qspec)
        else:
            kb = k_pages[pid].astype(jnp.float32)        # [B,P,KV,hd]
            vb = v_pages[pid].astype(jnp.float32)
        s = jnp.einsum("bkrh,bskh->bkrs", qg, kb) * scale
        jpos = i * P + jnp.arange(P)
        msk = jpos[None, :] <= positions[:, None]
        if window is not None:
            msk &= jpos[None, :] > positions[:, None] - window
        msk = msk[:, None, None, :]
        row_max = jnp.max(jnp.where(msk, s, NEG_INF), axis=-1)
        m_new = jnp.maximum(m, row_max)
        p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkrs,bskh->bkrh", p, vb)
        return m_new, l, acc

    m0 = jnp.full((B, KV, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, rep), jnp.float32)
    a0 = jnp.zeros((B, KV, rep, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]         # [B,KV,rep,hd]
    return out.reshape(B, 1, H * hd).astype(q.dtype)


def _as_kv(k_pages, v_pages, k_scales, v_scales,
           qspec: Optional[KVQuantSpec]):
    """Assemble the pool dict from positional operands (the public
    array-level entry points keep the historical signature; quantised
    callers pass the scale sidecars by keyword)."""
    qspec = qspec or KVQuantSpec()
    if not qspec.quantised:
        return {"k": k_pages, "v": v_pages}, qspec
    if k_scales is None or v_scales is None:
        raise ValueError(
            f"kv dtype {qspec.dtype!r} needs k_scales/v_scales sidecars"
        )
    return {"k": k_pages, "v": v_pages, "ks": k_scales, "vs": v_scales}, qspec


def dispatch_attention(config, q, k_pages, v_pages, block_table, positions,
                       *, window: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       k_scales=None, v_scales=None,
                       qspec: Optional[KVQuantSpec] = None):
    """Run one paged-attention candidate config.  q ``[B, 1, H, hd]``;
    returns ``[B, 1, H*hd]`` in q.dtype.  Quantised pools pass int8
    code pages plus their ``[n_pages, P, KV]`` scale sidecars; every
    impl fuses the dequant into its read loop."""
    impl = config["impl"]
    kv, qspec = _as_kv(k_pages, v_pages, k_scales, v_scales, qspec)
    if impl == "lax":
        return _attend_lax(q, kv, block_table, positions, window, qspec)
    if impl == "flash-lax":
        return _attend_flash_lax(q, kv, block_table, positions, window,
                                 qspec)
    if impl == "flash":
        from repro.kernels.flash_decode import flash_decode

        B, Sq, H, hd = q.shape
        KV = k_pages.shape[2]
        rep = H // KV
        out = flash_decode(
            q.reshape(B, KV, rep, hd), k_pages, v_pages, block_table,
            positions + 1, window=window,
            n_splits=config.get("n_splits", 4), interpret=interpret,
            k_scales=k_scales, v_scales=v_scales, kv_dtype=qspec.dtype,
        )
        return out.reshape(B, 1, H * hd).astype(q.dtype)
    raise ValueError(f"unknown paged attention impl {impl!r}")


def paged_attention(q, k_pages, v_pages, block_table, positions, *,
                    window: Optional[int] = None, impl: str = "auto",
                    tune_on_miss: bool = False,
                    k_scales=None, v_scales=None,
                    qspec: Optional[KVQuantSpec] = None):
    """Paged decode attention with autotuned dispatch.

    ``impl='auto'`` resolves through the shape-keyed cache
    (kernels/autotune.py, same verify-then-time contract as the lookup
    GEMMs); inside jit the lookup is a pure host-side read and a miss
    lowers the ``lax`` oracle.  ``tune_on_miss`` only fires on concrete
    operands (benchmarks pre-tune; serving never sweeps inline).
    Quantised pools key the cache with the kv dtype as well — an int8
    pool's winner never serves an fp pool's shape."""
    if impl != "auto":
        return dispatch_attention(
            {"impl": impl}, q, k_pages, v_pages, block_table, positions,
            window=window, k_scales=k_scales, v_scales=v_scales,
            qspec=qspec,
        )
    from repro.kernels import autotune

    B, Sq, H, hd = q.shape
    KV = k_pages.shape[2]
    key = autotune.attn_shape_key(
        B, KV, H // KV, hd, block_table.shape[1], k_pages.shape[1],
        window, kv_dtype=(qspec or KVQuantSpec()).dtype,
    )
    config = autotune.lookup(key)
    if config is None:
        if tune_on_miss and not isinstance(q, jax.core.Tracer):
            config = autotune.tune_attention(
                q, k_pages, v_pages, block_table, positions, window=window,
                k_scales=k_scales, v_scales=v_scales, qspec=qspec,
            )
        else:
            config = {"impl": "lax"}
    return dispatch_attention(
        config, q, k_pages, v_pages, block_table, positions, window=window,
        k_scales=k_scales, v_scales=v_scales, qspec=qspec,
    )


def pool_scales(kv: dict):
    """(k_scales, v_scales) of a pool dict, or (None, None) for fp."""
    return kv.get("ks"), kv.get("vs")
