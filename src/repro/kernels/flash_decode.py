"""Pallas split-K flash-decode over a paged KV cache.

FlashDecoding for the serve path: one query token per slot, K/V read
through the block table (kernels/paged.py layout), online softmax run
per split of the page range, partials combined outside the kernel.

Grid ``(B, n_splits, blocks_per_split)`` — the last dim is
innermost/sequential, so the online-softmax state for one (slot, split)
lives in VMEM scratch across its block steps and is flushed to the
partial outputs on the split's final step.

One grid step reads one whole page: the K/V block is ``(1, P, KV,
hd)`` over the ``[n_pages, P, KV, hd]`` pool, i.e. every KV head of
the page at once.  The block's last two dims equal the pool's, which
is what the TPU lowering requires of a block (a per-head ``(1, P, 1,
hd)`` block is refused), and it leaves the pool layout and every page
writer untouched.  The per-head contraction runs on the VPU as a
broadcast multiply + lane reduction over ``[P, KV, hd]``: at decode
each K/V element meets one query row per group member, so the kernel
is bound by the page reads, not by arithmetic.  GQA's ``rep`` query
heads per KV head are a static loop over the same page.

The block table and per-slot lengths ride in scalar prefetch: the K/V
page BlockSpecs *compute their HBM block index from the table*, which
is what makes the cache paged as far as the kernel is concerned.
Invalid steps (beyond a slot's valid pages) map to physical page 0 —
the pool's scratch page — and skip their compute under ``pl.when``;
since consecutive revisits of the same block index skip the copy, the
wasted traffic is one scratch page, not O(S_max).

Quantised pools (``kv_dtype`` int8/int4): the code pages stream in as
int8 blocks and their per-(page slot, head) absmax scales ride as
``(1, P, KV)`` blocks whose index map follows the SAME block-table
lookup as the codes — the scale DMA is paged exactly like the data it
scales.  The scales multiply the scores (K) and the probabilities (V)
instead of the codes.  int4 never interleaves its nibbles in-kernel:
the query arrives split into even/odd halves of the head dim, the low
and high nibbles of each code byte contract against their own half,
and the two halves of the output are interleaved outside the kernel.
HBM traffic per token is the code page plus a P x KV scale block — 2x
(int8) / ~4x (int4) less than the bf16 pool.

Numerics: fully-masked visits never poison the running max because
masked probabilities are zeroed explicitly (``where(mask, exp, 0)``)
rather than trusting ``exp(NEG_INF - m)`` to underflow.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# pure-jnp nibble decode, shared with the lax readers so the packing
# convention has exactly one implementation (no import cycle: paged.py
# only imports this module lazily inside dispatch_attention)
from repro.kernels.paged import int4_nibbles
from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30


def _code_parts(codes, kv_dtype: str):
    """A ``[P, KV, hdc]`` page block -> the f32 operands it contributes:
    the values themselves (fp/int8), or the low and high nibbles of
    each byte (int4), which pair with the even and odd query halves."""
    if kv_dtype == "int4":
        return [n.astype(jnp.float32) for n in int4_nibbles(codes)]
    return [codes.astype(jnp.float32)]


def _kernel(
    bt_ref,       # [B, MB] int32   scalar prefetch: block table
    len_ref,      # [B]     int32   scalar prefetch: per-slot lengths
    *refs,
    P: int,
    bps: int,
    window: Optional[int],
    kv_dtype: str,
):
    quantised = kv_dtype != "fp"
    if quantised:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_s, m_s, l_s) = refs
    else:
        (q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_s, m_s, l_s) = refs
    b = pl.program_id(0)
    s = pl.program_id(1)
    i = pl.program_id(2)
    blk = s * bps + i
    L = len_ref[b]
    rep, E, KV, hdc = acc_s.shape

    @pl.when(i == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    @pl.when(blk * P < L)
    def _visit():
        kparts = _code_parts(k_ref[0], kv_dtype)        # E x [P, KV, hdc]
        vparts = _code_parts(v_ref[0], kv_dtype)
        jpos = blk * P + jax.lax.broadcasted_iota(jnp.int32, (P, KV, 1), 0)
        msk = jpos < L
        if window is not None:
            msk &= jpos > (L - 1) - window
        scale = 1.0 / math.sqrt(E * hdc)
        if quantised:
            # dequant by scaling scores/probabilities, not codes
            ks = ks_ref[0].astype(jnp.float32)[:, :, None]   # [P, KV, 1]
            vs = vs_ref[0].astype(jnp.float32)[:, :, None]
        for r in range(rep):
            scores = jnp.zeros((P, KV, 1), jnp.float32)
            for e in range(E):
                qe = q_ref[0, r, e].astype(jnp.float32)      # [KV, hdc]
                scores += jnp.sum(qe[None] * kparts[e], axis=-1,
                                  keepdims=True)
            if quantised:
                scores = scores * ks
            scores = scores * scale                          # [P, KV, 1]
            m_old = m_s[r]                                   # [KV, 1]
            row_max = jnp.max(jnp.where(msk, scores, NEG_INF), axis=0)
            m_new = jnp.maximum(m_old, row_max)
            p = jnp.where(msk, jnp.exp(scores - m_new[None]), 0.0)
            corr = jnp.exp(m_old - m_new)
            l_s[r] = l_s[r] * corr + jnp.sum(p, axis=0)
            if quantised:
                p = p * vs
            for e in range(E):
                acc_s[r, e] = acc_s[r, e] * corr + jnp.sum(
                    p * vparts[e], axis=0)
            m_s[r] = m_new

    @pl.when(i == bps - 1)
    def _flush():
        o_ref[0, 0] = acc_s[...]
        m_ref[0, 0] = m_s[...]
        l_ref[0, 0] = l_s[...]


@functools.partial(
    jax.jit,
    static_argnames=("window", "n_splits", "interpret", "kv_dtype"),
)
def flash_decode(
    q: jnp.ndarray,            # [B, KV, rep, hd]
    k_pages: jnp.ndarray,      # [n_pages, P, KV, hd | hd/2 codes]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, MB] int32
    lengths: jnp.ndarray,      # [B] int32 (valid tokens = pos + 1)
    *,
    window: Optional[int] = None,
    n_splits: int = 4,
    interpret: Optional[bool] = None,
    k_scales: Optional[jnp.ndarray] = None,   # [n_pages, P, KV]
    v_scales: Optional[jnp.ndarray] = None,
    kv_dtype: str = "fp",
) -> jnp.ndarray:
    """Split-K paged flash decode; returns ``[B, KV, rep, hd]`` f32."""
    B, KV, rep, hd = q.shape
    _, P, _, hdc = k_pages.shape
    MB = block_table.shape[1]
    n_splits = max(1, min(n_splits, MB))
    bps = -(-MB // n_splits)   # blocks per split
    if kv_dtype != "fp" and (k_scales is None or v_scales is None):
        raise ValueError(f"kv_dtype {kv_dtype!r} needs k_scales/v_scales")
    E = hd // hdc              # 2 for packed int4: even/odd query halves

    bt = block_table.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    # [B, rep, E, KV, hdc]: head-dim element 2j+e of int4 lands in half e
    qs = q.reshape(B, KV, rep, hdc, E).transpose(0, 2, 4, 1, 3)

    def kv_index(b, s, i, bt_ref, len_ref):
        blk = s * bps + i
        valid = blk * P < len_ref[b]
        pid = jnp.where(valid, bt_ref[b, jnp.minimum(blk, MB - 1)], 0)
        return (pid, 0, 0, 0)

    def scale_index(b, s, i, bt_ref, len_ref):
        # the scale sidecar pages through the block table exactly like
        # its codes (same page id, one [P, KV] block per page)
        return kv_index(b, s, i, bt_ref, len_ref)[:3]

    in_specs = [
        pl.BlockSpec((1, rep, E, KV, hdc),
                     lambda b, s, i, *_: (b, 0, 0, 0, 0)),
        pl.BlockSpec((1, P, KV, hdc), kv_index),
        pl.BlockSpec((1, P, KV, hdc), kv_index),
    ]
    operands = [qs, k_pages, v_pages]
    if kv_dtype != "fp":
        in_specs += [
            pl.BlockSpec((1, P, KV), scale_index),
            pl.BlockSpec((1, P, KV), scale_index),
        ]
        operands += [k_scales, v_scales]

    stat_spec = pl.BlockSpec((1, 1, rep, KV, 1),
                             lambda b, s, i, *_: (b, s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_splits, bps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, rep, E, KV, hdc),
                         lambda b, s, i, *_: (b, s, 0, 0, 0, 0)),
            stat_spec,
            stat_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, E, KV, hdc), jnp.float32),
            pltpu.VMEM((rep, KV, 1), jnp.float32),
            pltpu.VMEM((rep, KV, 1), jnp.float32),
        ],
    )
    stat = jax.ShapeDtypeStruct((B, n_splits, rep, KV, 1), jnp.float32)
    o_p, m_p, l_p = pl.pallas_call(
        functools.partial(_kernel, P=P, bps=bps, window=window,
                          kv_dtype=kv_dtype),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_splits, rep, E, KV, hdc),
                                 jnp.float32),
            stat, stat,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(bt, lens, *operands)

    # combine split partials (FlashDecoding reduction); empty splits
    # carry (acc=0, m=NEG_INF, l=0) and contribute exact zeros
    m = m_p[..., 0]                                      # [B,S,rep,KV]
    l = l_p[..., 0]
    m_tot = jnp.max(m, axis=1)                           # [B,rep,KV]
    w = jnp.exp(m - m_tot[:, None])
    l_tot = jnp.sum(l * w, axis=1)
    o = jnp.sum(o_p * w[:, :, :, None, :, None], axis=1)  # [B,rep,E,KV,hdc]
    o = o / jnp.maximum(l_tot, 1e-30)[:, :, None, :, None]
    return o.transpose(0, 3, 1, 4, 2).reshape(B, KV, rep, hd)
