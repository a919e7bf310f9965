"""Fused revisit-hoisted Pallas TLMAC megakernel.

Improvements over ``tlmac_gemm`` (the PR's tentpole, see DESIGN.md §2):

1. **Fused bit-plane packing.**  ``tlmac_gemm`` consumes pre-packed
   ``codes [B_a, M, KG]`` which ``ops.tlmac_matmul`` recomputes with
   ``pack_bitplanes_ref`` on every call.  This kernel takes the raw
   activation codes ``a [M, K]`` and derives the per-plane G-bit group
   codes in-register (VPU shifts/masks) right before the MXU contraction
   — one HBM read of the activations, no [B_a, M, KG] intermediate.

2. **Revisit hoisting.**  The gathered/expanded table operand ``rhs``
   depends only on the (output-tile, k-block) grid coordinates, but the
   original kernel recomputed it for every M-block revisit.  Here the
   grid stays ``(n_tiles, M/bm, KG/bk)`` with k innermost — output-tile
   revisits remain *consecutive*, the only accumulation pattern that is
   safe on real TPU, where an output block is only held in VMEM across
   back-to-back visits — and the rhs for **all** k-blocks of the
   current tile is staged into VMEM scratch during the first M pass
   (``mi == 0``), then reused by every later M block: gather work drops
   from ``n_tiles * n_m * n_k`` to ``n_tiles * n_k`` table expansions.
   When the staging buffer would exceed the VMEM budget (large K), the
   kernel degrades to per-visit recompute — never to wrong results.

3. **Pipeline parallelism.**  ``dimension_semantics=('parallel',
   'arbitrary', 'arbitrary')`` tells Mosaic the output-tile axis carries
   no cross-iteration state, so independent tiles can overlap their
   prologue DMA with compute.  (The m and k axes stay 'arbitrary': m
   reuses the hoisted scratch, k accumulates into the output.)

Both gather variants of the original kernel are kept ('take' = dynamic
VMEM row gather, 'onehot' = MXU-only addressing).  Bit-exact in int32
against ``ref.tlmac_matmul_ref``; blocks are padded so M and K need not
be multiples of ``bm``/``bk*G`` (padded k-groups address a zero table
row and contribute nothing).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

# staging budget for the hoisted rhs scratch [nk, bk*C, dp] f32; above
# this the kernel recomputes rhs per visit instead (correct, just slower)
HOIST_VMEM_BYTES = 6 * 1024 * 1024


def rowbase_from_plan(table, exec_idx, step_cluster, n_tiles: int, kg: int):
    """Flatten (mapping-memory select, switch select) into table rows:
    rowbase[nt, k, p] = step_cluster[s] * N_arr + exec_idx[s, p] with
    s = nt * kg + k.  Shared by every non-ref impl."""
    n_arr = table.shape[1]
    rb = (
        step_cluster.astype(jnp.int32)[:, None] * n_arr
        + exec_idx.astype(jnp.int32)
    )
    return rb.reshape(n_tiles, kg, exec_idx.shape[1])


def _expand_rhs(rb, table, C: int, gather: str):
    """[bk, dp] table rows -> contraction operand [bk*C, dp]."""
    bk, dp = rb.shape
    R = table.shape[0]
    if gather == "take":
        t_cols = jnp.take(table, rb.reshape(-1), axis=0)      # [bk*dp, C]
    else:  # 'onehot': MXU-only addressing
        oh = rb.reshape(-1, 1) == jax.lax.iota(jnp.int32, R)[None, :]
        t_cols = jax.lax.dot(
            oh.astype(jnp.float32),
            table.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    return (
        t_cols.reshape(bk, dp, C)
        .astype(jnp.float32)
        .transpose(0, 2, 1)
        .reshape(bk * C, dp)
    )


def _kernel(
    a_ref,          # [bm, bk*G] int32  raw activation codes (unpacked)
    rowbase_ref,    # [1, bk, dp] int32 table row per (step, output)
    table_ref,      # [R, C]      int32 VMEM-resident MAC table
    out_ref,        # [bm, 1, dp] int32
    rhs_ref,        # VMEM scratch [nk|1, bk*C, dp] f32 — hoisted rhs
    *,
    B_a: int,
    G: int,
    C: int,
    gather: str,
    hoist: bool,
):
    mi = pl.program_id(1)
    ki = pl.program_id(2)

    if hoist:
        # rhs depends on (nt, ki) only; k is innermost so the first M
        # pass (mi == 0) visits every ki once and stages all of them —
        # later M blocks reuse the scratch without touching the table
        @pl.when(mi == 0)
        def _stage():
            rhs_ref[ki] = _expand_rhs(
                rowbase_ref[0], table_ref[...], C, gather
            )
        rhs = rhs_ref[ki]
    else:
        # staging buffer over budget: recompute per visit (original
        # behavior) — correctness never depends on the hoist
        rhs = _expand_rhs(rowbase_ref[0], table_ref[...], C, gather)

    @pl.when(ki == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]                                            # [bm, bk*G]
    bm = a.shape[0]
    bk, dp = rowbase_ref.shape[1], rowbase_ref.shape[2]
    acc = jnp.zeros((bm, dp), dtype=jnp.float32)
    iota_c = jax.lax.iota(jnp.int32, C)
    for b in range(B_a):                                      # static: unrolled
        # fused Eq. 3 packing: code_b[m, j] = sum_g bit_b(a[m, j*G+g]) << g
        code = jnp.zeros((bm, bk), dtype=jnp.int32)
        for g in range(G):
            code = code | (((a[:, g::G] >> b) & 1) << g)
        sel = (code[:, :, None] == iota_c[None, None, :]).astype(jnp.float32)
        # MXU: [bm, bk*C] @ [bk*C, dp]; f32 exact at these magnitudes
        # (|T| <= G*2^(B_w-1) <= 48, partial sums << 2^24)
        acc = acc + jax.lax.dot(
            sel.reshape(bm, bk * C), rhs, preferred_element_type=jnp.float32
        ) * float(1 << b)

    # k is innermost: (mi, nt) revisits are consecutive, accumulation in
    # the resident output block is TPU-safe (same pattern as tlmac_gemm)
    out_ref[...] += acc.astype(jnp.int32)[:, None, :]


@functools.partial(
    jax.jit,
    static_argnames=("B_a", "G", "N", "bm", "bk", "gather", "interpret",
                     "hoist_vmem_bytes"),
)
def tlmac_gemm_fused(
    a_codes: jnp.ndarray,      # [M, K] int activation codes (B_a bits)
    rowbase: jnp.ndarray,      # [n_tiles, KG, D_p] int32
    table2d: jnp.ndarray,      # [R, C] int32
    *,
    B_a: int,
    G: int,
    N: int,
    bm: int = 128,
    bk: int = 128,
    gather: str = "take",
    interpret: Optional[bool] = None,
    hoist_vmem_bytes: int = HOIST_VMEM_BYTES,
) -> jnp.ndarray:
    """Fused pack+lookup GEMM. Returns int32 [M, N]."""
    M, K = a_codes.shape
    n_tiles, KG, D_p = rowbase.shape
    assert K == KG * G and n_tiles * D_p == N
    C = table2d.shape[-1]
    assert C == 2**G

    a = a_codes.astype(jnp.int32)
    bm = min(bm, M)
    bk = min(bk, KG)
    pad_m = (-M) % bm
    pad_k = (-KG) % bk
    if pad_k:
        # zero activation codes + a zero table row: padding contributes 0
        a = jnp.pad(a, ((0, 0), (0, pad_k * G)))
        R = table2d.shape[0]
        table2d = jnp.pad(table2d, ((0, 1), (0, 0)))
        rowbase = jnp.pad(
            rowbase, ((0, 0), (0, pad_k), (0, 0)), constant_values=R
        )
    if pad_m:
        a = jnp.pad(a, ((0, pad_m), (0, 0)))
    Mp, KGp = M + pad_m, KG + pad_k

    nk = KGp // bk
    hoist = nk * bk * C * D_p * 4 <= hoist_vmem_bytes
    grid = (n_tiles, Mp // bm, nk)
    out = pl.pallas_call(
        functools.partial(
            _kernel, B_a=B_a, G=G, C=C, gather=gather, hoist=hoist
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk * G), lambda nt, mi, ki: (mi, ki)),
            pl.BlockSpec((1, bk, D_p), lambda nt, mi, ki: (nt, ki, 0)),
            pl.BlockSpec(table2d.shape, lambda nt, mi, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1, D_p), lambda nt, mi, ki: (mi, nt, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, n_tiles, D_p), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((nk if hoist else 1, bk * C, D_p), jnp.float32)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=resolve_interpret(interpret),
    )(a, rowbase, table2d)
    return out.reshape(Mp, N)[:M]


def tlmac_matmul_fused(
    a_codes: jnp.ndarray,
    table: jnp.ndarray,
    exec_idx: jnp.ndarray,
    step_cluster: jnp.ndarray,
    *,
    B_a: int,
    G: int,
    N: int,
    bm: int = 128,
    bk: int = 128,
    gather: str = "take",
    interpret: Optional[bool] = None,
    hoist_vmem_bytes: int = HOIST_VMEM_BYTES,
) -> jnp.ndarray:
    """Plan-level wrapper: build rowbase, run the fused megakernel."""
    M, K = a_codes.shape
    kg = K // G
    n_tiles = N // exec_idx.shape[1]
    rowbase = rowbase_from_plan(table, exec_idx, step_cluster, n_tiles, kg)
    return tlmac_gemm_fused(
        a_codes, rowbase, table.reshape(-1, 2**G),
        B_a=B_a, G=G, N=N, bm=bm, bk=bk, gather=gather, interpret=interpret,
        hoist_vmem_bytes=hoist_vmem_bytes,
    )
