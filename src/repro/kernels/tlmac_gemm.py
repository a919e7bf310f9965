"""Pallas TPU kernel: table-lookup GEMM (the TLMAC PE, DESIGN.md §2).

Computes, bit-exactly in int32,

    out[m, n] = sum_b 2^b * sum_kg  T2D[ rowbase[nt, kg, p], code_b[m, kg] ]

where ``rowbase = step_cluster * N_arr + exec_idx`` flattens the paper's
(mapping-memory select, switch select) pair into a row of the 2-D MAC
table ``T2D [N_clus*N_arr, 2^G]``.

TPU mapping (per DESIGN.md):
- The MAC table is small (<= N_clus * N_arr * 2^G ints) and stays
  **resident in VMEM** across the whole grid — the analogue of weights
  living in LUT truth tables instead of DRAM.
- Activation bit-planes are one-hot expanded in-register and contracted
  against gathered table columns on the **MXU** (the paper's LUT read +
  switch select become a gather + one-hot matmul).
- HBM traffic: ``codes`` (B_a planes of G-bit group codes) + ``rowbase``
  (one small int per weight *group*, i.e. log2(N_arr)/G bits per weight)
  — never the full-width weights.

Grid: (n_tiles, M/bm, KG/bk), k innermost so each out tile is revisited
consecutively and accumulated in int32.

Two gather variants:
- 'take'   : dynamic row gather from the VMEM table (jnp.take).
- 'onehot' : one-hot(rowbase) @ T2D on the MXU — no dynamic addressing at
             all; preferable when N_clus*N_arr is modest (clustering keeps
             it so: that is exactly what §5.1 is for).

Validated in interpret mode against ``ref.tlmac_matmul_ref`` (bit-exact);
block shapes are chosen so the working set fits v5e VMEM (~16 MiB) and
the MXU contraction dims are multiples of 128 where possible.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def _kernel(
    codes_ref,      # [B_a, bm, bk] int32   activation bit-plane group codes
    rowbase_ref,    # [1, bk, dp]   int32   table row per (step, output)
    table_ref,      # [R, C]        int32   VMEM-resident MAC table
    out_ref,        # [bm, 1, dp]   int32
    *,
    B_a: int,
    C: int,
    gather: str,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rb = rowbase_ref[0]                      # [bk, dp]
    bk, dp = rb.shape
    table = table_ref[...]                   # [R, C]
    R = table.shape[0]

    if gather == "take":
        t_cols = jnp.take(table, rb.reshape(-1), axis=0)          # [bk*dp, C]
    else:  # 'onehot': MXU-only addressing
        oh = (rb.reshape(-1, 1) == jax.lax.iota(jnp.int32, R)[None, :])
        t_cols = jax.lax.dot(
            oh.astype(jnp.float32),
            table.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )                                                          # [bk*dp, C]
    # [bk, dp, C] -> contraction layout [bk*C, dp]
    t_cols = t_cols.reshape(bk, dp, C).astype(jnp.float32)
    rhs = t_cols.transpose(0, 2, 1).reshape(bk * C, dp)

    bm = codes_ref.shape[1]
    acc = jnp.zeros((bm, dp), dtype=jnp.float32)
    iota_c = jax.lax.iota(jnp.int32, C)
    for b in range(B_a):                      # B_a is static: unrolled
        code = codes_ref[b]                   # [bm, bk]
        sel = (code[:, :, None] == iota_c[None, None, :]).astype(jnp.float32)
        lhs = sel.reshape(bm, bk * C)
        # MXU: [bm, bk*C] @ [bk*C, dp]; f32 is exact for these magnitudes
        # (|T| <= G*2^(B_w-1) <= 48, bk*C partial sums << 2^24).
        acc = acc + jax.lax.dot(
            lhs, rhs, preferred_element_type=jnp.float32
        ) * float(1 << b)

    out_ref[...] += acc.astype(jnp.int32)[:, None, :]


@functools.partial(
    jax.jit,
    static_argnames=("B_a", "G", "N", "bm", "bk", "gather", "interpret"),
)
def tlmac_gemm(
    codes: jnp.ndarray,        # [B_a, M, KG] int32 (from pack_bitplanes)
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
) -> jnp.ndarray:
    """Blocked Pallas lookup GEMM. Returns int32 [M, N]."""
    _, M, KG = codes.shape
    n_tiles, KG2, D_p = rowbase.shape
    assert KG == KG2 and n_tiles * D_p == N
    C = table2d.shape[-1]
    assert C == 2**G

    bm = min(bm, M)
    bk = min(bk, KG)
    # pad M and KG to block multiples; padded k-groups point at a zero row
    pad_m = (-M) % bm
    pad_k = (-KG) % bk
    if pad_k:
        codes = jnp.pad(codes, ((0, 0), (0, 0), (0, pad_k)))
        R = table2d.shape[0]
        table2d = jnp.pad(table2d, ((0, 1), (0, 0)))  # zero row at R
        rowbase = jnp.pad(
            rowbase, ((0, 0), (0, pad_k), (0, 0)), constant_values=R
        )
    if pad_m:
        codes = jnp.pad(codes, ((0, 0), (0, pad_m), (0, 0)))
    Mp, KGp = M + pad_m, KG + pad_k

    grid = (n_tiles, Mp // bm, KGp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, B_a=B_a, C=C, gather=gather),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B_a, bm, bk), lambda nt, mi, ki: (0, mi, ki)),
            pl.BlockSpec((1, bk, D_p), lambda nt, mi, ki: (nt, ki, 0)),
            pl.BlockSpec(table2d.shape, lambda nt, mi, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1, D_p), lambda nt, mi, ki: (mi, nt, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, n_tiles, D_p), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(codes, rowbase, table2d)
    return out.reshape(Mp, N)[:M]
