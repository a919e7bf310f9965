"""Pallas TPU kernel #2: cluster-scheduled lookup GEMM.

This is the direct TPU mapping of the paper's PE control structure
(DESIGN.md §2 table):

  FPGA                                TPU (this kernel)
  ------------------------------      --------------------------------
  mapping memory: step -> select s    steps re-ordered by cluster at
                                      compile time; the grid's cluster
                                      coordinate IS the select signal
  LUT array select s picks the        BlockSpec index_map streams ONLY
  truth-table slice                   cluster c's table slice [N_arr,2^G]
                                      into VMEM for grid step c
  switches (mux per output)           one-hot(exec_idx < N_arr) @ T_c
                                      on the MXU — no dynamic gather at
                                      all, N_arr bounded by clustering

Because each grid step touches one cluster's table slice only, the VMEM
working set is N_arr x 2^G ints instead of the whole codebook — which is
exactly why §5.1 minimises N_arr.  The kernel processes one output tile
(N == D_p) per call; the ops wrapper loops tiles.

Host-side ``cluster_schedule`` turns a compiled TLMACLayerPlan into the
padded, cluster-sorted operand layout; ``tlmac_gemm_clustered`` is
validated bit-exactly against the dense integer GEMM in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def cluster_schedule(plan, bk: int = 8):
    """Reorder a plan's steps by cluster and pad each cluster to a
    multiple of ``bk`` k-steps.

    Returns dict with:
      order      [n_clus, ms]      original step ids (-1 padding)
      idx_sorted [n_clus, ms, D_p] within-cluster LUT-array ids
                                   (N_arr on padding slots)
      table_pad  [n_clus, N_arr+1, 2^G]  per-cluster tables + zero row
      ms         padded steps per cluster
    """
    n_clus, n_arr, C = plan.table.shape
    D_s, D_p = plan.exec_idx.shape
    per = [np.nonzero(plan.step_cluster == c)[0] for c in range(n_clus)]
    ms = max((len(p) for p in per), default=1)
    ms = -(-ms // bk) * bk
    order = np.full((n_clus, ms), -1, np.int32)
    idx_sorted = np.full((n_clus, ms, D_p), n_arr, np.int32)  # pad -> zero row
    for c, steps in enumerate(per):
        order[c, : len(steps)] = steps
        idx_sorted[c, : len(steps)] = plan.exec_idx[steps]
    table_pad = np.concatenate(
        [plan.table, np.zeros((n_clus, 1, C), np.int32)], axis=1
    )
    return {"order": order, "idx_sorted": idx_sorted,
            "table_pad": table_pad, "ms": ms}


def _kernel(codes_ref, idx_ref, table_ref, out_ref, *, B_a, C, n_arr1):
    ci = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when((ci == 0) & (ki == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tbl = table_ref[0]                                   # [N_arr+1, C]
    idx = idx_ref[0]                                     # [bk, D_p]
    bk, D_p = idx.shape
    # switches: one-hot over the (clustering-bounded) array count — pure
    # MXU addressing, the whole point of keeping N_arr small
    oh = (idx.reshape(-1, 1) == jax.lax.iota(jnp.int32, n_arr1)[None, :])
    t_cols = jax.lax.dot(
        oh.astype(jnp.float32), tbl.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).reshape(bk, D_p, C)
    rhs = t_cols.transpose(0, 2, 1).reshape(bk * C, D_p)

    bm = codes_ref.shape[1]
    acc = jnp.zeros((bm, D_p), jnp.float32)
    iota_c = jax.lax.iota(jnp.int32, C)
    for b in range(B_a):
        code = codes_ref[b]                              # [bm, bk]
        sel = (code[:, :, None] == iota_c[None, None, :]).astype(jnp.float32)
        acc = acc + jax.lax.dot(
            sel.reshape(bm, bk * C), rhs,
            preferred_element_type=jnp.float32,
        ) * float(1 << b)
    out_ref[...] += acc.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("B_a", "G", "bm", "bk", "interpret"),
)
def tlmac_gemm_clustered(
    codes_sorted: jnp.ndarray,   # [B_a, M, n_clus*ms] int32, cluster-sorted
    idx_sorted: jnp.ndarray,     # [n_clus, ms, D_p] int32 (N_arr = padding)
    table_pad: jnp.ndarray,      # [n_clus, N_arr+1, 2^G] int32
    *,
    B_a: int,
    G: int,
    bm: int = 128,
    bk: int = 8,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """One-output-tile clustered lookup GEMM -> int32 [M, D_p]."""
    n_clus, ms, D_p = idx_sorted.shape
    _, M, tot = codes_sorted.shape
    assert tot == n_clus * ms and ms % bk == 0
    C = 2**G
    n_arr1 = table_pad.shape[1]

    bm = min(bm, M)
    pad_m = (-M) % bm
    if pad_m:
        codes_sorted = jnp.pad(codes_sorted, ((0, 0), (0, pad_m), (0, 0)))
    Mp = M + pad_m

    grid = (Mp // bm, n_clus, ms // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, B_a=B_a, C=C, n_arr1=n_arr1),
        grid=grid,
        in_specs=[
            # codes laid out [B_a, M, n_clus*ms]: block (c, ki) picks the
            # cluster-c k-slice — the grid coordinate is the paper's
            # select signal
            pl.BlockSpec(
                (B_a, bm, bk),
                lambda mi, c, ki: (0, mi, c * (ms // bk) + ki),
            ),
            pl.BlockSpec((1, bk, D_p), lambda mi, c, ki: (c, ki, 0)),
            # ONLY cluster c's table slice enters VMEM at grid step c
            pl.BlockSpec((1, n_arr1, C), lambda mi, c, ki: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, D_p), lambda mi, c, ki: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, D_p), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(codes_sorted, idx_sorted, table_pad)
    return out[:M]


def run_clustered(plan, a_codes, B_a: int, bk: int = 8, bm: int = 128):
    """Host wrapper: schedule a plan, sort the activation codes, run the
    kernel. a_codes [M, K] -> int32 [M, N] (single-output-tile plans)."""
    from repro.kernels import ref as kref

    sched = cluster_schedule(plan, bk=bk)
    G = plan.G
    codes = kref.pack_bitplanes_ref(jnp.asarray(a_codes), B_a, G)  # [B_a,M,kg]
    order = sched["order"]                        # [n_clus, ms]
    # gather codes into cluster order; padding slots point at step 0 but
    # their idx rows select the zero table row, so they contribute 0
    safe = np.where(order >= 0, order, 0)
    codes_sorted = jnp.take(codes, jnp.asarray(safe.reshape(-1)), axis=2)
    out = tlmac_gemm_clustered(
        codes_sorted.astype(jnp.int32),
        jnp.asarray(sched["idx_sorted"]),
        jnp.asarray(sched["table_pad"]),
        B_a=B_a, G=G, bm=bm, bk=bk,
    )
    return out


# ---------------------------------------------------------------------------
# Multi-output-tile clustered kernel: whole layer in ONE pallas_call
# ---------------------------------------------------------------------------


def cluster_schedule_tiled(plan, n_tiles: int, bk: int = 8):
    """Per-(output-tile, cluster) schedule for multi-tile plans.

    The single-tile kernel above needs a host loop over output tiles
    (one ``pallas_call`` each — per-call dispatch and no cross-tile
    pipelining).  This schedule re-orders every tile's steps by cluster
    and pads each (tile, cluster) run to a common multiple-of-``bk``
    length ``ms`` so one 4-D grid covers the whole layer.

    Returns dict with:
      order      [n_tiles, n_clus, ms]       original step ids (-1 pad)
      idx_sorted [n_tiles, n_clus, ms, D_p]  within-cluster array ids
                                             (N_arr on padding slots)
      table_pad  [n_clus, N_arr+1, 2^G]      per-cluster tables + zero row
      ms         padded steps per (tile, cluster)
    """
    n_clus, n_arr, C = plan.table.shape
    D_s, D_p = plan.exec_idx.shape
    assert D_s % n_tiles == 0
    kg = D_s // n_tiles
    per = [
        [np.nonzero(plan.step_cluster[nt * kg:(nt + 1) * kg] == c)[0] + nt * kg
         for c in range(n_clus)]
        for nt in range(n_tiles)
    ]
    ms = max((len(s) for tile in per for s in tile), default=1)
    ms = -(-ms // bk) * bk
    order = np.full((n_tiles, n_clus, ms), -1, np.int32)
    idx_sorted = np.full((n_tiles, n_clus, ms, D_p), n_arr, np.int32)
    for nt in range(n_tiles):
        for c, steps in enumerate(per[nt]):
            order[nt, c, : len(steps)] = steps
            idx_sorted[nt, c, : len(steps)] = plan.exec_idx[steps]
    table_pad = np.concatenate(
        [plan.table, np.zeros((n_clus, 1, C), np.int32)], axis=1
    )
    return {"order": order, "idx_sorted": idx_sorted,
            "table_pad": table_pad, "ms": ms}


def _kernel_multi(codes_ref, idx_ref, table_ref, out_ref, *, B_a, C, n_arr1):
    ci = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when((ci == 0) & (ki == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tbl = table_ref[0]                                   # [N_arr+1, C]
    idx = idx_ref[0, 0]                                  # [bk, D_p]
    bk, D_p = idx.shape
    oh = (idx.reshape(-1, 1) == jax.lax.iota(jnp.int32, n_arr1)[None, :])
    t_cols = jax.lax.dot(
        oh.astype(jnp.float32), tbl.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).reshape(bk, D_p, C)
    rhs = t_cols.transpose(0, 2, 1).reshape(bk * C, D_p)

    bm = codes_ref.shape[1]
    acc = jnp.zeros((bm, D_p), jnp.float32)
    iota_c = jax.lax.iota(jnp.int32, C)
    for b in range(B_a):
        code = codes_ref[b]                              # [bm, bk]
        sel = (code[:, :, None] == iota_c[None, None, :]).astype(jnp.float32)
        acc = acc + jax.lax.dot(
            sel.reshape(bm, bk * C), rhs,
            preferred_element_type=jnp.float32,
        ) * float(1 << b)
    out_ref[...] += acc.astype(jnp.int32)[:, None, :]


@functools.partial(
    jax.jit,
    static_argnames=("B_a", "G", "bm", "bk", "interpret"),
)
def tlmac_gemm_clustered_multi(
    codes_sorted: jnp.ndarray,   # [B_a, M, n_tiles*n_clus*ms] int32
    idx_sorted: jnp.ndarray,     # [n_tiles, n_clus, ms, D_p] int32
    table_pad: jnp.ndarray,      # [n_clus, N_arr+1, 2^G] int32
    *,
    B_a: int,
    G: int,
    bm: int = 128,
    bk: int = 8,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Whole-layer clustered lookup GEMM -> int32 [M, n_tiles*D_p].

    Grid (n_tiles, M/bm, n_clus, ms/bk): the (cluster) coordinate is
    still the paper's mapping-memory select signal — only cluster c's
    table slice sits in VMEM at grid step c — but every output tile of
    the layer now rides the same grid, so the host loop (and its
    per-call dispatch) is gone and tiles pipeline through the same
    table slices.
    """
    n_tiles, n_clus, ms, D_p = idx_sorted.shape
    _, M, tot = codes_sorted.shape
    assert tot == n_tiles * n_clus * ms and ms % bk == 0
    C = 2**G
    n_arr1 = table_pad.shape[1]

    bm = min(bm, M)
    pad_m = (-M) % bm
    if pad_m:
        codes_sorted = jnp.pad(codes_sorted, ((0, 0), (0, pad_m), (0, 0)))
    Mp = M + pad_m
    kpc = ms // bk                                        # k-blocks per cluster

    grid = (n_tiles, Mp // bm, n_clus, kpc)
    out = pl.pallas_call(
        functools.partial(_kernel_multi, B_a=B_a, C=C, n_arr1=n_arr1),
        grid=grid,
        in_specs=[
            # codes laid out [B_a, M, n_tiles*n_clus*ms]: block
            # (nt, c, ki) picks tile nt / cluster c's k-slice
            pl.BlockSpec(
                (B_a, bm, bk),
                lambda nt, mi, c, ki: (0, mi, (nt * n_clus + c) * kpc + ki),
            ),
            pl.BlockSpec((1, 1, bk, D_p), lambda nt, mi, c, ki: (nt, c, ki, 0)),
            # ONLY cluster c's table slice enters VMEM at grid step c
            pl.BlockSpec((1, n_arr1, C), lambda nt, mi, c, ki: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1, D_p), lambda nt, mi, c, ki: (mi, nt, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, n_tiles, D_p), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(codes_sorted, idx_sorted, table_pad)
    return out.reshape(Mp, n_tiles * D_p)[:M]


def run_clustered_multi(plan, a_codes, B_a: int, N: int, bk: int = 8,
                        bm: int = 128):
    """Host wrapper for multi-output-tile plans: schedule, sort codes,
    run the single fused pallas_call.  a_codes [M, K] -> int32 [M, N]."""
    from repro.kernels import ref as kref

    D_s, D_p = plan.exec_idx.shape
    n_tiles = N // D_p
    sched = cluster_schedule_tiled(plan, n_tiles, bk=bk)
    G = plan.G
    codes = kref.pack_bitplanes_ref(jnp.asarray(a_codes), B_a, G)  # [B_a,M,kg]
    kg = D_s // n_tiles
    order = sched["order"]                        # [n_tiles, n_clus, ms]
    # code column for step s is s % kg (codes are shared across tiles);
    # padding slots point at column 0 but their idx rows select the zero
    # table row, so they contribute 0
    safe = np.where(order >= 0, order % kg, 0)
    codes_sorted = jnp.take(codes, jnp.asarray(safe.reshape(-1)), axis=2)
    return tlmac_gemm_clustered_multi(
        codes_sorted.astype(jnp.int32),
        jnp.asarray(sched["idx_sorted"]),
        jnp.asarray(sched["table_pad"]),
        B_a=B_a, G=G, bm=bm, bk=bk,
    )
