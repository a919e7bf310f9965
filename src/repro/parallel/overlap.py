"""Compute/communication overlap: collective matmul (ring all-gather).

Standard TP computes ``y = x @ W`` with ``x`` sequence/batch-sharded by
first all-gathering ``x`` (exposed latency), then the matmul.  The
*collective matmul* overlaps the two: each ring step multiplies the
shard currently held while ``ppermute`` forwards it to the next
neighbour — after n-1 steps every device has accumulated the full
product without a standalone all-gather on the critical path.

This is the latency-hiding trick used for TP projections where the
gather would otherwise stall the MXU (DESIGN.md §5).  Expressed with
``shard_map`` so the schedule is explicit rather than left to GSPMD.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ring_ag_matmul(x, w, mesh: Mesh, axis: str = "model"):
    """y = allgather(x, axis) @ w, overlapped via a ppermute ring.

    x: [M_shard, K] sharded on ``axis`` along M (sequence-parallel
       boundary layout); w: [K, N] replicated along ``axis``.
    Returns y: [M_full, N] replicated on ``axis``.

    Each ring step contributes one shard's rows of the output while the
    next shard is in flight — on real hardware the ppermute DMA and the
    dot overlap; the dry-run proves the schedule lowers with exactly
    n-1 collective-permutes and no all-gather.
    """
    n = mesh.shape[axis]

    def body(x_blk, w_full):
        idx = jax.lax.axis_index(axis)

        def step(i, carry):
            blk, out = carry
            # rows owned by the device this block came from
            src = (idx - i) % n
            out = jax.lax.dynamic_update_slice_in_dim(
                out, jnp.dot(blk, w_full, preferred_element_type=out.dtype),
                src * blk.shape[0], axis=0,
            )
            blk = jax.lax.ppermute(
                blk, axis, [(j, (j + 1) % n) for j in range(n)]
            )
            return blk, out

        out0 = jnp.zeros((x_blk.shape[0] * n, w_full.shape[1]), jnp.float32)
        _, out = jax.lax.fori_loop(0, n, step, (x_blk.astype(jnp.float32), out0))
        return out

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    return fn(x, w)


def ring_rs_matmul(x, w, mesh: Mesh, axis: str = "model"):
    """Reduce-scatter fused matmul (Megatron 'g' partner of the 'f'
    all-gather above): w is K-sharded, partial products need a cross-
    device reduction, and the result lands row-scattered.

    x: [M, K] replicated; w: [K, N] sharded along K on ``axis``.
    Returns y: [M, N] == x @ w, physically reduce-scattered over M
    (reassembled by the out_spec).  The ring accumulates each output
    row-slice while rotating it home — reduction overlaps the dots.
    """
    n = mesh.shape[axis]

    def body(x_full, w_blk):
        idx = jax.lax.axis_index(axis)
        M = x_full.shape[0]
        m_shard = M // n
        k_shard = w_blk.shape[0]
        x_j = jax.lax.dynamic_slice_in_dim(
            x_full, idx * k_shard, k_shard, 1
        )  # this device's K slice [M, K/n]

        def step(i, acc):
            # the accumulator rotates one hop per step; computing slice
            # (idx - i - 1) keeps each accumulator pinned to ONE output
            # row-slice, which lands on its owner after n steps
            src = (idx - i - 1) % n
            part = jnp.dot(
                jax.lax.dynamic_slice_in_dim(x_j, src * m_shard, m_shard, 0),
                w_blk, preferred_element_type=jnp.float32,
            )
            acc = jax.lax.ppermute(
                acc, axis, [(j, (j + 1) % n) for j in range(n)]
            )
            return acc + part

        acc0 = jnp.zeros((m_shard, w_blk.shape[1]), jnp.float32)
        return jax.lax.fori_loop(0, n, step, acc0)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(axis, None)),
        out_specs=P(axis, None),
        check_vma=False,
    )
    return fn(x, w)
