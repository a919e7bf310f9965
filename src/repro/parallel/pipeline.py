"""GPipe-style pipeline parallelism over the 'pod' axis (optional
feature; the default meshes use pod as outer DP — see DESIGN.md §5).

``pipeline_apply`` runs S stages over M microbatches with the classic
(S + M - 1)-slot schedule expressed as a lax.scan over slots: at each
slot every stage processes the microbatch it holds and hands its output
to the next stage via ``ppermute``.  Bubble fraction = (S-1)/(S+M-1);
tests verify both the numerics (== sequential apply) and the schedule
length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, params_stacked, x_microbatches, mesh: Mesh,
                   axis: str = "pod"):
    """Run ``stage_fn(stage_params, x)`` as a pipeline over ``axis``.

    params_stacked: pytree with leading dim = n_stages (sharded on axis)
    x_microbatches: [M, mb, ...] microbatches (replicated)
    Returns [M, mb, ...] outputs after all stages.
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]
    n_slots = S + M - 1

    def body(stage_params, xs):
        sid = jax.lax.axis_index(axis)
        # in_specs P(axis) leaves a leading per-device stage dim of 1
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        mb_shape = xs.shape[1:]

        def slot(carry, t):
            held, outs = carry
            # stage 0 ingests microbatch t (if any left)
            fresh = jnp.where(
                t < M,
                jax.lax.dynamic_index_in_dim(
                    xs, jnp.minimum(t, M - 1), 0, keepdims=False
                ),
                jnp.zeros(mb_shape, xs.dtype),
            )
            inp = jnp.where(sid == 0, fresh, held)
            out = stage_fn(stage_params, inp)
            # pass to the next stage; last stage's output is collected
            held_next = jax.lax.ppermute(
                out, axis, [(j, j + 1) for j in range(S - 1)]
            )
            # stage S-1 finished microbatch (t - (S-1)) at this slot
            done_idx = t - (S - 1)
            outs = jnp.where(
                (sid == S - 1) & (done_idx >= 0),
                jax.lax.dynamic_update_index_in_dim(
                    outs, out, jnp.maximum(done_idx, 0), 0
                ),
                outs,
            )
            return (held_next, outs), None

        outs0 = jnp.zeros((M, *mb_shape), xs.dtype)
        held0 = jnp.zeros(mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(
            slot, (held0, outs0), jnp.arange(n_slots)
        )
        # replicate the last stage's collected outputs to all stages
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(None),
        check_vma=False,
    )
    return fn(params_stacked, x_microbatches)
