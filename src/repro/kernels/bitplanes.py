"""Pallas kernel: activation bit-plane packing (paper Eq. 3, serial step).

Turns B_a-bit activation codes [M, K] into per-plane G-bit group codes
[B_a, M, K/G] — the values presented to the LUT-array inputs at each
bit-serial iteration.  Pure VPU work (shifts/masks), blocked over M with
full-K rows so the strided group gather stays static.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def _kernel(a_ref, out_ref, *, B_a: int, G: int):
    a = a_ref[...]                      # [bm, K] int32
    bm, K = a.shape
    kg = K // G
    # code_b[m, j] = sum_g bit_b(a[m, j*G + g]) << g  — static strided slices
    for b in range(B_a):
        acc = jnp.zeros((bm, kg), dtype=jnp.int32)
        for g in range(G):
            bits = (a[:, g::G] >> b) & 1
            acc = acc | (bits << g)
        out_ref[b] = acc


@functools.partial(jax.jit, static_argnames=("B_a", "G", "bm", "interpret"))
def pack_bitplanes_pallas(
    a_codes: jnp.ndarray, *, B_a: int, G: int, bm: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    M, K = a_codes.shape
    assert K % G == 0
    bm = min(bm, M)
    pad_m = (-M) % bm
    a = jnp.pad(a_codes.astype(jnp.int32), ((0, pad_m), (0, 0)))
    Mp = M + pad_m
    out = pl.pallas_call(
        functools.partial(_kernel, B_a=B_a, G=G),
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda mi: (mi, 0))],
        out_specs=pl.BlockSpec((B_a, bm, K // G), lambda mi: (0, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((B_a, Mp, K // G), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a)
    return out[:, :M]
