"""Public jit'd kernel API with implementation dispatch.

``impl`` selects the execution path:
- 'ref'       : obvious jnp oracle (tests, tiny shapes)
- 'xla'       : memory-bounded XLA formulation — outer scan over
                N-tiles, inner loop over k-group chunks, gather + one-hot
                MXU contraction, dequant fused per tile.  The MoE serve
                linears (experts vmapped) lower this path.
- 'xla-kscan' : scan over k-chunks with a full [M, N] accumulator —
                keeps n_tiles a sharded tensor dim for TP layers.  The
                dense serve linears lower this path (the untuned
                default of ``impl='auto'`` there), on one chip and under
                a TP mesh.
- 'xla-flat'  : no scan at all — one gather + one one-hot GEMM per bit
                plane.  Fastest when the [kg*2^G, N] expanded table fits
                comfortably (small K or small N), pays full
                materialisation otherwise.
- 'pallas'    : the Pallas TPU kernel (interpreted off-TPU);
                gather='take'
- 'pallas-onehot' : Pallas kernel with MXU-only addressing
- 'fused'     : the fused revisit-hoisted Pallas megakernel
                (tlmac_fused.py): bit-plane packing fused in-kernel,
                table gather hoisted out of the M loop
- 'auto'      : shape-keyed autotuned dispatch (kernels/autotune.py).
                Inside jit it resolves from the persisted cache (pure
                host-side read at trace time) and falls back to
                ``auto_default`` on a miss; called eagerly on concrete
                arrays it tunes once and caches the winner.

All paths are bit-exact in int32 and are asserted equal in tests.

``codes=`` lets callers pass activations already packed with
``pack_bitplanes`` so one packing feeds many GEMMs (q/k/v, swiglu
wi/wg); the fused kernel instead consumes the *raw* codes and packs
in-register.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.bitplanes import pack_bitplanes_pallas
from repro.kernels.tlmac_fused import rowbase_from_plan, tlmac_matmul_fused
from repro.kernels.tlmac_gemm import tlmac_gemm


# resolved-'auto'-config memo, invalidated by autotune.generation bumps
_AUTO_MEMO: dict = {}


def auto_resolutions() -> list:
    """What ``impl='auto'`` resolved to in this process, one entry per
    lookup-GEMM shape: ``{"M", "K", "N", "config"}``."""
    return [{"M": k[0], "K": k[1], "N": k[2], "config": dict(v[1])}
            for k, v in _AUTO_MEMO.items()]


def dense_int_matmul(a_codes: jnp.ndarray, w_codes: jnp.ndarray) -> jnp.ndarray:
    """Dense int8-style GEMM baseline (what a non-lookup QNN would run)."""
    return _ref.dense_int_matmul_ref(a_codes, w_codes)


def bitserial_matmul(a_codes, w_codes, B_a: int) -> jnp.ndarray:
    """Ablation: Eq. 3 serialisation without the lookup (see ref.py)."""
    return _ref.bitserial_matmul_ref(a_codes, w_codes, B_a)


def pack_bitplanes(
    a_codes: jnp.ndarray, B_a: int, G: int, impl: str = "ref"
) -> jnp.ndarray:
    if impl == "pallas":
        return pack_bitplanes_pallas(a_codes, B_a=B_a, G=G)
    return _ref.pack_bitplanes_ref(a_codes, B_a, G)


# single source of truth for the (select, switch) -> table-row flattening
_rowbase = rowbase_from_plan


def _kscan_chunk(kg: int, chunk: int) -> int:
    """The k-chunk ``tlmac_matmul_xla_kscan`` scans by: ``chunk`` capped
    at kg where it divides kg, else the largest divisor of kg in
    [64, chunk], so no k-group is padded (kg 576 -> 192, 1440 -> 240).
    Where kg has no such divisor, ``chunk`` itself, and the caller pads."""
    chunk = min(chunk, kg)
    for d in range(chunk, 63, -1):
        if kg % d == 0:
            return d
    return chunk


@functools.partial(jax.jit, static_argnames=("B_a", "G", "N", "chunk"))
def tlmac_matmul_xla_kscan(
    a_codes: jnp.ndarray,
    table: jnp.ndarray,
    exec_idx: jnp.ndarray,
    step_cluster: jnp.ndarray,
    *,
    B_a: int,
    G: int,
    N: int,
    chunk: int = 256,
    codes: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Scan-over-k-chunks lookup GEMM (f32 [M, N] accumulator).

    Preferred for TP-sharded dense layers: the accumulator keeps n_tiles
    as a sharded tensor dim, so no resharding reshape at the end (the
    N-tile-scan variant pays an all-to-all there).  The f32 [M, N]
    buffer is acceptable per matmul at dense sizes; the expert-stacked
    case (E buffers at once under vmap) uses the N-tile variant.

    The weight-side expansion crosses HBM once per k-chunk, in the
    dtype and layout the dot reads: the table is cast to bf16 before
    the gather (exact: |table| <= G*2^(B_w-1) <= 48), and its rows are
    gathered as columns of the transposed table, ``[C, n_tiles, chunk,
    D_p]``, so the gathered operand is laid out lane-dense in the chunk
    and n_tiles stays a tensor dim.  The bit planes share it through one
    dot whose lhs folds them, ``sum_b 2^b one_hot(code_b)``: integers
    below 2^B_a, exact in bf16 for B_a <= 8, accumulated in f32, so the
    integer result equals the per-plane sum.  The scan steps by
    ``_kscan_chunk(kg, chunk)``; k-groups are padded (with a zero table
    row) only where kg has no divisor in [64, chunk].
    """
    if B_a > 8:
        raise ValueError(f"B_a={B_a}: the folded bit-plane lhs is exact "
                         "in bf16 only for B_a <= 8")
    M, K = a_codes.shape
    D_s, D_p = exec_idx.shape
    n_tiles = N // D_p
    kg = K // G
    C = 2**G

    if codes is None:
        codes = _ref.pack_bitplanes_ref(a_codes, B_a, G)
    t2d = table.reshape(-1, C).astype(jnp.bfloat16)
    rowbase = _rowbase(table, exec_idx, step_cluster, n_tiles, kg)

    chunk = _kscan_chunk(kg, chunk)
    pad_k = (-kg) % chunk
    R = t2d.shape[0]
    if pad_k:
        t2d = jnp.pad(t2d, ((0, 1), (0, 0)))
        rowbase = jnp.pad(
            rowbase, ((0, 0), (0, pad_k), (0, 0)), constant_values=R
        )
        codes = jnp.pad(codes, ((0, 0), (0, 0), (0, pad_k)))
    kgp = kg + pad_k
    nchunks = kgp // chunk
    codes_s = jnp.moveaxis(codes.reshape(B_a, M, nchunks, chunk), 2, 0)
    rb_s = jnp.moveaxis(
        rowbase.reshape(n_tiles, nchunks, chunk, D_p), 1, 0
    )
    tT = t2d.T                                               # [C, R]

    def body(acc, xs):
        cb, rb = xs                          # [B_a, M, chunk], [nt, chunk, D_p]
        sel = sum(
            float(1 << b) * jax.nn.one_hot(cb[b], C, dtype=jnp.bfloat16)
            for b in range(B_a)
        )                                                    # [M, chunk, C]
        rhs = tT[:, rb]                                      # [C, nt, chunk, D_p]
        return acc + jax.lax.dot_general(
            sel, rhs, (((1, 2), (2, 0)), ((), ())),
            preferred_element_type=jnp.float32,
        ), None                                              # [M, nt, D_p]

    acc0 = jnp.zeros((M, n_tiles, D_p), dtype=jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (codes_s, rb_s))
    return acc.reshape(M, N)


@functools.partial(
    jax.jit, static_argnames=("B_a", "G", "N", "chunk", "out_dtype")
)
def tlmac_matmul_xla(
    a_codes: jnp.ndarray,
    table: jnp.ndarray,
    exec_idx: jnp.ndarray,
    step_cluster: jnp.ndarray,
    *,
    B_a: int,
    G: int,
    N: int,
    chunk: int = 256,
    out_scale: Optional[jnp.ndarray] = None,
    out_dtype=None,
    codes: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Lookup GEMM: outer scan over N-tiles, inner loop over k-chunks.

    Loop order matters for HBM: the f32 accumulator lives per N-tile
    ([M, D_p] at a time) and each finished tile is dequantised
    (``out_scale``) and emitted in ``out_dtype`` immediately — a single
    full-size [M, N] f32 accumulator costs ~8 GB/device per MoE expert
    stack at 32k-prefill shapes.  bf16 operands are exact here
    (|table| <= G*2^(B_w-1) <= 48, one-hots are 0/1); accumulation is
    f32 via preferred_element_type, so the integer result is exact.
    """
    M, K = a_codes.shape
    D_s, D_p = exec_idx.shape
    n_tiles = N // D_p
    kg = K // G
    C = 2**G

    if codes is None:
        codes = _ref.pack_bitplanes_ref(a_codes, B_a, G)    # [B_a, M, kg]
    t2d = table.reshape(-1, C)
    rowbase = _rowbase(table, exec_idx, step_cluster, n_tiles, kg)

    chunk = min(chunk, kg)
    pad_k = (-kg) % chunk
    R = t2d.shape[0]
    if pad_k:
        t2d = jnp.pad(t2d, ((0, 1), (0, 0)))                 # zero row
        rowbase = jnp.pad(
            rowbase, ((0, 0), (0, pad_k), (0, 0)), constant_values=R
        )
        codes = jnp.pad(codes, ((0, 0), (0, 0), (0, pad_k)))
    kgp = kg + pad_k
    nk = kgp // chunk
    codes_k = codes.reshape(B_a, M, nk, chunk)

    # The scan must NOT iterate a TP-sharded axis: keep an inner block
    # of 16 tiles (== the 'model' axis size, guaranteed by _pick_dp for
    # sharded layers) as a tensor dim and scan the outer factor.
    nt_in = 16 if n_tiles % 16 == 0 else 1
    nt_out = n_tiles // nt_in
    ncol = nt_in * D_p
    rb_x = rowbase.reshape(nt_out, nt_in, kgp, D_p)
    scale = (
        out_scale.reshape(nt_out, nt_in, D_p)
        if out_scale is not None else jnp.zeros((nt_out, 1, 1))
    )
    odt = out_dtype or (jnp.bfloat16 if out_scale is not None else jnp.float32)

    def n_step(_, xs):
        rb_tile, sc = xs                     # [nt_in, kgp, D_p], [nt_in, D_p]
        rb_k = rb_tile.reshape(nt_in, nk, chunk, D_p)

        def k_step(i, acc):
            rb = jax.lax.dynamic_index_in_dim(
                rb_k, i, axis=1, keepdims=False
            )                                                # [nt_in, chunk, D_p]
            t_rows = t2d[rb].astype(jnp.bfloat16)            # [nt_in, chunk, D_p, C]
            rhs = t_rows.transpose(0, 2, 1, 3).reshape(ncol, chunk * C)
            cb = jax.lax.dynamic_index_in_dim(
                codes_k, i, axis=2, keepdims=False
            )                                                # [B_a, M, chunk]
            for b in range(B_a):
                sel = jax.nn.one_hot(cb[b], C, dtype=jnp.bfloat16)
                acc = acc + float(1 << b) * jax.lax.dot_general(
                    sel.reshape(M, chunk * C), rhs,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                            # [M, ncol]
            return acc

        acc = jax.lax.fori_loop(
            0, nk, k_step, jnp.zeros((M, ncol), jnp.float32)
        )
        if out_scale is not None:
            acc = acc * sc.reshape(ncol)
        return None, acc.astype(odt)

    _, ys = jax.lax.scan(n_step, None, (rb_x, scale))        # [nt_out, M, ncol]
    return ys.transpose(1, 0, 2).reshape(M, N)


@functools.partial(jax.jit, static_argnames=("B_a", "G", "N"))
def tlmac_matmul_xla_flat(
    a_codes: jnp.ndarray,
    table: jnp.ndarray,
    exec_idx: jnp.ndarray,
    step_cluster: jnp.ndarray,
    *,
    B_a: int,
    G: int,
    N: int,
    codes: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Scan-free lookup GEMM: one gather + one one-hot MXU dot per bit
    plane over the *whole* [kg*C, N] expanded table.

    No loop-carried state means XLA fuses the gather into the GEMM
    prologue and the B_a dots run back-to-back — at decode/small-batch
    shapes this beats the chunked scans by >1.5x (the scan's per-step
    dispatch dominates).  The cost is materialising the full expanded
    table, so it loses at large K*N; the autotuner arbitrates.
    """
    M, K = a_codes.shape
    D_s, D_p = exec_idx.shape
    n_tiles = N // D_p
    kg = K // G
    C = 2**G

    if codes is None:
        codes = _ref.pack_bitplanes_ref(a_codes, B_a, G)     # [B_a, M, kg]
    t2d = table.reshape(-1, C)
    rowbase = _rowbase(table, exec_idx, step_cluster, n_tiles, kg)

    t_rows = t2d[rowbase].astype(jnp.bfloat16)               # [nt, kg, dp, C]
    rhs = t_rows.transpose(1, 3, 0, 2).reshape(kg * C, N)
    out = jnp.zeros((M, N), dtype=jnp.float32)
    for b in range(B_a):
        sel = jax.nn.one_hot(codes[b], C, dtype=jnp.bfloat16)
        out = out + float(1 << b) * jax.lax.dot_general(
            sel.reshape(M, kg * C), rhs,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    return out


@functools.partial(jax.jit, static_argnames=("B_a", "G", "N"))
def _tlmac_matmul_ref_jit(a_codes, table, exec_idx, step_cluster, *,
                          B_a: int, G: int, N: int):
    return _ref.tlmac_matmul_ref(
        a_codes, table, exec_idx, step_cluster, B_a, G, N
    )


def dispatch_config(
    config: Dict[str, Any],
    a_codes: jnp.ndarray,
    table: jnp.ndarray,
    exec_idx: jnp.ndarray,
    step_cluster: jnp.ndarray,
    *,
    B_a: int,
    G: int,
    N: int,
    codes: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Run one autotuner candidate config (see kernels/autotune.py).
    Always returns int32 [M, N]."""
    impl = config["impl"]
    if impl == "ref":
        return _tlmac_matmul_ref_jit(
            a_codes, table, exec_idx, step_cluster, B_a=B_a, G=G, N=N
        )
    if impl == "xla-flat":
        return tlmac_matmul_xla_flat(
            a_codes, table, exec_idx, step_cluster,
            B_a=B_a, G=G, N=N, codes=codes,
        ).astype(jnp.int32)
    if impl == "xla":
        return tlmac_matmul_xla(
            a_codes, table, exec_idx, step_cluster,
            B_a=B_a, G=G, N=N, chunk=config.get("chunk", 256), codes=codes,
        ).astype(jnp.int32)
    if impl == "xla-kscan":
        return tlmac_matmul_xla_kscan(
            a_codes, table, exec_idx, step_cluster,
            B_a=B_a, G=G, N=N, chunk=config.get("chunk", 256), codes=codes,
        ).astype(jnp.int32)
    if impl == "fused":
        return tlmac_matmul_fused(
            a_codes, table, exec_idx, step_cluster,
            B_a=B_a, G=G, N=N,
            bm=config.get("bm", 128), bk=config.get("bk", 128),
            gather=config.get("gather", "take"),
        )
    if impl in ("pallas", "pallas-onehot"):
        M, K = a_codes.shape
        kg = K // G
        n_tiles = N // exec_idx.shape[1]
        if codes is None:
            codes = _ref.pack_bitplanes_ref(a_codes, B_a, G)
        rowbase = _rowbase(table, exec_idx, step_cluster, n_tiles, kg)
        return tlmac_gemm(
            codes.astype(jnp.int32), rowbase, table.reshape(-1, 2**G),
            B_a=B_a, G=G, N=N,
            bm=config.get("bm", 128), bk=config.get("bk", 128),
            gather="take" if impl == "pallas" else "onehot",
        )
    raise ValueError(f"unknown impl {impl!r}")


def tlmac_matmul(
    a_codes: jnp.ndarray,
    table: jnp.ndarray,
    exec_idx: jnp.ndarray,
    step_cluster: jnp.ndarray,
    *,
    B_a: int,
    G: int,
    N: int,
    impl: str = "xla",
    chunk: int = 256,
    codes: Optional[jnp.ndarray] = None,
    auto_default: str = "xla",
    auto_allow: Optional[tuple] = None,
    tune_on_miss: bool = True,
) -> jnp.ndarray:
    """Lookup-based quantised GEMM: int32 [M, N] == a_codes @ W_codes.

    ``auto`` knobs: ``auto_allow`` restricts which cached winners may be
    dispatched (the serve path passes the XLA impls only — a winner
    tuned on unsharded eager operands must not embed a Pallas call into
    a TP-sharded graph); ``tune_on_miss=False`` makes a cache miss fall
    back to ``auto_default`` instead of tuning synchronously (serving
    must never pay a candidate sweep at request time)."""
    if impl == "ref":
        return _ref.tlmac_matmul_ref(
            a_codes, table, exec_idx, step_cluster, B_a, G, N
        )
    if impl == "auto":
        from repro.kernels import autotune

        import numpy as _np
        M, K = a_codes.shape
        # memoise the resolved config: shape_key/lookup cost ~100s of us
        # of host time per eager call otherwise, charged to every decode
        memo_key = (M, K, N, B_a, G, exec_idx.shape[1],
                    int(_np.prod(table.shape[:-1])), auto_allow,
                    auto_default, tune_on_miss)
        hit = _AUTO_MEMO.get(memo_key)
        if hit is not None and hit[0] == autotune.generation:
            config = hit[1]
        else:
            key = autotune.shape_key(
                M, K, N, B_a=B_a, G=G, D_p=exec_idx.shape[1],
                R=memo_key[6],
            )
            config = autotune.lookup(key)
            if config is None:
                if tune_on_miss and not isinstance(a_codes, jax.core.Tracer):
                    config = autotune.tune(
                        a_codes, table, exec_idx, step_cluster,
                        B_a=B_a, G=G, N=N,
                    )
                else:
                    # tracing (cannot time) or tuning disabled: fall
                    # back, leave the cache untouched
                    config = {"impl": auto_default}
            # the restriction binds cached AND freshly tuned winners:
            # the tuner may legitimately pick e.g. a Pallas impl, but
            # this call site may not dispatch it (TP-sharded graph)
            if auto_allow is not None and config["impl"] not in auto_allow:
                config = {"impl": auto_default}
            _AUTO_MEMO[memo_key] = (autotune.generation, config)
        return dispatch_config(
            config, a_codes, table, exec_idx, step_cluster,
            B_a=B_a, G=G, N=N, codes=codes,
        )
    config: Dict[str, Any] = {"impl": impl}
    if impl in ("xla", "xla-kscan"):
        config["chunk"] = chunk
    return dispatch_config(
        config, a_codes, table, exec_idx, step_cluster,
        B_a=B_a, G=G, N=N, codes=codes,
    )
