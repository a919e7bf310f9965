"""NN primitives: linears (with every quantised execution mode), norms,
embeddings, rotary — pure functions over param dicts.

Every ``init_*`` returns ``(params, axes)`` where ``axes`` mirrors the
param tree with ``jax.sharding.PartitionSpec`` leaves.  Mesh axes are the
production mesh's: ``('pod', 'data', 'model')``; FSDP configs additionally
shard the reduction dim over ``('pod', 'data')``.

Linear execution modes
----------------------
train : 'dense' (bf16), 'qdq' (N2UQ/LSQ fake-quant QAT — the paper's
        "train in float, quantise weights/activations" regime)
serve : 'dense', 'int8' (dense integer GEMM baseline), 'tlmac'
        (the paper's lookup path: codebook tables + indices; weights are
        never materialised at full width)
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quant import quantizers as Q
from repro.core.tlmac.compile import plan_shapes
from repro.kernels import ops as kops

COMPUTE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _winit(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return jax.random.normal(key, shape, dtype) * s


MODEL_AXIS = 16  # 'model' axis size in both production meshes


def _pick_dp(N: int, want: int) -> int:
    """Largest dp <= want dividing N with N/dp divisible by the model
    axis, so TLMAC output tiles shard cleanly (TP over n_tiles)."""
    best = None
    for dp in range(min(want, N), 0, -1):
        if N % dp == 0:
            if (N // dp) % MODEL_AXIS == 0:
                return dp
            if best is None:
                best = dp
    return best or min(want, N)


def _fsdp_spec(spec: P, fsdp: bool, shape=None, axes=("pod", "data"),
               n_shards=32) -> P:
    """Extend a TP spec with FSDP sharding on the first unsharded dim
    whose size divides the shard count (shape-aware)."""
    if not fsdp:
        return spec
    parts = list(spec)
    for i, s in enumerate(parts):
        if s is None and (shape is None or shape[i] % n_shards == 0):
            parts[i] = axes
            return P(*parts)
    return spec


# ---------------------------------------------------------------------------
# Linear — train path (dense / fake-quant QAT)
# ---------------------------------------------------------------------------


def init_linear(
    key,
    K: int,
    N: int,
    cfg,
    shard: Tuple = (None, "model"),
    use_bias: bool = False,
    expert: int = 0,
):
    """Train-path linear. ``expert > 0`` stacks an expert dimension."""
    shape = (expert, K, N) if expert else (K, N)
    keys = jax.random.split(key, 3)
    p = {"w": _winit(keys[0], shape)}
    if expert:
        # EP owns the 'model' axis; within-expert dims stay unsharded
        # (FSDP may still claim the K dim below)
        spec = P("model", None, None)
        a = {"w": _fsdp_spec(spec, cfg.fsdp, shape)}
    elif getattr(cfg, "pure_fsdp", False):
        # no TP: params fully sharded over ('data','model') (256-way
        # ZeRO-3), batch data-parallel over the same axes, pod = outer DP
        spec = _fsdp_spec(P(None, None), True, shape,
                          axes=("data", "model"), n_shards=256)
        if spec == P(None, None):  # neither dim divides 256
            spec = _fsdp_spec(P(None, None), True, shape)
        a = {"w": spec}
    else:
        spec = P(*shard)
        a = {"w": _fsdp_spec(spec, cfg.fsdp, shape)}
    if use_bias:
        p["b"] = jnp.zeros((N,) if not expert else (expert, N))
        a["b"] = P(shard[-1]) if not expert else P("model", shard[-1])
    if cfg.linear_impl == "qdq":
        w2 = p["w"].reshape(-1, N)
        p["w_step"] = Q.lsq_init(w2, cfg.quant.w_bits, per_channel=True)
        a["w_step"] = P(shard[-1]) if not expert else P(shard[-1])
        ap = Q.n2uq_act_init(cfg.quant.a_bits)
        p["aq"] = ap
        a["aq"] = {"deltas": P(None), "out_step": P()}
    return p, a


def linear_apply(params, x, cfg, use_bias: bool = False):
    """Train-path forward: bf16 dense or fake-quant QAT.

    Dispatches on the *param structure* so individual layers can opt out
    of quantisation (the paper keeps first/last layers float)."""
    w = params["w"]
    if "aq" in params:
        xq = Q.n2uq_act_quant(x.astype(jnp.float32), params["aq"], cfg.quant.a_bits)
        wq = Q.lsq_quant(
            w.reshape(-1, w.shape[-1]), params["w_step"], cfg.quant.w_bits
        ).reshape(w.shape)
        x_, w_ = xq.astype(COMPUTE_DTYPE), wq.astype(COMPUTE_DTYPE)
    else:
        x_, w_ = x.astype(COMPUTE_DTYPE), w.astype(COMPUTE_DTYPE)
    if w.ndim == 3:  # expert weights [E, K, N]; x [..., E, cap, K]
        y = jnp.einsum("...eck,ekn->...ecn", x_, w_)
    else:
        y = jnp.einsum("...k,kn->...n", x_, w_)
    if use_bias:
        y = y + params["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Linear — serve path (dense / int8 / tlmac)
# ---------------------------------------------------------------------------


def init_serve_linear(
    key,
    K: int,
    N: int,
    cfg,
    shard: Tuple = (None, "model"),
    use_bias: bool = False,
    expert: int = 0,
):
    """Serve-path linear params.

    'tlmac' stores the compiled plan arrays (AOT capacity shapes from
    ``plan_shapes``): int16 indices + int32 VMEM tables — the HBM
    footprint the paper's LUT mapping achieves, visible to
    ``memory_analysis()``.
    """
    impl = cfg.serve_impl
    e = (expert,) if expert else ()
    espec = ("model",) if expert else ()
    if impl == "dense":
        p = {"w": _winit(key, (*e, K, N), dtype=jnp.bfloat16)}
        a = {"w": P(*espec, *shard) if not expert else P("model", *shard[:-1], None)}
    elif impl == "int8":
        p = {
            "w8": jax.random.randint(key, (*e, K, N), -127, 127, jnp.int8),
            "w_step": jnp.ones((*e, N), jnp.float32),
            "a_step": jnp.ones(e, jnp.float32) if e else jnp.float32(1.0),
        }
        a = {
            "w8": P(*espec, *shard) if not expert else P("model", None, None),
            "w_step": P(*espec, None if expert else shard[-1]),
            "a_step": P(*espec) if e else P(),
        }
    elif impl == "tlmac":
        G, dp = cfg.tlmac_G, _pick_dp(N, cfg.tlmac_dp)
        ps = plan_shapes(K, N, G, cfg.quant.w_bits, n_arr_cap=cfg.tlmac_narr_cap, d_p=dp)
        n_tiles, kg = N // dp, K // G
        keys = jax.random.split(key, 3)
        # TP follows the dense layout: shard=(None,'model') shards the
        # output tiles (n_tiles); shard=('model',None) shards the
        # reduction groups (kg) with a psum at the dot.
        # mesh 'model' axis is 16 in both production meshes; pick the
        # first idx dim divisible by it (output tiles strongly preferred
        # — reduction sharding replicates the f32 accumulator).  For
        # big (fsdp) archs the kg dim additionally shards over
        # ('pod','data') — 100B+ dense / 1T MoE index tensors otherwise
        # leave tens of GB/device on the serve graphs.
        dp_extra = ("pod", "data") if (cfg.fsdp and kg % 32 == 0) else None
        if expert:
            idx_spec = P("model", None, dp_extra, None)
            cl_spec = P("model", None, dp_extra)
        elif shard == (None, None):
            idx_spec, cl_spec = P(None, None, None), P(None, None)
        elif n_tiles % MODEL_AXIS == 0:
            idx_spec, cl_spec = P("model", dp_extra, None), P("model", dp_extra)
        elif kg % MODEL_AXIS == 0:
            idx_spec, cl_spec = P(None, "model", None), P(None, "model")
        else:
            idx_spec, cl_spec = P(None, None, None), P(None, None)
        # uint8 indices when the LUT-pool capacity allows (the paper's
        # clustering bounds per-cluster arrays; cap<=256 => 1 byte/group)
        idx_dtype = jnp.uint8 if ps["N_arr"] <= 256 else jnp.int16
        p = {
            "table": jax.random.randint(
                keys[0], (*e, *ps["table"][0]), -8, 8, jnp.int32
            ),
            # [n_tiles, kg, dp] — log2(N_arr) bits per *group* of G weights
            "exec_idx": jax.random.randint(
                keys[1], (*e, n_tiles, kg, dp), 0, ps["N_arr"], idx_dtype
            ),
            "step_cluster": jax.random.randint(
                keys[2], (*e, n_tiles, kg), 0, ps["N_clus"], jnp.int8
            ),
            "w_step": jnp.ones((*e, N), jnp.float32),
            "a_step": jnp.ones(e, jnp.float32) if e else jnp.float32(1.0),
        }
        a = {
            "table": P(*espec),                       # small; replicated
            "exec_idx": idx_spec,
            "step_cluster": cl_spec,
            "w_step": P(*espec, None if expert else shard[-1]),
            "a_step": P(*espec) if e else P(),
        }
    else:
        raise ValueError(impl)
    if use_bias:
        p["b"] = jnp.zeros((*e, N), jnp.bfloat16)
        a["b"] = P(*espec, shard[-1])
    return p, a


def serve_linear_apply(params, x, cfg, use_bias: bool = False,
                       fused: bool = False):
    """Serve-path forward. x: [..., K] -> [..., N].

    Dispatches on param structure: 'table' => tlmac, 'w8' => int8,
    'w' => dense — so mixed-precision layer layouts (paper §6.1) work.
    ``fused=True`` (expert path) uses the N-tile fused-dequant GEMM."""
    impl = "tlmac" if "table" in params else ("int8" if "w8" in params else "dense")
    if impl == "dense":
        y = jnp.einsum("...k,kn->...n", x.astype(COMPUTE_DTYPE), params["w"])
    elif impl == "int8":
        a_step = params["a_step"]
        aq = jnp.clip(
            jnp.round(x.astype(jnp.float32) / a_step), -127, 127
        ).astype(jnp.int8)
        yi = jax.lax.dot_general(
            aq, params["w8"], (((aq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        y = (yi.astype(jnp.float32) * (a_step * params["w_step"])).astype(
            COMPUTE_DTYPE
        )
    elif impl == "tlmac":
        aq, codes_fn = _tlmac_quant_pack(params["a_step"], x, cfg)
        y = _tlmac_gemm(params, aq, codes_fn, x.shape[:-1], cfg, fused)
    else:
        raise ValueError(impl)
    if use_bias:
        y = y + params["b"].astype(y.dtype)
    return y


def _tlmac_quant_pack(a_step, x, cfg):
    """Quantise activations and pack bit-planes ONCE per input tensor.

    Packing is the per-call host work the paper's PE does for free in
    the LUT-array wiring; hoisting it out of the GEMM lets several
    lookup GEMMs reading the same tensor (swiglu wi/wg via
    ``serve_linear_pair_apply``) share a single pack.  Returns
    ``(aq, codes_fn)`` — packing is lazy/memoised so impls that pack
    in-kernel ('fused') or not at all never materialise the
    [B_a, M, K/G] intermediate."""
    B_a, G = cfg.quant.a_bits, cfg.tlmac_G
    K = x.shape[-1]
    aq = jnp.clip(
        jnp.round(x.astype(jnp.float32) / a_step), 0, 2**B_a - 1
    ).astype(jnp.int8).reshape(-1, K)
    cell = []

    def codes_fn():
        if not cell:
            cell.append(kops.pack_bitplanes(aq, B_a, G))
        return cell[0]

    return aq, codes_fn


# trace-time 'auto' dispatch inside model graphs may only pick XLA
# impls: a winner tuned on unsharded eager operands must not embed a
# Pallas call into a TP-sharded serve graph.  Under an active mesh the
# set shrinks further to the scan impls whose accumulators stay sharded
# — 'xla-flat'/'ref' materialise the full expanded table / [M, N]
# intermediates, and 'xla' trades the sharded K-scan for the N-tile
# scan: both are the per-device memory regression the measured comment
# in _tlmac_gemm quantifies (mistral 9.2 vs 23.7 GB/dev).
_SERVE_AUTO_ALLOW = ("ref", "xla", "xla-kscan", "xla-flat")
_SERVE_AUTO_ALLOW_SHARDED = ("xla-kscan",)


def _serve_auto_allow():
    from repro.parallel.sharding import _active_axes

    return (_SERVE_AUTO_ALLOW if _active_axes() is None
            else _SERVE_AUTO_ALLOW_SHARDED)


@jax.named_scope("repro.lookup_gemm")
def _tlmac_gemm(params, aq, codes_fn, lead, cfg, fused: bool):
    """One lookup GEMM from pre-quantised/packed activations.  The
    scope holds the bit-plane pack, the GEMM and the dequant scale on
    both paths and under every impl, so a trace reads the lookup GEMM's
    device time by one name whatever implements it."""
    B_a, G = cfg.quant.a_bits, cfg.tlmac_G
    n_tiles, kg, dp = params["exec_idx"].shape
    N = n_tiles * dp
    a_step = params["a_step"]
    # MoE archs fare better with the fused N-tile scan on ALL serve
    # matmuls (measured: kimi prefill 34.2 vs 21.8 GB/dev); dense
    # archs keep the TP-sharded K-scan (mistral 9.2 vs 23.7).
    fused = fused or cfg.n_experts > 0
    if fused:
        # expert path (vmapped): dequant fused into the GEMM's
        # N-tile scan — no E simultaneous [M, N] f32 accumulators
        y = kops.tlmac_matmul_xla(
            aq,
            params["table"],
            params["exec_idx"].reshape(n_tiles * kg, dp).astype(jnp.int32),
            params["step_cluster"].reshape(-1).astype(jnp.int32),
            B_a=B_a, G=G, N=N, codes=codes_fn(),
            out_scale=(a_step * params["w_step"]).astype(jnp.float32),
        )
        return y.reshape(*lead, N).astype(COMPUTE_DTYPE)
    # dense TP path: autotuned dispatch; on an untuned shape inside jit
    # it falls back to the k-chunk scan, which keeps n_tiles sharded.
    # tune_on_miss=False: serving never pays a candidate sweep inline.
    impl = getattr(cfg, "serve_tlmac_impl", "xla-kscan") or "xla-kscan"
    allow = _serve_auto_allow()
    if impl != "auto" and impl not in allow:
        # the auto path filters disallowed winners silently (a cache is
        # advisory); an EXPLICIT config asking for e.g. a Pallas impl in
        # a sharded graph is a configuration error — fail loudly
        raise ValueError(
            f"serve_tlmac_impl={impl!r} cannot be embedded in this serve "
            f"graph (allowed here: {allow}); Pallas/full-materialisation "
            "impls are benchmark/TPU-single-device paths"
        )
    yi = kops.tlmac_matmul(
        aq,
        params["table"],
        params["exec_idx"].reshape(n_tiles * kg, dp).astype(jnp.int32),
        params["step_cluster"].reshape(-1).astype(jnp.int32),
        B_a=B_a, G=G, N=N,
        codes=None if impl == "fused" else codes_fn(),
        impl=impl,
        auto_default="xla-kscan",
        auto_allow=_serve_auto_allow(),
        tune_on_miss=False,
    )
    y = (yi * (a_step * params["w_step"])).astype(COMPUTE_DTYPE)
    return y.reshape(*lead, N)


def serve_linear_pair_apply(p1, p2, x, cfg):
    """Two serve linears reading the SAME tensor (swiglu wi/wg).  For
    tlmac pairs the activation quantiser and bit-plane packing run once
    and both lookup GEMMs consume the shared packed codes; any other
    param layout falls back to two independent applies, so callers
    never need to introspect the params.

    tlmac branches share the FIRST branch's activation step — same
    tensor, same quantisation grid — which is what makes the shared
    pack exact for both GEMMs.  That is a numerics decision: if the two
    branches were calibrated to different a_steps, routing wg through
    wi's grid changes its codes.  Callers gate on
    ``cfg.serve_shared_act_quant`` (default True; set False for
    checkpoints with per-branch activation calibration to fall back to
    independent quantise+pack per branch)."""
    if "table" not in p1 or "table" not in p2:
        return (serve_linear_apply(p1, x, cfg),
                serve_linear_apply(p2, x, cfg))
    aq, codes_fn = _tlmac_quant_pack(p1["a_step"], x, cfg)
    lead = x.shape[:-1]
    y1 = _tlmac_gemm(p1, aq, codes_fn, lead, cfg, fused=False)
    p2_shared = dict(p2, a_step=p1["a_step"])
    y2 = _tlmac_gemm(p2_shared, aq, codes_fn, lead, cfg, fused=False)
    return y1, y2


# protocol attribute: an apply_fn that supports shared-input pair
# application advertises it here; model code dispatches on the
# attribute, never on function identity (wrappers can re-attach it)
serve_linear_apply.pair_apply = serve_linear_pair_apply


def serve_expert_linear_apply(params, xe, cfg):
    """Serve-path expert linear: params have a leading E dim on every
    leaf; xe [G, E, cap, K] -> [G, E, cap, N] via vmap over experts."""
    G, E, cap, K = xe.shape
    xeT = xe.transpose(1, 0, 2, 3).reshape(E, G * cap, K)
    yT = jax.vmap(
        lambda p, xx: serve_linear_apply(p, xx, cfg, fused=True)
    )(params, xeT)
    N = yT.shape[-1]
    return yT.reshape(E, G, cap, N).transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# Norms / embeddings / rotary
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,))}, {"scale": P(None)}


def rmsnorm_apply(params, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(x.dtype)


def init_layernorm(d: int):
    return (
        {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        {"scale": P(None), "bias": P(None)},
    )


def layernorm_apply(params, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def padded_vocab(vocab: int) -> int:
    """Pad odd vocab sizes (122753, 256206, ...) up to the model axis so
    embeddings/logits stay vocab-parallel.  Sharding the d axis instead
    replicates the [tokens, V] logits+grad (tens of GB/device at
    train_4k).  Padded rows are masked out of loss/sampling."""
    return vocab + (-vocab) % MODEL_AXIS


def init_embedding(key, vocab: int, d: int, cfg):
    p = {"emb": _winit(key, (padded_vocab(vocab), d), scale=0.02)}
    a = {"emb": P("model", None)}   # vocab-parallel
    return p, a


def embed_apply(params, tokens):
    return jnp.take(params["emb"], tokens, axis=0).astype(COMPUTE_DTYPE)


def logits_apply(params, x, vocab: Optional[int] = None):
    """Vocab-parallel logits; padded rows masked to -inf (never argmax'd,
    contribute exp(-inf)=0 to the loss logsumexp)."""
    lg = jnp.einsum(
        "...d,vd->...v", x.astype(COMPUTE_DTYPE), params["emb"].astype(COMPUTE_DTYPE)
    )
    if vocab is not None and lg.shape[-1] != vocab:
        iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
        lg = jnp.where(iota < vocab, lg, jnp.asarray(-1e30, lg.dtype))
    return lg


def rotary_embedding(positions: jnp.ndarray, dim: int, base: float = 10000.0):
    """Returns (sin, cos) [..., dim/2]."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.sin(ang), jnp.cos(ang)


def apply_rotary(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray):
    """x: [..., S, H, hd]; sin/cos: [..., S, hd/2] broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    s, c = sin[..., None, :], cos[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(
        x.dtype
    )


def act_fn(kind: str):
    return {"gelu": jax.nn.gelu, "silu": jax.nn.silu, "relu": jax.nn.relu}[
        "silu" if kind == "swiglu" else kind
    ]
