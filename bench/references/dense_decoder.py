"""Plain float32 reference of the dense decoder both configurations run.

Straight ``jax.numpy``, every matrix product at ``HIGHEST`` precision, no
cache, no paging, no batching, no kernels: one causal forward over the
whole sequence.  It imports nothing of the program.  Its inputs are the
benchmark's own weights in plain form (``bench/model.py``): for each
linear the 3-bit weight groups ``gw [n_clus, n_arr, G]``, the index of
each output column's group (``exec_idx [n_tiles, K/G, d_p]``), the
cluster of each row of groups (``step_cluster [n_tiles, K/G]``), the
output scales ``w_step [N]``, the activation step ``a_step`` and the bias.
The lookup weights are expanded once (``expand``) to a dense ``[K, N]``
matrix per linear and layer:

    W[kg*G + g, t*d_p + p] = gw[step_cluster[t, kg], exec_idx[t, kg, p], g]

The block, as the program defines it (pre-norm, rotary over split halves
with base 10000, SwiGLU, rmsnorm eps 1e-6):

    h = rmsnorm(x) * norm1;  q, k, v = lin(h);  q, k = rope(q), rope(k)
    x = x + lin_o(softmax(q k^T / sqrt(hd) + causal) v)
    h = rmsnorm(x) * norm2;  x = x + lin_wo(lin_wi(h) * silu(lin_wg(h)))

with ``lin(x) = (clip(round(x / a_step), 0, 2^a_bits - 1) @ W) * a_step *
w_step + b`` — the quantised activation codes times the integer weights,
exact in float32 at these sizes.  The head is ``rmsnorm(x) * final_norm
@ head^T`` over the first ``vocab`` rows.

``dtype`` rounds every stored activation (linear outputs, the residual
stream, q/k/v, attention outputs, the logits) to a lower precision:
bfloat16, the precision the program computes in, gives the seed's own
sensitivity to rounding; a precision below it is the control
(``bench/check.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 512          # sequences are padded to a multiple of this (few shapes)


def _dense(lin):
    gw = lin["gw"].astype(jnp.float32)
    cl = lin["step_cluster"].astype(jnp.int32)
    e = lin["exec_idx"].astype(jnp.int32)
    sel = gw[cl[:, :, None], e]                     # [nt, kg, dp, G]
    nt, kg, dp, G = sel.shape
    return sel.transpose(1, 3, 0, 2).reshape(kg * G, nt * dp)


@jax.jit
def _expand_linears(parts):
    def one(lin):
        out = {k: lin[k] for k in ("w_step", "a_step", "b") if k in lin}
        out["w"] = jax.lax.map(_dense, {k: lin[k] for k in (
            "gw", "step_cluster", "exec_idx")})
        return out

    return {part: {k: one(v) for k, v in lins.items()}
            for part, lins in parts.items()}


def expand(plain):
    """The reference's weights: ``plain`` with every lookup linear
    replaced by its dense float32 weights ``w [n_layers, K, N]``, expanded
    one layer at a time."""
    return dict(plain, **_expand_linears(
        {part: plain[part] for part in ("attn", "ffn")}))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, base):
    hd = x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv    # [S, hd/2]
    s, c = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _block(x, lw, dims, rnd):
    S = x.shape[0]
    H, KV, hd = dims["n_heads"], dims["n_kv"], dims["head_dim"]
    top = 2 ** dims["a_bits"] - 1

    def lin(name, h):
        p = lw[name[0]][name[1]]
        a = p["a_step"]
        codes = jnp.clip(jnp.round(h / a), 0, top)
        y = jnp.dot(codes, p["w"], precision=HI) * (a * p["w_step"])
        if "b" in p:
            y = y + p["b"].astype(jnp.float32)
        return rnd(y)

    eps = dims["norm_eps"]
    h = rnd(_rms(x, lw["norm1"], eps))
    pos = jnp.arange(S)
    q = rnd(_rope(lin(("attn", "wq"), h).reshape(S, H, hd), pos,
                  dims["rope_base"]))
    k = rnd(_rope(lin(("attn", "wk"), h).reshape(S, KV, hd), pos,
                  dims["rope_base"]))
    v = lin(("attn", "wv"), h).reshape(S, KV, hd)
    qg = q.reshape(S, KV, H // KV, hd)
    scores = jnp.einsum("qkrh,skh->krqs", qg, k, precision=HI) / np.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("krqs,skh->qkrh", w, v, precision=HI).reshape(S, H * hd)
    x = rnd(x + lin(("attn", "wo"), rnd(o)))
    h = rnd(_rms(x, lw["norm2"], eps))
    f = rnd(lin(("ffn", "wi"), h) * jax.nn.silu(lin(("ffn", "wg"), h)))
    return rnd(x + lin(("ffn", "wo"), f))


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _hidden(params, tokens, dims, dtype):
    if dtype == "float32":
        rnd = lambda t: t
    else:
        rnd = lambda t: t.astype(dtype).astype(jnp.float32)
    dims = dict(dims)
    layers = {n: params[n] for n in ("norm1", "norm2", "attn", "ffn")}
    x = rnd(params["embed"][tokens])

    def body(x, lw):
        return _block(x, lw, dims, rnd), None

    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, params["final_norm"], dims["norm_eps"])


@functools.partial(jax.jit, static_argnames=("rows", "vocab", "dtype"))
def _head(head, h, start, rows, vocab, dtype):
    h = jnp.concatenate([h, jnp.zeros((rows, h.shape[1]), h.dtype)])
    lg = jnp.dot(jax.lax.dynamic_slice_in_dim(h, start, rows),
                 head[:vocab].T, precision=HI)
    return lg if dtype == "float32" else lg.astype(dtype).astype(jnp.float32)


def logits(params, dims: dict, tokens, start: int,
           dtype: str = "float32") -> jnp.ndarray:
    """f32 logits ``[len(tokens) - start, vocab]``: row ``j`` predicts
    ``tokens[start + j + 1]`` (the last row predicts past the end).
    ``params`` are the expanded weights (``expand``)."""
    tokens = np.asarray(tokens, np.int32)
    S = len(tokens)
    padded = np.zeros(-(-S // PAD) * PAD, np.int32)
    padded[:S] = tokens
    h = _hidden(params, jnp.asarray(padded),
                tuple(sorted(dims.items())), dtype)
    rows = S - start
    lg = _head(params["head"], h, jnp.int32(start), -(-rows // PAD) * PAD,
               dims["vocab"], dtype)
    return lg[:rows]
