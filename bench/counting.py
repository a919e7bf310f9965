"""Operations and bytes of the work the benchmark counts, from shapes.

These functions are the yardstick's own: a kernel's roofline and the
model's ops per token are computed here from the configuration's sizes
and the run's counts, never from the program, so the numbers do not move
when the program does.  An operation is a multiply or an add: a
multiply-accumulate counts 2.
"""

from __future__ import annotations

import json
import math
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown device is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(k for k in table if k != 'source')})")
    return table[device_kind]


def layer_weights(d: dict) -> int:
    """Weights of one decoder layer's linears (q, k, v, o, SwiGLU)."""
    dm, H, KV, hd, ff = (d["d_model"], d["n_heads"], d["n_kv"],
                         d["head_dim"], d["d_ff"])
    return dm * H * hd + 2 * dm * KV * hd + H * hd * dm + 3 * dm * ff


def token_ops(d: dict, ctx: int) -> int:
    """Ops of one token through every layer, attending over ``ctx``
    positions (itself included): 2 x the linear weights, plus 4·H·ctx·hd
    of attention a layer (scores and the weighted sum of values)."""
    return d["n_layers"] * (2 * layer_weights(d)
                            + 4 * d["n_heads"] * ctx * d["head_dim"])


def head_ops(d: dict) -> int:
    """Ops of the head for one row of logits."""
    return 2 * d["d_model"] * d["vocab"]


def span_ctx_sum(a: int, b: int) -> int:
    """Sum of (p + 1) over positions p in [a, b): the attention context
    of every token of a prefill run from position a to b."""
    return (b * (b + 1) - a * (a + 1)) // 2


def flash_decode_cost(d: dict, lens, page: int, kv_bytes: int = 2,
                      q_bytes: int = 2) -> tuple:
    """(ops, bytes) of the paged flash-decode kernel over one decode
    step, summed over layers.  ``lens`` are the live slots' context
    lengths (the new token included).  Ops are 4·H·L·hd per slot;
    bytes are the K and V pages that hold the live tokens, the query and
    the float32 output."""
    H, KV, hd, L = d["n_heads"], d["n_kv"], d["head_dim"], d["n_layers"]
    ops = sum(4 * H * n * hd for n in lens)
    pages = sum(math.ceil(n / page) for n in lens)
    kv = 2 * pages * page * KV * hd * kv_bytes
    qo = len(lens) * H * hd * (q_bytes + 4)
    return L * ops, L * (kv + qo)


def lookup_gemm_cost(M: int, K: int, N: int, *, G: int = 4, d_p: int = 128,
                     index_bytes: int = 2, n_clus: int = 4,
                     n_arr: int = 4096) -> tuple:
    """(ops, bytes) of one lookup GEMM [M, K] x [K, N], whatever
    implements it: 2·M·K·N ops; bytes are the index stream (K/G·N
    indices), the cluster ids (K/G·N/d_p bytes), the tables
    (n_clus·n_arr·2^G int32), the activation codes (M·K bytes) and the
    bf16 output."""
    idx = (K // G) * N * index_bytes
    clus = (K // G) * (N // d_p)
    tables = n_clus * n_arr * (2 ** G) * 4
    return 2 * M * K * N, idx + clus + tables + M * K + 2 * M * N
