"""A configuration file -> the program's config, and its weights from a seed.

A configuration file (``bench/configs/<name>.json``) holds the model's
sizes and constants under their Hugging Face names as they are run, the
keys changed from the published model (``published_values`` keeps what
they were, ``departures`` why), the serving deployment, the quantisation
the program runs, and the statistics of the random weights.
``program_config`` checks every size against the program's own
configuration of that model, and the rotary base and rmsnorm eps against
the program's fixed ones, so a file that says one thing while the program
runs another is refused, not measured.

``make_weights`` makes the weights on the device in one jitted call from
the seed, in the program's serve layout (lookup tables, int16 indices,
cluster ids, scales), together with the plain form the reference reads:
the 3-bit weight groups the tables were packed from.  The layout is read
from the program's own ``init_lm`` by ``jax.eval_shape`` (shapes only);
every value is the benchmark's.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

# published key -> the program's ArchConfig field
HF_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "num_hidden_layers": "n_layers",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
}
# published constants the program fixes for every model
FIXED_KEYS = ("rope_theta", "rms_norm_eps")
# keys of the file that are not published sizes
META_KEYS = {"name", "source", "program_config", "reference",
             "published_values", "deployment", "serving", "quant",
             "weights", "assumed", "departures", "correct"}
# second moment of a 3-bit activation code when its step puts the
# input's standard deviation at two codes (half-normal, clipped at 7)
CODE_M2 = 2.0


def load_config(root: str, name: str) -> dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        spec = json.load(f)
    if spec.get("name") != name:
        raise ValueError(f"configs/{name}.json names {spec.get('name')!r}")
    unknown = set(spec) - META_KEYS - set(HF_TO_PROGRAM) - set(FIXED_KEYS)
    unexplained = unknown - (set(spec["departures"])
                             & set(spec["published_values"]))
    if unexplained:
        raise ValueError(f"{name}: keys {sorted(unexplained)} are not run by "
                         "the program, and their published value and "
                         "departure are not both given")
    return spec


def _program_fixed() -> dict:
    """The rotary base and rmsnorm eps the program uses for every model."""
    import inspect

    from repro.models import nn

    def default(fn, arg):
        return inspect.signature(fn).parameters[arg].default

    return {"rope_theta": default(nn.rotary_embedding, "base"),
            "rms_norm_eps": default(nn.rmsnorm_apply, "eps")}


def program_config(spec: dict):
    """The program's ArchConfig for a configuration file: the program's
    own config of that model, with the file's reduced keys applied.  Any
    other difference between the file and the program is an error."""
    from repro.configs import get_config

    cfg = get_config(spec["program_config"])
    changes = {}
    for key, field in HF_TO_PROGRAM.items():
        want = spec[key]
        have = getattr(cfg, field)
        if have == want:
            continue
        if key not in spec["published_values"]:
            raise ValueError(f"{spec['name']}: {key}={want} but the program's "
                             f"{spec['program_config']} has {field}={have}, "
                             "and the key is not listed as reduced")
        changes[field] = want
    for key, have in _program_fixed().items():
        if spec[key] != have:
            raise ValueError(f"{spec['name']}: {key}={spec[key]} but the "
                             f"program runs {have}")
    q = spec["quant"]
    if (cfg.quant.w_bits, cfg.quant.a_bits, cfg.tlmac_G) != (
            q["w_bits"], q["a_bits"], q["G"]) or cfg.serve_impl != "tlmac":
        raise ValueError(f"{spec['name']}: the program serves {cfg.serve_impl} "
                         f"w{cfg.quant.w_bits}a{cfg.quant.a_bits} "
                         f"G{cfg.tlmac_G}, the file states {q}")
    changes["serve_kv_dtype"] = spec["serving"]["kv_dtype"]
    return dataclasses.replace(cfg, **changes)


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (all of its bits)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _code_bits(G: int) -> np.ndarray:
    """[2^G, G] int32: bit g of each G-bit activation pattern."""
    codes = np.arange(2 ** G)[:, None]
    return ((codes >> np.arange(G)[None, :]) & 1).astype(np.int32)


def _linear(node, key, role, wspec):
    """One lookup linear: (serve params, plain params)."""
    levels = int(wspec["weight_levels"])
    *lead, n_clus, n_arr, C = node["table"].shape
    G = int(round(math.log2(C)))
    n_tiles, kg, dp = node["exec_idx"].shape[-3:]
    K, N = kg * G, n_tiles * dp
    k = jax.random.split(key, 5)
    gw = jax.random.randint(k[0], (*lead, n_clus, n_arr, G), -levels,
                            levels + 1, jnp.int32)
    table = jnp.einsum("cg,...ag->...ac", jnp.asarray(_code_bits(G)), gw)
    exec_idx = jax.random.randint(k[1], node["exec_idx"].shape, 0, n_arr,
                                  jnp.int32).astype(node["exec_idx"].dtype)
    step_cluster = jax.random.randint(
        k[2], node["step_cluster"].shape, 0, n_clus,
        jnp.int32).astype(node["step_cluster"].dtype)
    a = float(wspec["a_step"][role])
    var_w = levels * (levels + 1) / 3.0
    w_scale = float(wspec["out_rms"][role]) / (
        a * math.sqrt(K * CODE_M2 * var_w))
    w_step = w_scale * jax.random.uniform(k[3], node["w_step"].shape,
                                          jnp.float32, 0.8, 1.2)
    a_step = jnp.full(node["a_step"].shape, a, jnp.float32)
    serve = {"table": table.astype(node["table"].dtype),
             "exec_idx": exec_idx, "step_cluster": step_cluster,
             "w_step": w_step, "a_step": a_step}
    plain = {"gw": gw.astype(jnp.int8), "exec_idx": exec_idx,
             "step_cluster": step_cluster, "w_step": w_step,
             "a_step": a_step}
    if "b" in node:
        b = (float(wspec["bias_std"]) * jax.random.normal(
            k[4], node["b"].shape, jnp.float32)).astype(node["b"].dtype)
        serve["b"] = plain["b"] = b
    return serve, plain


def _fill(node, key, path, wspec):
    if isinstance(node, dict) and "table" in node:
        return _linear(node, key, f"{path[-2]}.{path[-1]}", wspec)
    if isinstance(node, (dict, list)):
        items = sorted(node.items()) if isinstance(node, dict) \
            else list(enumerate(node))
        pairs = {name: _fill(sub, jax.random.fold_in(key, i),
                             path + (name,), wspec)
                 for i, (name, sub) in enumerate(items)}
        if isinstance(node, list):
            return ([pairs[i][0] for i in range(len(node))],
                    [pairs[i][1] for i in range(len(node))])
        return ({n: p[0] for n, p in pairs.items()},
                {n: p[1] for n, p in pairs.items()})
    name = path[-1]
    if name == "emb":
        x = float(wspec["embed_std"]) * jax.random.normal(
            key, node.shape, jnp.float32)
    elif name == "scale":
        lo, hi = wspec["norm_scale"]
        x = jax.random.uniform(key, node.shape, jnp.float32, lo, hi)
    else:
        raise ValueError(f"no weight rule for the program's leaf {path}")
    x = x.astype(node.dtype)
    return x, x


def make_weights(cfg, wspec: dict, seed: int):
    """(serve params in the program's layout, plain params for the
    reference), made on the device in one jitted call from ``seed``."""
    from repro.models import lm

    layout = jax.eval_shape(lambda k: lm.init_lm(k, cfg, purpose="serve")[0],
                            jax.random.PRNGKey(0))
    if len(layout["segments"]) != 1 or set(layout["segments"][0]) != {"b0"}:
        raise ValueError(f"{cfg.name}: not a uniform decoder stack")
    serve, plain = jax.jit(lambda k: _fill(layout, k, (), wspec))(
        seed_key(seed))
    blk = plain["segments"][0]["b0"]
    ref = {
        "embed": plain["embed"]["emb"],
        "head": plain["head"]["emb"] if "head" in plain
        else plain["embed"]["emb"],
        "final_norm": plain["final_norm"]["scale"],
        "norm1": blk["norm1"]["scale"], "norm2": blk["norm2"]["scale"],
        "attn": blk["attn"], "ffn": blk["ffn"],
    }
    return serve, ref


def dims(cfg, spec: dict) -> dict:
    """The sizes the reference and the counting functions read, as plain
    numbers."""
    return {"vocab": cfg.vocab, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "n_heads": cfg.n_heads, "n_kv": cfg.n_kv,
            "head_dim": cfg.kv_head_dim, "a_bits": cfg.quant.a_bits,
            "rope_base": float(spec["rope_theta"]),
            "norm_eps": float(spec["rms_norm_eps"])}
