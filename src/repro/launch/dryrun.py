import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first init).  Do not set this flag anywhere else —
# smoke tests and benches are supposed to see 1 device.

"""Multi-pod dry-run (deliverable (e)).

For every (architecture x input shape) cell, build the production mesh
(single-pod 16x16 = 256 chips, or multi-pod 2x16x16 = 512 chips),
``jax.jit(step).lower(**ShapeDtypeStruct inputs).compile()``, and record:

- ``compiled.memory_analysis()``  -> per-device bytes (proves it fits)
- ``compiled.cost_analysis()``    -> HLO FLOPs/bytes (cross-check; scan
  bodies are counted once by XLA — see §Roofline methodology)
- parsed optimized-HLO collective bytes (hlo_analysis.parse_collectives)
- the analytic roofline (launch/analytic.py) — primary source for §Roofline

Usage:
  python -m repro.launch.dryrun --arch codeqwen1.5-7b --shape train_4k
  python -m repro.launch.dryrun --all --mesh multipod --out experiments/dryrun
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.data.pipeline import batch_specs
from repro.launch import analytic
from repro.launch.hlo_analysis import Roofline, parse_collectives
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.parallel.sharding import filter_specs, make_shardings
from repro.train.trainer import TrainConfig, make_train_step

ENC_LEN_DECODE = 4096  # enc-dec decode cells: cached encoder length


def abstract_init(cfg, purpose):
    holder = {}

    def f(k):
        p, a = lm.init_lm(k, cfg, purpose)
        holder["a"] = a
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, holder["a"]


def abstract_caches(cfg, B, S_max, enc_len=0):
    holder = {}

    def f():
        c, a = lm.init_caches(cfg, B, S_max, enc_len)
        holder["a"] = a
        return c

    shapes = jax.eval_shape(f)
    return shapes, holder["a"]


def _batch_entry(B, mesh):
    """Largest data-parallel axis combo that divides B."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    combos = [("pod", "data"), ("data",), ("pod",)]
    for c in combos:
        n = 1
        ok = True
        for a in c:
            if a not in sizes:
                ok = False
                break
            n *= sizes[a]
        if ok and B % n == 0 and n > 1:
            return c
    return None


def _fix_batch_axes(tree, B, mesh):
    """Replace ('pod','data') batch entries with a combo that divides B."""
    entry = _batch_entry(B, mesh)

    def fix(spec):
        out = []
        for e in spec:
            if isinstance(e, tuple) and set(e) == {"pod", "data"}:
                out.append(entry)
            else:
                out.append(e)
        return P(*out)

    return jax.tree.map(fix, tree, is_leaf=lambda s: isinstance(s, P))


def _opt_axes(param_axes, opt_shapes, state_dtype, mesh):
    """Moment shardings: int8 leaves inherit the param spec (last-axis
    block split appends a trailing unsharded dim); non-divisible entries
    degrade to None per-dim."""
    if state_dtype != "int8":
        return {"m": param_axes, "v": param_axes, "step": P()}

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _nshards(entry):
        if entry is None:
            return 1
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in names:
            n *= sizes.get(a, 1)
        return n

    def leaf(pspec, shape_leaf):
        if not (isinstance(shape_leaf, dict) and "q" in shape_leaf):
            return pspec  # f32 fallback leaf keeps the param spec
        qshape = shape_leaf["q"].shape
        entries = list(pspec) + [None] * (len(qshape) - len(pspec))
        q_entries = [
            e if d % _nshards(e) == 0 else None
            for e, d in zip(entries, qshape)
        ]
        return {"q": P(*q_entries), "scale": P(*q_entries[:-1])}

    mv = jax.tree.map(
        leaf, param_axes, opt_shapes["m"],
        is_leaf=lambda s: isinstance(s, P),
    )
    return {"m": mv, "v": mv, "step": P()}


def _mem_dict(compiled):
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        out = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            if hasattr(ma, k):
                out[k] = int(getattr(ma, k))
        out["total_nonalias_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
        )
        return out
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def _cost_dict(compiled):
    try:
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        return {k: float(v) for k, v in c.items()
                if k in ("flops", "bytes accessed", "transcendentals")}
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}


def run_cell(arch: str, shape_name: str, multi_pod: bool, skip_hlo=False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "ok": False,
    }

    if shape.kind == "long-decode" and not cfg.supports_long:
        result.update(ok=True, skipped="by-design: full-attention arch has "
                      "no sub-quadratic path (DESIGN.md §Arch-applicability)")
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                lowered, mult = _lower_train(cfg, shape, mesh)
            elif shape.kind == "prefill":
                lowered, mult = _lower_prefill(cfg, shape, mesh)
            else:
                lowered, mult = _lower_decode(cfg, shape, mesh)
            result["lower_s"] = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            result["compile_s"] = time.time() - t1

            result["memory_analysis"] = _mem_dict(compiled)
            result["cost_analysis"] = _cost_dict(compiled)
            if not skip_hlo:
                try:
                    text = compiled.as_text()
                    coll = parse_collectives(text, loop_multiplier=mult)
                    result["hlo_collectives"] = {
                        "bytes_by_kind": coll.bytes_by_kind,
                        "count_by_kind": coll.count_by_kind,
                        "total_bytes": coll.total_bytes,
                        "loop_multiplier": mult,
                        "hlo_chars": len(text),
                    }
                except Exception as e:
                    result["hlo_collectives"] = {"error": str(e)}
            result["ok"] = True
    except Exception as e:
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
        return result

    # analytic roofline (primary §Roofline source)
    try:
        mesh_shape = (2, 16, 16) if multi_pod else (16, 16)
        ana = analytic.analyze(cfg, shape, mesh_shape)
        mf = analytic.model_flops_6nd(cfg, shape)
        rl = Roofline(
            flops=ana.flops, hbm_bytes=ana.hbm_bytes,
            collective_bytes=ana.collective_bytes, n_chips=n_chips,
            model_flops=mf,
        )
        result["analytic"] = {**rl.as_dict(), "detail": ana.detail}
    except Exception as e:
        result["analytic"] = {"error": f"{type(e).__name__}: {e}"}
    return result


def _lower_train(cfg, shape, mesh):
    tc = TrainConfig(
        adamw=AdamWConfig(state_dtype=cfg.opt_state_dtype),
        accum_steps=getattr(cfg, "train_accum", 1),
    )
    step_fn = make_train_step(cfg, tc)

    params_s, axes = abstract_init(cfg, "train")
    opt_s = jax.eval_shape(lambda p: adamw_init(p, tc.adamw), params_s)
    opt_axes = _opt_axes(axes, opt_s, cfg.opt_state_dtype, mesh)

    bspecs = batch_specs(cfg, shape)
    if getattr(cfg, "pure_fsdp", False):
        bentry = ("data", "model")
    else:
        bentry = _batch_entry(shape.global_batch, mesh)
    batch_axes = {k: P(bentry, *([None] * (len(v.shape) - 1)))
                  for k, v in bspecs.items()}

    shard_p = make_shardings(mesh, axes)
    shard_o = make_shardings(mesh, opt_axes)
    shard_b = make_shardings(mesh, batch_axes)
    rep = NamedSharding(mesh, P())

    jitted = jax.jit(
        step_fn,
        in_shardings=(shard_p, shard_o, shard_b, rep, rep),
        donate_argnums=(0, 1),
    )
    lowered = jitted.lower(
        params_s, opt_s, bspecs,
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    return lowered, cfg.n_layers


def _lower_prefill(cfg, shape, mesh):
    params_s, axes = abstract_init(cfg, "serve")
    bspecs = batch_specs(cfg, shape)
    bentry = _batch_entry(shape.global_batch, mesh)
    batch_axes = {k: P(bentry, *([None] * (len(v.shape) - 1)))
                  for k, v in bspecs.items()}
    shard_p = make_shardings(mesh, axes)
    shard_b = make_shardings(mesh, batch_axes)

    fn = lambda p, b: lm.prefill(p, b, cfg, S_max=shape.seq_len)
    jitted = jax.jit(fn, in_shardings=(shard_p, shard_b))
    lowered = jitted.lower(params_s, bspecs)
    return lowered, cfg.n_layers


def _lower_decode(cfg, shape, mesh):
    B, S = shape.global_batch, shape.seq_len
    params_s, axes = abstract_init(cfg, "serve")
    enc_len = ENC_LEN_DECODE if cfg.n_enc_layers else 0
    caches_s, cache_axes = abstract_caches(cfg, B, S, enc_len)
    cache_axes = _fix_batch_axes(cache_axes, B, mesh)

    shard_p = make_shardings(mesh, axes)
    shard_c = make_shardings(mesh, cache_axes)
    bentry = _batch_entry(B, mesh)
    shard_t = NamedSharding(mesh, P(bentry, None))
    rep = NamedSharding(mesh, P())

    fn = lambda p, c, t, pos: lm.decode_step(p, c, t, pos, cfg)
    jitted = jax.jit(
        fn, in_shardings=(shard_p, shard_c, shard_t, rep),
        donate_argnums=(1,),
    )
    lowered = jitted.lower(
        params_s, caches_s,
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    return lowered, cfg.n_layers


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", type=str, default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="experiments/dryrun")
    ap.add_argument("--skip-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [a for a in list_archs() if a != "resnet18"] if (
        args.all or args.arch is None
    ) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multipod' if mp else 'pod'}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"skip {tag} (exists)")
                    continue
                print(f"=== {tag} ===", flush=True)
                res = run_cell(arch, shape, mp, skip_hlo=args.skip_hlo)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1, default=str)
                status = "OK" if res["ok"] else "FAIL"
                extra = res.get("skipped", res.get("error", ""))
                mem = res.get("memory_analysis", {}).get("total_nonalias_bytes")
                print(f"{status} {tag} mem/dev={mem} {extra}", flush=True)


if __name__ == "__main__":
    main()
