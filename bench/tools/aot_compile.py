"""Compile a configuration's serve forwards at its cell sizes for a
described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python bench/tools/aot_compile.py <config> [...]

For each configuration file: the prefill chunk ``[1, chunk]`` and the
decode step ``[slots, 1]`` as ``PagedServeLoop`` jits them, with the
decode attention as the ``lax`` gather and as the Pallas flash-decode
kernel (the two the tuner chooses between on a TPU), each lowered and
compiled for one described v5e chip.  Prints each program's
``memory_analysis()``.  Nothing runs; what the TPU compiler would refuse
fails here.
"""

from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import model  # noqa: E402


def compile_config(name: str, chip) -> None:
    from repro.kernels import flash_decode, paged
    from repro.models import lm

    # the process's backend is the CPU: compile the kernel, do not
    # interpret it, as it would be on the chip
    flash_decode.resolve_interpret = lambda interpret=None: False
    spec = model.load_config(os.path.join(ROOT, "bench"), name)
    srv = spec["serving"]
    B, S, P, C = srv["slots"], srv["s_max"], srv["page_size"], srv["chunk"]
    pspec = paged.spec_for(S, B, page_size=P)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    base = model.program_config(spec)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: lm.init_lm(k, base, purpose="serve")[0],
        jax.random.PRNGKey(0)))
    caches = jax.tree.map(on_chip, jax.eval_shape(
        lambda: lm.init_caches(base, B, S, paged=pspec)[0]))
    i32 = jnp.int32

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, i32, sharding=chip)

    for impl in ("lax", "flash"):
        cfg = dataclasses.replace(base, serve_paged_attn_impl=impl)
        step = jax.jit(lambda p, c, t, pos, bt: lm.decode_step_paged(
            p, c, t, pos, bt, cfg), donate_argnums=(1,))
        comp = step.lower(params, caches, sds((B, 1)), sds((B,)),
                          sds((B, pspec.max_blocks))).compile()
        print(f"{name} decode[{impl}] B={B}: {comp.memory_analysis()}",
              flush=True)
    chunk = jax.jit(lambda p, c, t, start, row, last: lm.prefill_chunk(
        p, c, t, start, row, base, last=last), donate_argnums=(1,))
    comp = chunk.lower(params, caches, sds((1, C)), sds(()),
                       sds((pspec.max_blocks,)), sds(())).compile()
    print(f"{name} prefill_chunk C={C}: {comp.memory_analysis()}", flush=True)


def main() -> int:
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in sys.argv[1:]:
        compile_config(name, chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
