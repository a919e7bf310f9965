"""AOT compiles for a described TPU v5e, at the serve path's real widths.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, so what the TPU compiler would refuse (block shapes, VMEM,
memory) fails here without chip time.  The topology is described inside
a module-scoped fixture — never at import — so every pytest worker
collects the same tests and only the one running this file loads the
TPU library.  Keep every such compile in this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import paged
from repro.kernels.flash_decode import flash_decode
from repro.launch.serve import FULL_SHAPE
from repro.models import lm

# the launcher's full-size serve shape: 8 slots x S_MAX tokens, page 16,
# CHUNK-token prefill chunks
_, _, S_MAX, CHUNK = FULL_SHAPE
B, HD, P = 8, 128, 16
MB = S_MAX // P


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# (pool dtype, KV heads, query heads per KV head): codeqwen1.5-7b's MHA,
# and the GQA of command-r / mistral-large / kimi-k2
@pytest.mark.parametrize("kv_dtype,KV,REP", [
    pytest.param(dt, kv, rep, id=f"{name}{dt}")
    for name, kv, rep in (("", 32, 1), ("gqa-", 8, 4))
    for dt in ("fp", "int8", "int4")])
def test_flash_decode_compiles_for_v5e(one_chip, kv_dtype, KV, REP):
    hdc = HD // 2 if kv_dtype == "int4" else HD
    pool_dt = jnp.bfloat16 if kv_dtype == "fp" else jnp.int8
    n_pages = B * MB + 1
    args = [_sds(one_chip, (B, KV, REP, HD), jnp.bfloat16),
            _sds(one_chip, (n_pages, P, KV, hdc), pool_dt),
            _sds(one_chip, (n_pages, P, KV, hdc), pool_dt),
            _sds(one_chip, (B, MB), jnp.int32),
            _sds(one_chip, (B,), jnp.int32)]
    scales = {}
    if kv_dtype != "fp":
        scales = {n: _sds(one_chip, (n_pages, P, KV), paged.SCALE_DTYPE)
                  for n in ("k_scales", "v_scales")}
    compiled = jax.jit(
        lambda q, k, v, bt, lens, **kw: flash_decode(
            q, k, v, bt, lens, interpret=False, kv_dtype=kv_dtype, **kw)
    ).lower(*args, **scales).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _model(one_chip, n_layers):
    """codeqwen1.5-7b at published widths, depth cut to ``n_layers``:
    (cfg, params, paged caches, page spec) as shapes on the chip."""
    cfg = dataclasses.replace(get_config("codeqwen1.5-7b"),
                              n_layers=n_layers)
    spec = paged.spec_for(S_MAX, B, page_size=P)

    def on_chip(x):
        return _sds(one_chip, x.shape, x.dtype)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: lm.init_lm(k, cfg, purpose="serve")[0],
        jax.random.PRNGKey(0)))
    caches = jax.tree.map(on_chip, jax.eval_shape(
        lambda: lm.init_caches(cfg, B, S_MAX, paged=spec)[0]))
    return cfg, params, caches, spec


def test_decode_step_paged_compiles_for_v5e(one_chip):
    cfg, params, caches, spec = _model(one_chip, n_layers=2)
    step = jax.jit(lambda p, c, t, pos, bt: lm.decode_step_paged(
        p, c, t, pos, bt, cfg), donate_argnums=(1,))
    compiled = step.lower(
        params, caches, _sds(one_chip, (B, 1), jnp.int32),
        _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B, spec.max_blocks), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    # the donated pool is updated in place, not copied
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))


def test_prefill_chunk_compiles_for_v5e(one_chip):
    cfg, params, caches, spec = _model(one_chip, n_layers=1)
    chunk = jax.jit(lambda p, c, t, start, row, last: lm.prefill_chunk(
        p, c, t, start, row, cfg, last=last), donate_argnums=(1,))
    i32 = jnp.int32
    chunk.lower(params, caches, _sds(one_chip, (1, CHUNK), i32),
                _sds(one_chip, (), i32),
                _sds(one_chip, (spec.max_blocks,), i32),
                _sds(one_chip, (), i32)).compile()


@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_pallas_lookup_kernels_are_refused_for_v5e(one_chip, impl):
    """The Pallas lookup GEMMs do not lower for a TPU yet (their
    ``(bm, 1, D_p)`` output block; ROADMAP §1 item 2).  An explicit
    request for one on a TPU compiles it (``interpret=None`` resolves to
    compiled there; this process's backend is the CPU, so the test says
    ``interpret=False``) and must surface the compiler's refusal."""
    from repro.kernels.tlmac_fused import tlmac_gemm_fused
    from repro.kernels.tlmac_gemm import tlmac_gemm

    K = N = 4096
    G, B_a, dp, M = 4, 3, 128, 8
    kg, n_tiles = K // G, N // dp
    i32 = jnp.int32
    rowbase = _sds(one_chip, (n_tiles, kg, dp), i32)
    table = _sds(one_chip, (4096 * 2**G, 2**G), i32)
    if impl == "fused":
        fn = lambda a, rb, t: tlmac_gemm_fused(a, rb, t, B_a=B_a, G=G, N=N,
                                              interpret=False)
        act = _sds(one_chip, (M, K), i32)
    else:
        fn = lambda c, rb, t: tlmac_gemm(c, rb, t, B_a=B_a, G=G, N=N,
                                        interpret=False)
        act = _sds(one_chip, (B_a, M, kg), i32)
    with pytest.raises(ValueError, match="block shape"):
        jax.jit(fn).lower(act, rowbase, table).compile()


def test_kscan_lookup_gemm_expands_in_bf16_unpadded_for_v5e(one_chip):
    """The dense serve linears' lookup GEMM at minicpm-2b's 2304 x 2304
    (dp 144) and 32 decode rows: the table expansion is gathered in bf16,
    never as an int32 tensor that a convert pass reads again; kg 576
    scans by its divisor 192 with no padded k-group; and the compiler's
    byte count stays under 1.5 GB (the int32, padded expansion of 256-
    group chunks counted 3.56 GB)."""
    from repro.kernels.ops import tlmac_matmul_xla_kscan

    M, K, N, dp, G = 32, 2304, 2304, 144, 4
    kg, n_tiles = K // G, N // dp
    i32 = jnp.int32
    lowered = tlmac_matmul_xla_kscan.lower(
        _sds(one_chip, (M, K), i32), _sds(one_chip, (4, 4096, 2**G), i32),
        _sds(one_chip, (n_tiles * kg, dp), i32),
        _sds(one_chip, (n_tiles * kg,), i32), B_a=3, G=G, N=N)
    text = lowered.as_text()
    assert "pad" not in text
    # the scanned rowbase: 576 / 192 = 3 chunks of [16, 192, 144]
    assert "tensor<3x16x192x144xi32>" in text
    compiled = lowered.compile()
    gathers = [ln for ln in compiled.as_text().splitlines()
               if " gather(" in ln]
    assert gathers
    assert not [ln for ln in gathers if "= s32[" in ln], gathers
    assert compiled.cost_analysis()["bytes accessed"] <= 1.5e9
