"""Multi-device parallel primitives (overlap + pipeline + dry-run bits).

shard_map needs >1 device, so these tests run a scriptlet in a
subprocess with a forced 4-device host platform.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(src: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_ring_ag_matmul_matches_dense():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.overlap import ring_ag_matmul
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("model",))
        M, K, N = 32, 16, 24
        x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
        w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
        y = ring_ag_matmul(x, w, mesh)
        ref = x.astype(jnp.float32) @ w.astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # schedule check: collective-permutes, no all-gather of x
        hlo = jax.jit(lambda x, w: ring_ag_matmul(x, w, mesh)).lower(x, w)\
            .compile().as_text()
        assert "collective-permute" in hlo
        print("ring_ag ok")
    """))


def test_ring_rs_matmul_matches_dense():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.overlap import ring_rs_matmul
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("model",))
        M, K, N = 32, 16, 24
        x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
        w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
        y = ring_rs_matmul(x, w, mesh)   # [M, N] sharded on M
        ref = x.astype(jnp.float32) @ w.astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        print("ring_rs ok")
    """))


def test_pipeline_matches_sequential():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        S, M, mb, d = 4, 6, 8, 16
        params = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.3

        def stage(w, x):
            return jnp.tanh(x @ w)

        xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
        y = pipeline_apply(stage, params, xs, mesh)
        ref = xs
        for s in range(S):
            ref = jax.vmap(lambda x: stage(params[s], x))(ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("pipeline ok")
    """))


def test_dryrun_single_cell_in_subprocess():
    """End-to-end dry-run machinery on a small arch (both meshes)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "xlstm-350m", "--shape", "decode_32k", "--mesh", "both",
         "--out", "/tmp/dryrun_test", "--skip-hlo"],
        capture_output=True, text=True, env=env, timeout=580, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.count("OK") == 2, out.stdout
