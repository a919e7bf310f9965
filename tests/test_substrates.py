"""Substrate tests: optimizer, schedules, 8-bit states, checkpointing,
fault tolerance, gradient compression, data pipeline, straggler monitor."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal containers: deterministic fallback engine
    from _hypothesis_fallback import given, settings, st

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.data.pipeline import SyntheticLMData
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm
from repro.optim.lowbit import q8_decode, q8_encode
from repro.optim.schedules import cosine_schedule, wsd_schedule
from repro.train.compress import compress_grads, q8_sr
from repro.train.ft import StragglerMonitor


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def _toy_params(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (16, 8)), "b": jnp.zeros((8,))}


def test_adamw_converges_quadratic():
    params = _toy_params()
    target = jax.tree.map(lambda p: jnp.ones_like(p), params)
    cfg = AdamWConfig(weight_decay=0.0)
    state = adamw_init(params, cfg)

    def loss(p):
        return sum(jnp.sum((a - b) ** 2) for a, b in
                   zip(jax.tree.leaves(p), jax.tree.leaves(target)))

    l0 = float(loss(params))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = adamw_update(params, g, state, 0.05, cfg)
    assert float(loss(params)) < l0 * 0.01


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
def test_adamw_state_dtypes_track(dtype):
    params = _toy_params(1)
    cfg = AdamWConfig(state_dtype=dtype, weight_decay=0.0)
    state = adamw_init(params, cfg)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)

    for i in range(20):
        g = jax.grad(loss)(params)
        params, state = adamw_update(params, g, state, 0.05, cfg)
    assert float(loss(params)) < float(loss(_toy_params(1))) * 0.9


def test_q8_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 1024)) * 3)
    enc = q8_encode(x)
    assert enc["q"].shape == (4, 4, 256) and enc["scale"].shape == (4, 4)
    y = q8_decode(enc, x.shape)
    # per-block bound: |err| <= blockmax/127 (x2 slack for rounding)
    assert float(jnp.max(jnp.abs(x - y))) <= float(jnp.max(jnp.abs(x))) / 127 * 2

def test_q8_sharding_friendly_layout():
    """No flatten: leading dims are preserved verbatim (GSPMD-critical,
    see lowbit.py docstring)."""
    from repro.optim.lowbit import q8_compatible
    x = jnp.ones((3, 5, 512))
    enc = q8_encode(x)
    assert enc["q"].shape[:2] == (3, 5)
    assert not q8_compatible(jnp.ones((7,)))
    assert not q8_compatible(jnp.ones((4, 100)))


def test_clip_by_global_norm():
    g = {"a": jnp.ones((10,)) * 10}
    gc, gn = clip_by_global_norm(g, 1.0)
    assert abs(float(jnp.linalg.norm(gc["a"])) - 1.0) < 1e-5
    g2 = {"a": jnp.ones((4,)) * 1e-3}
    gc2, _ = clip_by_global_norm(g2, 1.0)
    assert np.allclose(np.asarray(gc2["a"]), 1e-3)


def test_schedules():
    assert float(wsd_schedule(0, 1.0, 100, warmup_steps=10)) < 0.2
    assert abs(float(wsd_schedule(50, 1.0, 100, warmup_steps=10)) - 1.0) < 1e-6
    assert float(wsd_schedule(99, 1.0, 100, warmup_steps=10)) < 0.1
    assert float(cosine_schedule(99, 1.0, 100, warmup_steps=10)) < 0.2


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_q8_sr_unbiased(seed):
    """Stochastic rounding must be unbiased: E[q(x)] == x."""
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(64,)))
    keys = jax.random.split(jax.random.PRNGKey(seed), 256)
    ys = jnp.stack([q8_sr(x, k) for k in keys])
    mean = jnp.mean(ys, axis=0)
    scale = float(jnp.max(jnp.abs(x))) / 127
    assert float(jnp.max(jnp.abs(mean - x))) < 4 * scale / np.sqrt(256) * 3 + 1e-5


def test_error_feedback_accumulates():
    g = {"w": jnp.asarray([1e-4, 5e-1, -3e-3])}
    cg, err = compress_grads(g, jax.random.PRNGKey(0))
    # residual = original - quantised
    np.testing.assert_allclose(
        np.asarray(err["w"]), np.asarray(g["w"] - cg["w"]), atol=1e-7
    )


# ---------------------------------------------------------------------------
# checkpointing + fault tolerance
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"params": {"w": jnp.arange(12.0).reshape(3, 4)},
            "opt": {"step": jnp.int32(7)}}
    save_checkpoint(str(tmp_path), 7, tree, metadata={"loss": 1.5})
    assert latest_step(str(tmp_path)) == 7
    restored, manifest = restore_checkpoint(str(tmp_path), tree)
    assert manifest["step"] == 7 and manifest["metadata"]["loss"] == 1.5
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.asarray(tree["params"]["w"])
    )


def test_checkpoint_keeps_multiple_steps(tmp_path):
    tree = {"x": jnp.zeros(3)}
    for s in (10, 20, 30):
        save_checkpoint(str(tmp_path), s, {"x": jnp.full(3, float(s))})
    assert latest_step(str(tmp_path)) == 30
    r, m = restore_checkpoint(str(tmp_path), tree, step=20)
    assert float(r["x"][0]) == 20.0


def test_preemption_resume_bit_identical(tmp_path):
    """Preempted+resumed run must produce the exact losses of an
    uninterrupted run (deterministic data + atomic checkpoints)."""
    from repro.configs import smoke_config
    from repro.train.ft import FaultTolerantRunner, PreemptionSchedule
    from repro.train.trainer import TrainConfig, TrainLoop

    cfg = smoke_config("codeqwen1.5-7b")
    data = SyntheticLMData(vocab=cfg.vocab, seq_len=16, global_batch=2)
    tc = TrainConfig(lr=1e-3, total_steps=12, warmup_steps=2)

    loopA = TrainLoop(cfg, tc, data, donate=False)
    pA, oA = loopA.init(0)
    loopA.run(pA, oA, num_steps=12)
    ref_losses = [m["loss"] for m in loopA.metrics_log]

    loopB = TrainLoop(cfg, tc, data, ckpt_dir=str(tmp_path),
                      ckpt_interval=4, donate=False)
    runner = FaultTolerantRunner(loopB, str(tmp_path))
    hook = PreemptionSchedule([6])
    runner.run(12, seed=0, step_hook=hook)
    assert runner.restarts == 1
    got = {m["step"]: m["loss"] for m in loopB.metrics_log}
    for s in range(12):
        assert abs(got[s] - ref_losses[s]) < 1e-5, (s, got[s], ref_losses[s])


def test_elastic_restore_resharding(tmp_path):
    """Restore onto a different mesh (1-dev 'new cluster') via shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tree = {"w": jnp.arange(32.0).reshape(4, 8)}
    save_checkpoint(str(tmp_path), 1, tree)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    shardings = {"w": NamedSharding(mesh, P(None, "model"))}
    restored, _ = restore_checkpoint(str(tmp_path), tree, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding == shardings["w"]


# ---------------------------------------------------------------------------
# data pipeline + straggler monitor
# ---------------------------------------------------------------------------


def test_data_deterministic_random_access():
    d = SyntheticLMData(vocab=1000, seq_len=32, global_batch=8, seed=3)
    b1 = d.batch(17)
    b2 = d.batch(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d.batch(18)["tokens"], b1["tokens"])
    assert b1["tokens"].max() < 1000 and b1["tokens"].min() >= 0


def test_data_sharded_slices_disjoint_and_stable():
    d = SyntheticLMData(vocab=100, seq_len=8, global_batch=8, seed=0)
    s0 = d.batch(5, shard=0, n_shards=4)["tokens"]
    s1 = d.batch(5, shard=1, n_shards=4)["tokens"]
    assert s0.shape == (2, 8)
    assert not np.array_equal(s0, s1)
    np.testing.assert_array_equal(
        s0, d.batch(5, shard=0, n_shards=4)["tokens"]
    )


def test_straggler_monitor_flags_slow_shard():
    mon = StragglerMonitor(n_shards=8, threshold=2.0)
    for _ in range(20):
        times = {i: 1.0 for i in range(8)}
        times[3] = 5.0
        slow = mon.update(times)
    assert slow == [3]
