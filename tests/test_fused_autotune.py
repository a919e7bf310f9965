"""Fused megakernel + autotune dispatch + multi-tile clustered kernel +
serve-loop continuous batching (this PR's tentpole surface).

All integer kernels are bit-exact: array_equal against the pure-jnp
oracle / dense integer GEMM, never allclose."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tlmac import compile as tc
from repro.kernels import autotune, ops
from repro.kernels import ref as kref
from repro.kernels.tlmac_fused import tlmac_gemm_fused, tlmac_matmul_fused


def _setup(seed, K, N, M, B_w, B_a, G, d_p=64):
    rng = np.random.default_rng(seed)
    w = rng.integers(-(2 ** (B_w - 1)), 2 ** (B_w - 1), size=(K, N))
    plan = tc.compile_layer(w, B_w=B_w, B_a=B_a, G=G, d_p=d_p,
                            anneal_iters=60, seed=seed)
    a = rng.integers(0, 2**B_a, size=(M, K))
    return (jnp.asarray(a), jnp.asarray(w), jnp.asarray(plan.table),
            jnp.asarray(plan.exec_idx), jnp.asarray(plan.step_cluster), plan)


# ---------------------------------------------------------------------------
# fused megakernel
# ---------------------------------------------------------------------------

# (K, N, M, B_w, B_a, G, d_p, bm, bk): M and KG deliberately NOT
# multiples of the block sizes to exercise the padding paths
FUSED_SWEEP = [
    (20, 64, 7, 2, 2, 2, 64, 4, 3),     # kg=10, bk=3; M=7, bm=4
    (24, 64, 13, 3, 3, 2, 32, 8, 5),    # 2 output tiles
    (32, 128, 37, 3, 2, 4, 64, 16, 4),  # kg=8, bk=4
    (48, 64, 5, 4, 3, 4, 64, 128, 128), # blocks bigger than the problem
]


@pytest.mark.parametrize("K,N,M,B_w,B_a,G,d_p,bm,bk", FUSED_SWEEP)
@pytest.mark.parametrize("gather", ["take", "onehot"])
def test_fused_bitexact_vs_ref(K, N, M, B_w, B_a, G, d_p, bm, bk, gather):
    a, w, t, e, c, _ = _setup(K + M + G, K, N, M, B_w, B_a, G, d_p=d_p)
    ref = np.asarray(kref.tlmac_matmul_ref(a, t, e, c, B_a, G, N))
    assert np.array_equal(ref, np.asarray(ops.dense_int_matmul(a, w)))
    out = np.asarray(tlmac_matmul_fused(
        a, t, e, c, B_a=B_a, G=G, N=N, bm=bm, bk=bk, gather=gather
    ))
    assert np.array_equal(out, ref), (K, N, M, gather)


def test_fused_dispatch_through_ops():
    a, w, t, e, c, _ = _setup(11, 32, 128, 9, 3, 3, 4)
    ref = np.asarray(ops.dense_int_matmul(a, w))
    out = np.asarray(ops.tlmac_matmul(a, t, e, c, B_a=3, G=4, N=128,
                                      impl="fused"))
    assert np.array_equal(out, ref)


def test_fused_prepacked_codes_paths_agree():
    """xla/xla-flat/kscan accept pre-packed codes (the one-time
    activation-packing path) and must agree with self-packing."""
    a, w, t, e, c, _ = _setup(3, 24, 64, 8, 3, 3, 3)
    ref = np.asarray(ops.dense_int_matmul(a, w))
    codes = kref.pack_bitplanes_ref(a, 3, 3)
    for impl in ("xla", "xla-flat", "xla-kscan"):
        out = np.asarray(ops.tlmac_matmul(
            a, t, e, c, B_a=3, G=3, N=64, impl=impl, codes=codes
        ))
        assert np.array_equal(out, ref), impl


# ---------------------------------------------------------------------------
# autotune cache
# ---------------------------------------------------------------------------


def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    """tune() persists the winner; a fresh in-memory cache re-reads it
    and impl='auto' honors the persisted config."""
    cache = tmp_path / "at.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
    autotune.reset_cache()
    try:
        a, w, t, e, c, plan = _setup(7, 32, 64, 6, 3, 3, 4)
        cands = [{"impl": "ref"}, {"impl": "xla-flat"},
                 {"impl": "xla", "chunk": 64}]
        cfg = autotune.tune(a, t, e, c, B_a=3, G=4, N=64, reps=2,
                            cands=cands)
        assert cfg["impl"] in {"ref", "xla-flat", "xla"}
        assert cache.exists()
        data = json.loads(cache.read_text())
        key = autotune.shape_key(6, 32, 64, B_a=3, G=4, D_p=64,
                                 R=int(np.prod(t.shape[:-1])))
        assert data[key]["config"] == cfg
        assert data[key]["us"] > 0

        # fresh process simulation: drop memory, lookup must re-load
        autotune.reset_cache()
        assert autotune.lookup(key) == cfg

        # impl='auto' dispatches from the cache without re-tuning
        # (file mtime unchanged) and stays bit-exact
        mtime = os.stat(cache).st_mtime_ns
        ref = np.asarray(ops.dense_int_matmul(a, w))
        out = np.asarray(ops.tlmac_matmul(a, t, e, c, B_a=3, G=4, N=64,
                                          impl="auto"))
        assert np.array_equal(out, ref)
        assert os.stat(cache).st_mtime_ns == mtime
    finally:
        autotune.reset_cache()   # don't leak the tmp path to other tests


def test_autotune_auto_inside_jit_falls_back(tmp_path, monkeypatch):
    """Tracing cannot time: on a cache miss impl='auto' must lower via
    auto_default instead of crashing or writing junk to the cache."""
    cache = tmp_path / "at.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
    autotune.reset_cache()
    try:
        a, w, t, e, c, _ = _setup(13, 24, 64, 5, 2, 2, 3)
        ref = np.asarray(ops.dense_int_matmul(a, w))

        @jax.jit
        def f(a, t, e, c):
            return ops.tlmac_matmul(a, t, e, c, B_a=2, G=3, N=64,
                                    impl="auto")

        out = np.asarray(f(a, t, e, c))
        assert np.array_equal(out, ref)
        assert not cache.exists()
    finally:
        autotune.reset_cache()


def test_autotune_rejects_non_bitexact(monkeypatch, tmp_path):
    """A fast-but-wrong candidate must never win: verification compares
    against the oracle before timing."""
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "at.json"))
    autotune.reset_cache()
    try:
        a, w, t, e, c, _ = _setup(17, 24, 64, 4, 2, 2, 3)

        calls = {}
        real = ops.dispatch_config

        def wrong(config, *args, **kw):
            out = real(config, *args, **kw)
            if config["impl"] == "xla-flat":
                calls["sabotaged"] = True
                return out + 1          # fast path, wrong result
            return out

        monkeypatch.setattr(ops, "dispatch_config", wrong)
        cfg = autotune.tune(a, t, e, c, B_a=2, G=3, N=64, reps=2,
                            cands=[{"impl": "xla-flat"}, {"impl": "ref"}])
        assert calls.get("sabotaged")
        # the sabotaged fast candidate must never win; either the
        # honest candidate or the always-timed xla baseline may
        # (which of the two is faster is machine noise)
        assert cfg["impl"] in ("ref", "xla")
    finally:
        autotune.reset_cache()


def test_autotune_names_every_failed_candidate(monkeypatch, tmp_path):
    """A candidate that raises (a compiler refusal, a shape it cannot
    take) or disagrees with the oracle is dropped, counted and named —
    never swallowed in silence."""
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "at.json"))
    autotune.reset_cache()
    autotune.reset_stats()
    try:
        a, w, t, e, c, _ = _setup(19, 24, 64, 4, 2, 2, 3)
        real = ops.dispatch_config

        def faulty(config, *args, **kw):
            if config["impl"] == "xla-flat":
                raise ValueError("refused by the compiler")
            out = real(config, *args, **kw)
            return out + 1 if config["impl"] == "ref" else out

        monkeypatch.setattr(ops, "dispatch_config", faulty)
        cfg = autotune.tune(a, t, e, c, B_a=2, G=3, N=64, reps=2,
                            cands=[{"impl": "xla-flat"}, {"impl": "ref"}])
        assert cfg == {"impl": "xla"}
        failed = autotune.snapshot_stats()["failed_candidates"]
        assert [f["config"] for f in failed] == [{"impl": "xla-flat"},
                                                 {"impl": "ref"}]
        assert "refused by the compiler" in failed[0]["reason"]
        assert "bit-exact" in failed[1]["reason"]
        assert autotune.registry().get_counter("candidate_failures") == 2
        autotune.reset_stats()
        assert autotune.snapshot_stats()["failed_candidates"] == []
    finally:
        autotune.reset_cache()
        autotune.reset_stats()


# ---------------------------------------------------------------------------
# multi-output-tile clustered kernel
# ---------------------------------------------------------------------------


def test_clustered_multi_tile_bitexact():
    """One pallas_call covers every output tile; == dense integer GEMM."""
    from repro.kernels.tlmac_clustered import (
        cluster_schedule_tiled, run_clustered_multi,
    )

    rng = np.random.default_rng(5)
    for (K, N, M, B_w, B_a, G, d_p, bk) in [
        (64, 128, 21, 3, 3, 4, 64, 4),   # 2 output tiles
        (24, 96, 7, 2, 2, 3, 32, 2),     # 3 output tiles
        (48, 128, 9, 4, 4, 4, 128, 8),   # 1 tile (degenerates to single)
    ]:
        w = rng.integers(-(2 ** (B_w - 1)), 2 ** (B_w - 1), size=(K, N))
        plan = tc.compile_layer(w, B_w=B_w, B_a=B_a, G=G, d_p=d_p,
                                anneal_iters=60, seed=0)
        a = rng.integers(0, 2**B_a, size=(M, K))
        ref = np.asarray(ops.dense_int_matmul(jnp.asarray(a), jnp.asarray(w)))
        out = np.asarray(run_clustered_multi(plan, a, B_a=B_a, N=N,
                                             bk=bk, bm=16))
        assert np.array_equal(out, ref), (K, N, G)
        sched = cluster_schedule_tiled(plan, N // d_p, bk=bk)
        assert sched["order"].shape[:2] == (N // d_p, plan.N_clus)
        assert sched["ms"] % bk == 0


# ---------------------------------------------------------------------------
# serve loop continuous batching
# ---------------------------------------------------------------------------


def test_serve_loop_refills_freed_slots_mid_decode():
    """A finished slot admits the next queued request while other slots
    are still decoding — the docstring's promise the seed didn't keep."""
    from repro.configs import smoke_config
    from repro.models import lm
    from repro.serve.loop import Request, ServeLoop

    cfg = smoke_config("codeqwen1.5-7b")
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, purpose="serve")
    rng = np.random.default_rng(0)
    loop = ServeLoop(params, cfg, batch_slots=2, s_max=48)
    max_new = [2, 8, 2, 3, 2]
    for i, mn in enumerate(max_new):
        loop.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, size=5).astype(np.int32),
            max_new_tokens=mn,
        ))
    done = loop.run()
    by_rid = {r.rid: r for r in done}
    assert len(done) == 5
    assert all(len(by_rid[i].output) == max_new[i] for i in range(5))
    # with batch [2, 8]: slot 0 frees at step 2 while slot 1 runs to 8 —
    # rids 2,3,4 must all be admitted into freed slots mid-decode
    assert loop.refills >= 3
    assert all(r.output.min() >= 0 and r.output.max() < cfg.vocab
               for r in done)


def test_autotune_concurrent_writers_merge(tmp_path, monkeypatch):
    """record() must merge the on-disk state, not clobber entries
    persisted by another process since this one memoised the cache."""
    cache = tmp_path / "at.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
    autotune.reset_cache()
    try:
        autotune.record("key_a", {"impl": "xla"}, 1.0)
        # simulate a second process persisting its own winner
        data = json.loads(cache.read_text())
        data["key_b"] = {"config": {"impl": "ref"}, "us": 2.0,
                         "baseline_us": {}}
        cache.write_text(json.dumps(data))
        # our process (memoised cache lacks key_b) records another key
        autotune.record("key_c", {"impl": "xla-flat"}, 3.0)
        merged = json.loads(cache.read_text())
        assert set(merged) == {"key_a", "key_b", "key_c"}
    finally:
        autotune.reset_cache()


def test_auto_allow_filters_cached_winner(tmp_path, monkeypatch):
    """A cached Pallas winner must not be dispatched where the caller
    restricts to XLA impls (TP-sharded serve graphs)."""
    cache = tmp_path / "at.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
    autotune.reset_cache()
    try:
        a, w, t, e, c, _ = _setup(21, 24, 64, 4, 2, 2, 3)
        key = autotune.shape_key(4, 24, 64, B_a=2, G=3, D_p=64,
                                 R=int(np.prod(t.shape[:-1])))
        autotune.record(key, {"impl": "fused", "bm": 64, "bk": 64}, 1.0)

        seen = []
        real = ops.dispatch_config

        def spy(config, *args, **kw):
            seen.append(config["impl"])
            return real(config, *args, **kw)

        monkeypatch.setattr(ops, "dispatch_config", spy)
        ref = np.asarray(ops.dense_int_matmul(a, w))
        out = np.asarray(ops.tlmac_matmul(
            a, t, e, c, B_a=2, G=3, N=64, impl="auto",
            auto_allow=("ref", "xla", "xla-kscan", "xla-flat"),
            auto_default="xla-kscan",
        ))
        assert np.array_equal(out, ref)
        assert seen == ["xla-kscan"]        # fused winner filtered out
        # without the restriction the cached winner is honored
        out2 = np.asarray(ops.tlmac_matmul(
            a, t, e, c, B_a=2, G=3, N=64, impl="auto"))
        assert np.array_equal(out2, ref)
        assert seen[-1] == "fused"
    finally:
        autotune.reset_cache()


def test_auto_tune_on_miss_false_never_tunes(tmp_path, monkeypatch):
    """The serve path passes tune_on_miss=False: an eager cache miss
    must fall back instead of running a candidate sweep inline."""
    cache = tmp_path / "at.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
    autotune.reset_cache()
    try:
        a, w, t, e, c, _ = _setup(23, 24, 64, 4, 2, 2, 3)
        monkeypatch.setattr(
            autotune, "tune",
            lambda *a_, **k_: (_ for _ in ()).throw(
                AssertionError("tune() ran at serve time")),
        )
        ref = np.asarray(ops.dense_int_matmul(a, w))
        out = np.asarray(ops.tlmac_matmul(
            a, t, e, c, B_a=2, G=3, N=64, impl="auto",
            tune_on_miss=False, auto_default="xla-kscan",
        ))
        assert np.array_equal(out, ref)
        assert not cache.exists()
    finally:
        autotune.reset_cache()


def test_serve_refill_keeps_first_token():
    """A refilled request's first generated token is the refill
    prefill's argmax; dropping it shifts the whole output.  With
    batch_slots=1 and equal-length prompts the refill happens at
    exact-fit length (no extra padding), so the refilled request's
    output must be IDENTICAL to running it solo."""
    from repro.configs import smoke_config
    from repro.models import lm
    from repro.serve.loop import Request, ServeLoop

    cfg = smoke_config("codeqwen1.5-7b")
    params, _ = lm.init_lm(jax.random.PRNGKey(0), cfg, purpose="serve")
    rng = np.random.default_rng(3)
    p0 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)

    loop = ServeLoop(params, cfg, batch_slots=1, s_max=32)
    loop.submit(Request(rid=0, prompt=p0, max_new_tokens=1))
    loop.submit(Request(rid=1, prompt=p1, max_new_tokens=3))
    done = {r.rid: r for r in loop.run()}
    assert loop.refills == 1          # rid=1 was admitted mid-batch

    solo = ServeLoop(params, cfg, batch_slots=1, s_max=32)
    solo.submit(Request(rid=9, prompt=p1, max_new_tokens=3))
    want = solo.run()[0].output
    assert np.array_equal(done[1].output, want), (done[1].output, want)
    assert len(done[0].output) == 1 and len(done[1].output) == 3


def test_serve_refill_immediate_finish_frees_slot():
    """max_new_tokens=1 requests admitted via refill finish on
    admission; the freed slot must immediately admit the next request
    in the same step (no deadlock, no lost requests)."""
    from repro.configs import smoke_config
    from repro.models import lm
    from repro.serve.loop import Request, ServeLoop

    cfg = smoke_config("codeqwen1.5-7b")
    params, _ = lm.init_lm(jax.random.PRNGKey(1), cfg, purpose="serve")
    rng = np.random.default_rng(4)
    loop = ServeLoop(params, cfg, batch_slots=1, s_max=32)
    for i in range(4):
        loop.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, size=5).astype(np.int32),
            max_new_tokens=1 if i else 2,
        ))
    done = loop.run()
    assert len(done) == 4
    assert all(len(r.output) == (1 if r.rid else 2) for r in done)


def test_fused_hoist_fallback_bitexact():
    """A tiny hoist budget forces the per-visit rhs recompute path; it
    must agree with the hoisted path and the oracle."""
    a, w, t, e, c, _ = _setup(31, 32, 128, 19, 3, 3, 4)
    ref = np.asarray(ops.dense_int_matmul(a, w))
    hoisted = np.asarray(tlmac_matmul_fused(
        a, t, e, c, B_a=3, G=4, N=128, bm=8, bk=4))
    fallback = np.asarray(tlmac_matmul_fused(
        a, t, e, c, B_a=3, G=4, N=128, bm=8, bk=4, hoist_vmem_bytes=1))
    assert np.array_equal(hoisted, ref)
    assert np.array_equal(fallback, ref)


def test_auto_allow_binds_freshly_tuned_winner(tmp_path, monkeypatch):
    """auto_allow must filter the tuner's winner too, not only cached
    entries (a disallowed impl must never run at this call site)."""
    cache = tmp_path / "at.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
    autotune.reset_cache()
    try:
        a, w, t, e, c, _ = _setup(29, 24, 64, 4, 2, 2, 3)
        monkeypatch.setattr(
            autotune, "tune", lambda *a_, **k_: {"impl": "fused"}
        )
        seen = []
        real = ops.dispatch_config

        def spy(config, *args, **kw):
            seen.append(config["impl"])
            return real(config, *args, **kw)

        monkeypatch.setattr(ops, "dispatch_config", spy)
        ref = np.asarray(ops.dense_int_matmul(a, w))
        out = np.asarray(ops.tlmac_matmul(
            a, t, e, c, B_a=2, G=3, N=64, impl="auto",
            auto_allow=("xla-kscan",), auto_default="xla-kscan",
        ))
        assert np.array_equal(out, ref)
        assert seen == ["xla-kscan"]
    finally:
        autotune.reset_cache()


def test_serve_dense_loop_admits_whenever_prompt_fits():
    """The dense loop's refill_quantum workaround is gone (bounding the
    compile set is the paged loop's job — tests/test_paged_serve.py
    asserts its two-shape property): admission now happens the moment
    the queue head fits the shared length."""
    from repro.configs import smoke_config
    from repro.models import lm as lm_mod
    from repro.serve.loop import Request, ServeLoop

    cfg = smoke_config("codeqwen1.5-7b")
    params, _ = lm_mod.init_lm(jax.random.PRNGKey(0), cfg, purpose="serve")
    rng = np.random.default_rng(7)
    loop = ServeLoop(params, cfg, batch_slots=2, s_max=48)
    for i, mn in enumerate([2, 10, 2, 2, 2]):
        loop.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=5).astype(np.int32),
            max_new_tokens=mn,
        ))
    done = loop.run()
    assert len(done) == 5
    assert all(len(r.output) in (2, 10) for r in done)
    # slot freed at step 2 admits immediately (no quantum wait): rids
    # 2..4 all ride the freed slot while rid 1 is still decoding
    assert loop.refills >= 3
