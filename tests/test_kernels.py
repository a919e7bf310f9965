"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes / bit-widths / G — bit-exact (integer semantics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal containers: deterministic fallback engine
    from _hypothesis_fallback import given, settings, st

from repro.core.tlmac import compile as tc
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.bitplanes import pack_bitplanes_pallas
from repro.kernels.tlmac_gemm import tlmac_gemm


def _setup(seed, K, N, M, B_w, B_a, G, d_p=64):
    rng = np.random.default_rng(seed)
    w = rng.integers(-(2 ** (B_w - 1)), 2 ** (B_w - 1), size=(K, N))
    plan = tc.compile_layer(w, B_w=B_w, B_a=B_a, G=G, d_p=d_p,
                            anneal_iters=100, seed=seed)
    a = rng.integers(0, 2**B_a, size=(M, K))
    return (jnp.asarray(a), jnp.asarray(w), jnp.asarray(plan.table),
            jnp.asarray(plan.exec_idx), jnp.asarray(plan.step_cluster))


SWEEP = [
    # (K, N, M, B_w, B_a, G)
    (16, 64, 4, 2, 2, 2),
    (24, 64, 8, 3, 3, 3),
    (32, 128, 16, 3, 4, 4),
    (48, 64, 5, 4, 4, 6),
    (64, 192, 33, 2, 3, 4),
    # k-chunk edges of 'xla-kscan' at G 4: kg 576 and 1440 (the serve
    # widths 2304 and 5760) scan by the divisors 192 and 240 unpadded;
    # kg 257 has no divisor in [64, 256] and takes the padded k-groups
    (2304, 128, 5, 3, 3, 4),
    (5760, 128, 3, 3, 3, 4),
    (1028, 128, 4, 3, 3, 4),
]


@pytest.mark.parametrize("K,N,M,B_w,B_a,G", SWEEP)
def test_tlmac_matmul_all_impls_bitexact(K, N, M, B_w, B_a, G):
    a, w, t, e, c = _setup(K * 7 + G, K, N, M, B_w, B_a, G)
    ref = np.asarray(ops.dense_int_matmul(a, w))
    for impl in ("ref", "xla", "xla-kscan", "xla-flat",
                 "pallas", "pallas-onehot", "fused"):
        out = np.asarray(
            ops.tlmac_matmul(a, t, e, c, B_a=B_a, G=G, N=N, impl=impl)
        )
        assert np.array_equal(out, ref), impl


# kg -> the k-chunk 'xla-kscan' scans by at the default chunk 256: the
# serve widths of minicpm-2b (576, 1440) and codeqwen1.5-7b (1024, 3360),
# kg below the chunk, and kg 257 (prime: padded by chunks of 256)
@pytest.mark.parametrize("kg,want", [(576, 192), (1440, 240), (1024, 256),
                                     (3360, 240), (40, 40), (257, 256)])
def test_kscan_chunk_divides_kg_where_it_can(kg, want):
    assert ops._kscan_chunk(kg, 256) == want


@given(
    seed=st.integers(0, 1000),
    B_w=st.integers(2, 4),
    B_a=st.integers(2, 4),
    G=st.sampled_from([2, 3, 4]),
    M=st.integers(1, 9),
)
@settings(max_examples=15, deadline=None)
def test_tlmac_matmul_property(seed, B_w, B_a, G, M):
    K, N = 4 * G, 64
    a, w, t, e, c = _setup(seed, K, N, M, B_w, B_a, G)
    ref = np.asarray(ops.dense_int_matmul(a, w))
    out = np.asarray(ops.tlmac_matmul(a, t, e, c, B_a=B_a, G=G, N=N, impl="xla"))
    assert np.array_equal(out, ref)


@pytest.mark.parametrize("B_a,G,M,K", [(2, 2, 3, 8), (3, 4, 7, 16), (4, 3, 2, 9)])
def test_pack_bitplanes_pallas_vs_ref(B_a, G, M, K):
    K = K - (K % G)
    rng = np.random.default_rng(M)
    a = jnp.asarray(rng.integers(0, 2**B_a, size=(M, K)))
    ref = kref.pack_bitplanes_ref(a, B_a, G)
    pal = pack_bitplanes_pallas(a, B_a=B_a, G=G)
    assert np.array_equal(np.asarray(ref), np.asarray(pal))


def test_pallas_kernel_blocking_edges():
    """M, KG not multiples of block sizes exercise the padding path."""
    a, w, t, e, c = _setup(99, 40, 128, 37, 3, 3, 4)
    ref = np.asarray(ops.dense_int_matmul(a, w))
    codes = kref.pack_bitplanes_ref(a, 3, 4)
    n_arr = t.shape[1]
    rb = (c.astype(jnp.int32)[:, None] * n_arr + e.astype(jnp.int32)).reshape(
        128 // 64, 10, 64
    )
    out = tlmac_gemm(codes.astype(jnp.int32), rb, t.reshape(-1, 16),
                     B_a=3, G=4, N=128, bm=16, bk=4)
    assert np.array_equal(np.asarray(out), ref)


def test_kernel_dtype_sweep():
    """int8/int16/int32 index and code dtypes all agree."""
    a, w, t, e, c = _setup(5, 32, 64, 8, 3, 3, 4)
    ref = np.asarray(ops.dense_int_matmul(a, w))
    for dt in (jnp.int8, jnp.int16, jnp.int32):
        out = np.asarray(ops.tlmac_matmul(
            a.astype(dt), t, e.astype(jnp.int16), c.astype(jnp.int8),
            B_a=3, G=4, N=64, impl="xla",
        ))
        assert np.array_equal(out, ref), dt


def test_bitserial_ablation_bitexact():
    """Eq. 3 without the lookup must equal the dense integer GEMM."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(0, 8, size=(9, 24)))
    w = jnp.asarray(rng.integers(-4, 4, size=(24, 32)))
    ref = ops.dense_int_matmul(a, w)
    out = ops.bitserial_matmul(a, w, B_a=3)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_clustered_kernel_bitexact():
    """Cluster-scheduled Pallas kernel (grid coord == the paper's select
    signal; per-cluster table slice in VMEM) == dense integer GEMM."""
    from repro.kernels.tlmac_clustered import cluster_schedule, run_clustered

    rng = np.random.default_rng(5)
    for (K, N, M, B_w, B_a, G, bk) in [
        (64, 64, 21, 3, 3, 4, 4),
        (24, 32, 7, 2, 2, 3, 2),
        (48, 128, 9, 4, 4, 4, 8),
    ]:
        w = rng.integers(-(2 ** (B_w - 1)), 2 ** (B_w - 1), size=(K, N))
        plan = tc.compile_layer(w, B_w=B_w, B_a=B_a, G=G, d_p=N,
                                anneal_iters=100, seed=0)
        a = rng.integers(0, 2**B_a, size=(M, K))
        ref = np.asarray(ops.dense_int_matmul(jnp.asarray(a), jnp.asarray(w)))
        out = np.asarray(run_clustered(plan, a, B_a=B_a, bk=bk, bm=16))
        assert np.array_equal(out, ref), (K, N, G)
        # the schedule really is per-cluster: padded steps x clusters
        sched = cluster_schedule(plan, bk=bk)
        assert sched["order"].shape[0] == plan.N_clus
