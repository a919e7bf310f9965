"""Shape-keyed autotuner backing ``ops.tlmac_matmul(impl="auto")``.

FINN-R's lesson (arXiv 1809.04570) is that a lookup datapath only wins
end-to-end when the folding/parallelism is *tuned per layer shape*; our
analogue is the (impl × bm × bk × chunk × gather) configuration of the
lookup GEMM.  The tuner:

- times each candidate on the concrete operands (median of ``reps``
  timed calls after a compile/warmup call),
- verifies every candidate bit-exactly against ``ref.tlmac_matmul_ref``
  before trusting its timing (a fast wrong kernel must never win),
- persists winners to a JSON cache keyed by
  ``(backend, M, K, N, B_a, G, D_p, R)`` so later processes — and
  tracing contexts, which cannot time — reuse them.

Cache file: ``$REPRO_TLMAC_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/tlmac_autotune.json``.  Format (one entry per key)::

    {
      "v1|cpu|M64,K256,N256,Ba3,G4,dp64,R1024": {
        "config": {"impl": "xla-flat"},
        "us": 2291.4,
        "baseline_us": {"xla": 3649.2},
      },
      ...
    }

``lookup`` is safe to call during jit tracing (pure host-side dict
read); ``tune`` needs concrete arrays and is called eagerly — first
concrete ``impl="auto"`` call on a new shape tunes once, then hits the
cache forever.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

try:
    import fcntl
except ImportError:  # non-POSIX: fall back to merge-without-lock
    fcntl = None

CACHE_ENV = "REPRO_TLMAC_AUTOTUNE_CACHE"
DEFAULT_IMPL = "xla"
_SCHEMA = "v1"

_lock = threading.RLock()
_cache: Optional[Dict[str, Any]] = None
_cache_file: Optional[str] = None
# bumped on every record()/reset_cache(): lets callers (ops.tlmac_matmul)
# memoise resolved configs and re-resolve only when the cache changed
generation: int = 0

# process-local observability: which keys hit/missed the cache and which
# were (re-)tuned this process, kept in the unified serve-telemetry
# metrics registry (serve/telemetry.MetricsRegistry) so the serving
# stack's ``metrics()`` snapshot covers the autotuner alongside the
# other subsystems.  The benches emit these into their JSON artifacts
# so a CI bench run is diagnosable after the fact — "the cache was
# overridden" alone says nothing about WHAT was re-tuned.  The registry
# is created lazily: serve.telemetry must not be imported while the
# serve package's own import chain (models -> kernels -> here) is
# still executing.
_stats_lock = threading.Lock()
_registry = None
_tuned_keys: List[str] = []
# candidates dropped by a sweep — raised, or disagreed with the oracle —
# each named with its shape key and reason, never dropped in silence
_failed: List[Dict[str, Any]] = []


def registry():
    """The autotuner's process-local MetricsRegistry (lazy)."""
    global _registry
    with _stats_lock:
        if _registry is None:
            from repro.serve.telemetry import MetricsRegistry
            _registry = MetricsRegistry()
        return _registry


def reset_stats() -> None:
    registry().reset()
    with _stats_lock:
        _tuned_keys.clear()
        _failed.clear()


def snapshot_stats() -> Dict[str, Any]:
    """Copy of the process-local lookup/tune counters (bench artifacts
    and ``PagedServeLoop.metrics()['autotune']``)."""
    reg = registry()
    with _stats_lock:
        return {"lookup_hits": int(reg.get_counter("lookup_hits")),
                "lookup_misses": int(reg.get_counter("lookup_misses")),
                "tuned_keys": list(_tuned_keys),
                "failed_candidates": [dict(f) for f in _failed]}


def _candidate_failed(key: str, config: Dict[str, Any], reason: str) -> None:
    registry().inc("candidate_failures")
    with _stats_lock:
        _failed.append({"key": key, "config": dict(config),
                        "reason": reason})


# ---------------------------------------------------------------------------
# cache persistence
# ---------------------------------------------------------------------------


def cache_path() -> str:
    return os.environ.get(CACHE_ENV) or os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "tlmac_autotune.json"
    )


def _load() -> Dict[str, Any]:
    """Load (and memoise) the cache; reloads if the env path changed."""
    global _cache, _cache_file
    path = cache_path()
    with _lock:
        if _cache is not None and _cache_file == path:
            return _cache
        data: Dict[str, Any] = {}
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        _cache, _cache_file = data, path
        return data


def _save() -> None:
    global _cache
    path = cache_path()
    with _lock:
        data = _cache or {}
        # merge the latest on-disk state under an exclusive file lock:
        # another process may persist winners between our read and our
        # os.replace — without the lock that window loses their update
        # (read-modify-write race).  In-memory entries are newer for any
        # key we both touched, so they win the merge.
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            lock_f = open(path + ".lock", "w")
        except OSError:
            return  # read-only FS: tuning still works, just not persisted
        try:
            if fcntl is not None:
                fcntl.flock(lock_f, fcntl.LOCK_EX)
            disk: Dict[str, Any] = {}
            try:
                with open(path) as f:
                    disk = json.load(f)
            except (OSError, ValueError):
                disk = {}
            disk.update(data)
            _cache = data = disk
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass  # read-only FS: tuning still works, just not persisted
        finally:
            lock_f.close()


def reset_cache() -> None:
    """Drop the in-memory cache (tests; or after changing the env path)."""
    global _cache, _cache_file, generation
    with _lock:
        _cache, _cache_file = None, None
        generation += 1


# ---------------------------------------------------------------------------
# keys and candidates
# ---------------------------------------------------------------------------


def shape_key(M: int, K: int, N: int, *, B_a: int, G: int, D_p: int,
              R: int) -> str:
    backend = jax.default_backend()
    return (f"{_SCHEMA}|{backend}|M{M},K{K},N{N},"
            f"Ba{B_a},G{G},dp{D_p},R{R}")


def candidates(M: int, K: int, N: int, *, B_a: int, G: int,
               include_pallas: Optional[bool] = None) -> List[Dict[str, Any]]:
    """Candidate configs for a shape.  The Pallas lookup kernels
    ('fused', 'pallas') join only when asked for (``include_pallas`` or
    ``REPRO_TLMAC_TUNE_PALLAS=1``): off-TPU they run in interpret mode,
    whose timings are meaningless, and the TPU compiler refuses them
    (a ``(bm, 1, D_p)`` output block, then the in-kernel ``jnp.take``
    table gather) until they are rebuilt.

    'pallas-onehot' is NOT a default candidate: its MXU-only addressing
    measures ~2 orders of magnitude slower than every other impl at
    bench shapes (~300 ms/call vs 1-4 ms), so sweeping it burns tuning
    wall-clock for a candidate that never wins.  It stays reachable via
    explicit ``impl='pallas-onehot'`` or ``REPRO_TLMAC_TUNE_ONEHOT=1``."""
    kg = K // G
    cands: List[Dict[str, Any]] = [{"impl": "ref"}, {"impl": "xla-flat"}]
    for chunk in (64, 128, 256, 512):
        if chunk <= max(64, kg):
            cands.append({"impl": "xla", "chunk": chunk})
            cands.append({"impl": "xla-kscan", "chunk": chunk})
    if include_pallas is None:
        include_pallas = os.environ.get("REPRO_TLMAC_TUNE_PALLAS") == "1"
    if include_pallas:
        include_onehot = os.environ.get("REPRO_TLMAC_TUNE_ONEHOT") == "1"
        for gather in ("take",) + (("onehot",) if include_onehot else ()):
            for bm in (64, 128, 256):
                for bk in (64, 128):
                    cands.append({"impl": "fused", "bm": bm, "bk": bk,
                                  "gather": gather})
        cands.append({"impl": "pallas"})
        if include_onehot:
            cands.append({"impl": "pallas-onehot"})
    return cands


# ---------------------------------------------------------------------------
# lookup / record / tune
# ---------------------------------------------------------------------------


def lookup(key: str) -> Optional[Dict[str, Any]]:
    """Winning config for a shape key, or None.  Trace-safe."""
    entry = _load().get(key)
    registry().inc("lookup_hits" if entry else "lookup_misses")
    return dict(entry["config"]) if entry else None


def record(key: str, config: Dict[str, Any], us: float,
           baseline_us: Optional[Dict[str, float]] = None) -> None:
    global generation
    with _lock:
        data = _load()
        data[key] = {"config": config, "us": us,
                     "baseline_us": baseline_us or {}}
        generation += 1
        _save()
    registry().inc("tunes")
    with _stats_lock:
        if key not in _tuned_keys:
            _tuned_keys.append(key)


def _time(fn, reps: int) -> float:
    fn()  # compile + warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def _ab(fn_a, fn_b, reps: int) -> Tuple[float, float]:
    """Median us/call of two impls measured INTERLEAVED so machine-load
    spikes hit both equally — the sweep's sequential per-candidate
    medians drift under shared-runner load, and a near-tie decided by
    that drift must not unseat the baseline."""
    ta, tb = [], []
    for _ in range(reps):
        t0 = time.perf_counter(); fn_a(); ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); fn_b(); tb.append(time.perf_counter() - t0)
    return float(np.median(ta) * 1e6), float(np.median(tb) * 1e6)


def _rematch_and_record(key, best_cfg, best_us, baseline_cfg, baseline_us,
                        make_run, reps: int, baseline_label: str):
    """Shared commit policy for every tuner (GEMM and attention): the
    winner must beat the baseline in an INTERLEAVED re-match, not just
    in the sequential sweep — committing a near-tie decided by load
    drift is how 'auto' ends up measurably slower than the default at
    the same shape.  ``make_run(cfg)`` returns a warmed zero-arg timed
    callable (the sweep's own, so nothing recompiles here)."""
    if baseline_us is not None and best_cfg != baseline_cfg:
        best_us, baseline_us = _ab(make_run(best_cfg),
                                   make_run(baseline_cfg), max(reps, 9))
        if best_us >= baseline_us:
            best_cfg, best_us = baseline_cfg, baseline_us
    baseline = ({baseline_label: baseline_us}
                if baseline_us is not None else {})
    record(key, best_cfg, best_us, baseline)
    return dict(best_cfg)


def tune(
    a_codes,
    table,
    exec_idx,
    step_cluster,
    *,
    B_a: int,
    G: int,
    N: int,
    reps: int = 5,
    cands: Optional[List[Dict[str, Any]]] = None,
    verify: bool = True,
) -> Dict[str, Any]:
    """Time candidates on concrete operands; persist and return the
    winner's config.  Candidates that raise (shape constraints, a
    compiler refusal) or are not bit-exact are discarded and named in
    ``snapshot_stats()['failed_candidates']``."""
    from repro.kernels import ops, ref as _ref

    M, K = a_codes.shape
    D_p = exec_idx.shape[1]
    key = shape_key(M, K, N, B_a=B_a, G=G, D_p=D_p,
                    R=int(np.prod(table.shape[:-1])))
    if cands is None:
        cands = candidates(M, K, N, B_a=B_a, G=G)

    want = (
        np.asarray(_ref.tlmac_matmul_ref(
            a_codes, table, exec_idx, step_cluster, B_a, G, N))
        if verify else None
    )
    # the default-impl baseline is ALWAYS timed alongside the sweep —
    # a cached winner that measures slower than what impl='xla' would
    # have dispatched anyway is a regression, not a win (the committed
    # winner must keep speedup_auto_vs_xla >= 1 at tune time)
    baseline_cfg = {"impl": DEFAULT_IMPL}
    if not any(c == baseline_cfg for c in cands):
        cands = list(cands) + [baseline_cfg]
    best_cfg, best_us = None, float("inf")
    baseline_us = None
    for cand in cands:
        def run(cand=cand):
            return ops.dispatch_config(
                cand, a_codes, table, exec_idx, step_cluster,
                B_a=B_a, G=G, N=N,
            ).block_until_ready()
        try:
            if want is not None and not np.array_equal(np.asarray(run()), want):
                _candidate_failed(key, cand, "not bit-exact vs the oracle")
                continue
            us = _time(run, reps)
        except Exception as e:
            _candidate_failed(key, cand, f"{type(e).__name__}: {e}"[:500])
            continue
        if cand == baseline_cfg:
            baseline_us = us
        if us < best_us:
            best_cfg, best_us = cand, us
    if best_cfg is None:  # everything failed: fall back, don't persist
        return {"impl": DEFAULT_IMPL}

    def make_run(cfg):
        return lambda: ops.dispatch_config(
            cfg, a_codes, table, exec_idx, step_cluster,
            B_a=B_a, G=G, N=N,
        ).block_until_ready()

    return _rematch_and_record(key, best_cfg, best_us, baseline_cfg,
                               baseline_us, make_run, reps, "xla")


def lookup_or_default(M: int, K: int, N: int, *, B_a: int, G: int,
                      D_p: int, R: int,
                      default_impl: str = DEFAULT_IMPL) -> Dict[str, Any]:
    """Trace-safe resolution: cached winner, else the given default."""
    cfg = lookup(shape_key(M, K, N, B_a=B_a, G=G, D_p=D_p, R=R))
    return cfg if cfg is not None else {"impl": default_impl}


# ---------------------------------------------------------------------------
# paged decode attention (kernels/paged.py) — same tuner, same cache
# ---------------------------------------------------------------------------

ATTN_DEFAULT_IMPL = "lax"


def attn_shape_key(B: int, KV: int, rep: int, hd: int, MB: int, P: int,
                   window=None, kv_dtype: str = "fp") -> str:
    backend = jax.default_backend()
    w = "none" if window is None else int(window)
    # quantised pools get their own keys (an int8 winner must never
    # serve an fp shape); fp keys stay byte-identical to the historical
    # format so existing caches — and the CI actions/cache entries —
    # survive this schema extension
    q = "" if kv_dtype == "fp" else f",q{kv_dtype}"
    return (f"{_SCHEMA}|{backend}|attn|B{B},KV{KV},rep{rep},hd{hd},"
            f"MB{MB},P{P},W{w}{q}")


def attention_candidates(
        include_pallas: Optional[bool] = None) -> List[Dict[str, Any]]:
    """Paged-attention candidates.  The Pallas flash kernel joins only
    where it is compiled (TPU) — interpret timings are meaningless —
    unless forced with ``REPRO_TLMAC_TUNE_PALLAS=1``."""
    cands: List[Dict[str, Any]] = [{"impl": "lax"}, {"impl": "flash-lax"}]
    if include_pallas is None:
        include_pallas = (
            jax.default_backend() == "tpu"
            or os.environ.get("REPRO_TLMAC_TUNE_PALLAS") == "1"
        )
    if include_pallas:
        for s in (1, 2, 4, 8):
            cands.append({"impl": "flash", "n_splits": s})
    return cands


def tune_attention(
    q,
    k_pages,
    v_pages,
    block_table,
    positions,
    *,
    window=None,
    reps: int = 5,
    cands: Optional[List[Dict[str, Any]]] = None,
    verify: bool = True,
    k_scales=None,
    v_scales=None,
    qspec=None,
) -> Dict[str, Any]:
    """Verify-then-time tuning for paged decode attention.

    Same contract as ``tune`` with one necessary relaxation: the lookup
    GEMMs are integer and candidates must be *bit*-exact, but attention
    is float and the flash paths legitimately reassociate the softmax
    reduction — candidates are verified against the ``lax`` oracle to a
    tolerance far below anything that could flip a greedy argmax, then
    timed.  The winner persists under an ``attn|`` shape key in the
    same JSON cache.  Quantised pools (``qspec``, with their
    ``k_scales``/``v_scales`` sidecars) tune under their own kv-dtype
    key, each candidate verified against the *dequantising* lax oracle."""
    from repro.kernels import paged

    qspec = qspec or paged.KVQuantSpec()
    B, _, H, hd = q.shape
    KV = k_pages.shape[2]
    key = attn_shape_key(B, KV, H // KV, hd, block_table.shape[1],
                         k_pages.shape[1], window, kv_dtype=qspec.dtype)
    if cands is None:
        cands = attention_candidates()
    want = (
        np.asarray(paged.dispatch_attention(
            {"impl": "lax"}, q, k_pages, v_pages, block_table, positions,
            window=window, k_scales=k_scales, v_scales=v_scales,
            qspec=qspec), np.float32)
        if verify else None
    )
    best_cfg, best_us = None, float("inf")
    baseline_us = None
    runners: Dict[str, Any] = {}   # warmed jitted callables by config
    for cand in cands:
        # time the candidate JITTED — that is how it runs inside the
        # serve graph; eager timing would charge flash-lax's fori_loop
        # one dispatch per page block and invert the ranking
        jitted = jax.jit(
            lambda q_, k_, v_, bt_, pos_, cand=cand:
            paged.dispatch_attention(cand, q_, k_, v_, bt_, pos_,
                                     window=window, k_scales=k_scales,
                                     v_scales=v_scales, qspec=qspec)
        )

        def run(jitted=jitted):
            return jitted(
                q, k_pages, v_pages, block_table, positions
            ).block_until_ready()
        try:
            if want is not None and not np.allclose(
                    np.asarray(run(), np.float32), want,
                    rtol=2e-2, atol=2e-2):
                _candidate_failed(key, cand, "disagrees with the lax oracle")
                continue
            us = _time(run, reps)
        except Exception as e:
            _candidate_failed(key, cand, f"{type(e).__name__}: {e}"[:500])
            continue
        runners[json.dumps(cand, sort_keys=True)] = run
        if cand == {"impl": ATTN_DEFAULT_IMPL}:
            baseline_us = us
        if us < best_us:
            best_cfg, best_us = cand, us
    if best_cfg is None:
        return {"impl": ATTN_DEFAULT_IMPL}
    return _rematch_and_record(
        key, best_cfg, best_us, {"impl": ATTN_DEFAULT_IMPL}, baseline_us,
        lambda cfg: runners[json.dumps(cfg, sort_keys=True)], reps, "lax",
    )
