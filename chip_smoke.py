"""One-chip smoke test: the paged serve loop at codeqwen1.5-7b's
published widths (32 layers, d_model 4096, 32 MHA heads, d_ff 13440,
vocab 92416), random weights from a seed, on one TPU.

    python chip_smoke.py

One process, no children.  Phases, in order; any failure exits non-zero
and prints no result line:

- device  ``jax.devices()[0]`` must be a TPU.  There is no CPU fallback.
- kernel  the Pallas paged flash-decode kernel, compiled, at the model's
          decode shapes (KV 32, head dim 128, page 16) over an fp and an
          int8 pool, against the ``lax`` paged oracle; then the paged-
          attention tuner at the serve loop's decode shape, where no
          candidate may fail (its winner is what the serve loop's
          ``auto`` resolves to).
- serve   8 requests (prompts of 128-512 tokens, 32 new tokens each)
          through ``repro.launch.serve`` -> ``PagedServeLoop`` with the
          TLMAC lookup serve linears and ``auto`` impl resolution; the
          compile-set check; and the shortest and the longest prompt
          (which spans two prefill chunks) against the dense-cache
          ``ServeLoop`` on the same weights: logits at the last prompt
          position and after ``DECODE_CHECK`` served tokens went through
          the paged decode step, both within ``LOGIT_TOL``, and the
          match of the first ``DENSE_NEW`` greedy tokens (printed).

Earlier lines report device kind, resolved impls, compile seconds,
tokens and wall seconds, peak device bytes and logit agreement.  The
last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or
to the checkout's ``.jax_cache/``.  The autotune cache is always
``.jax_cache/tlmac_autotune.json`` in the checkout, so ``auto`` resolves
from this checkout's own tuning.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import autotune, ops, paged  # noqa: E402
from repro.kernels.flash_decode import flash_decode  # noqa: E402
from repro.launch import serve as serve_launch  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.serve.loop import Request, ServeLoop  # noqa: E402

ARCH = "codeqwen1.5-7b"
SEED = 0
N_REQUESTS, MAX_NEW, SLOTS, PAGE = 8, 32, 8, 16
# the dense reference runs each checked request solo, one forward per
# token: it generates the first DENSE_NEW tokens, not all MAX_NEW, to
# keep the script inside its time limit at full width
DENSE_NEW = 8
# logits are also compared after this many served tokens went through
# the paged decode step
DECODE_CHECK = 2
# Kernel vs oracle: both contract the same bf16/int8 pages in f32 (the
# oracle at "highest" matmul precision), so they differ only by f32
# summation order and exp rounding, orders of magnitude below 1e-3.
KERNEL_TOL = 1e-3
# Paged vs dense logits, as max |diff| over max |dense logit|: the
# logits are bf16 (2^-8 relative steps), and paged chunk prefill and
# decode (flash-decode's online softmax over pages) sum softmax terms in
# another order than the one-shot dense prefill, which may move a bf16
# rounding; 2e-2 is ~5 bf16 steps of the largest logit.
LOGIT_TOL = 2e-2


class CompileClock:
    """Sums JAX's backend-compile durations, split by whether the
    persistent cache served the executable (warm) or XLA compiled it
    (cold)."""

    def __init__(self):
        self.cold_s = self.warm_s = 0.0
        self.hits = self.misses = 0
        self._hit = False

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
            self._hit = True
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            if self._hit:
                self.warm_s += secs
            else:
                self.cold_s += secs
            self._hit = False

    def __enter__(self):
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._duration)

    def line(self) -> str:
        return (f"cold_s={self.cold_s} warm_s={self.warm_s} "
                f"cache_hits={self.hits} cache_misses={self.misses}")


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:.1f}s] {msg}",
          flush=True)


def phase_device() -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {d.platform!r} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _pool(rng, n_pages, P, KV, hd, kv_dtype):
    """A random pool dict for one layer: bf16 pages, or int8 codes +
    scales quantised from them."""
    kv = {n: jnp.asarray(rng.normal(size=(n_pages, P, KV, hd)),
                         jnp.bfloat16) for n in ("k", "v")}
    if kv_dtype == "fp":
        return kv
    qspec = paged.KVQuantSpec(kv_dtype)
    k, ks = paged.quantise_kv(kv["k"], qspec)
    v, vs = paged.quantise_kv(kv["v"], qspec)
    return {"k": k, "v": v, "ks": ks, "vs": vs}


def phase_kernel(*, B: int, KV: int, rep: int, hd: int, P: int, MB: int,
                 seed: int = SEED) -> dict:
    """flash_decode vs the lax oracle (fp and int8 pools), then the
    attention tuner at this decode shape.  Returns the max errors and
    the tuned winner."""
    rng = np.random.default_rng(seed)
    n_pages = B * MB + 1
    bt = jnp.asarray(1 + rng.permutation(B * MB).reshape(B, MB)
                     .astype(np.int32))
    s_alloc = MB * P
    lens = rng.integers(1, s_alloc + 1, size=B).astype(np.int32)
    lens[:4] = [1, P, P + 1, s_alloc][:min(4, B)]   # page/slot edges
    positions = jnp.asarray(lens - 1)
    q = jnp.asarray(rng.normal(size=(B, 1, KV * rep, hd)), jnp.float32)
    out = {}
    for kv_dtype in ("fp", "int8"):
        kv = _pool(rng, n_pages, P, KV, hd, kv_dtype)
        qspec = paged.KVQuantSpec(kv_dtype)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(paged.dispatch_attention(
                {"impl": "lax"}, q, kv["k"], kv["v"], bt, positions,
                k_scales=kv.get("ks"), v_scales=kv.get("vs"), qspec=qspec))
        got = np.asarray(flash_decode(
            q.reshape(B, KV, rep, hd), kv["k"], kv["v"], bt,
            jnp.asarray(lens), k_scales=kv.get("ks"), v_scales=kv.get("vs"),
            kv_dtype=kv_dtype)).reshape(want.shape)
        err = float(np.max(np.abs(got - want)))
        log(f"kernel flash_decode[{kv_dtype}] B={B} KV={KV} rep={rep} "
            f"hd={hd} P={P} MB={MB}: max_abs_err={err} (tol {KERNEL_TOL})")
        if not np.allclose(got, want, rtol=KERNEL_TOL, atol=KERNEL_TOL):
            raise AssertionError(
                f"flash_decode[{kv_dtype}] disagrees with the lax oracle: "
                f"max_abs_err={err}")
        out[f"flash_{kv_dtype}_max_abs_err"] = err

    # the serve loop's decode attention resolves from this tuning
    kv = _pool(rng, n_pages, P, KV, hd, "fp")
    qb = q.astype(jnp.bfloat16)
    key = autotune.attn_shape_key(B, KV, rep, hd, MB, P)
    winner = autotune.lookup(key)
    if winner is None:
        winner = autotune.tune_attention(qb, kv["k"], kv["v"], bt, positions)
    failed = autotune.snapshot_stats()["failed_candidates"]
    log(f"attention tuner {key}: winner={winner} "
        f"failed_candidates={failed}")
    if failed:
        raise AssertionError(f"tuner candidates failed: {failed}")
    out["attn_winner"] = winner
    return out


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def phase_serve(cfg, *, min_len: int, max_len: int, s_max: int, chunk: int,
                n_requests: int = N_REQUESTS, max_new: int = MAX_NEW,
                slots: int = SLOTS, page: int = PAGE,
                dense_new: int = DENSE_NEW,
                seed: int = SEED) -> dict:
    t0 = time.perf_counter()
    with CompileClock() as clock:
        params = serve_launch.init_params(cfg, seed)
        jax.block_until_ready(params)
    log(f"init params: {time.perf_counter() - t0}s compile {clock.line()}")

    reqs = serve_launch.make_requests(cfg, n_requests, min_len, max_len,
                                      max_new, seed)
    loop = serve_launch.build_loop(params, cfg, slots=slots, s_max=s_max,
                                   page_size=page, chunk=chunk)
    log(f"loop: {type(loop).__name__} kv_pool_bytes={loop.kv_pool_bytes()}")
    with CompileClock() as clock:
        done, wall = serve_launch.serve(loop, reqs)
    tokens = sum(len(r.output) for r in done)
    chunks = loop.prefill_tokens_run // chunk
    log(f"served requests={len(done)} tokens={tokens} wall_s={wall} "
        f"tok_per_s={tokens / wall} decode_steps={loop.decode_steps} "
        f"prefill_chunks={chunks} "
        f"s_per_forward={wall / (loop.decode_steps + chunks)}")
    log(f"serve compile: {clock.line()}")
    log(f"compiled shapes: {loop.compiled_shapes()}")
    if len(done) != n_requests or any(
            len(r.output) != max_new for r in done):
        raise AssertionError(
            f"served {len(done)}/{n_requests} requests, outputs "
            f"{sorted(len(r.output) for r in done)} (want {max_new} each)")
    loop.check_compiled()

    attn_key = autotune.attn_shape_key(slots, cfg.n_kv,
                                       cfg.n_heads // cfg.n_kv,
                                       cfg.d_model // cfg.n_heads,
                                       loop.spec.max_blocks, page)
    attn = autotune.lookup(attn_key) or {"impl": autotune.ATTN_DEFAULT_IMPL}
    gemm = sorted({json.dumps(r["config"], sort_keys=True)
                   for r in ops.auto_resolutions()})
    log(f"resolved impls: lookup-GEMM {gemm} decode-attention {attn}")

    # reference: the dense-cache loop, each checked request run solo.
    # The checked requests are the shortest and the longest prompt, so
    # one prefill spans several chunks (later chunks attend to pages the
    # earlier ones wrote).
    by_rid = {r.rid: r for r in done}
    order = sorted(reqs, key=lambda r: len(r.prompt))
    check = [order[0], order[-1]]
    if len(check[-1].prompt) <= chunk:
        raise AssertionError(
            f"no checked prompt spans two {chunk}-token chunks: "
            f"{[len(r.prompt) for r in check]}")
    dense_new = min(dense_new, max_new)
    dense = ServeLoop(params, cfg, batch_slots=1,
                      s_max=max(len(r.prompt) for r in check) + dense_new)
    errs = {"prompt": [], "decode": []}
    argmax_equal, match, total = [], 0, 0
    with CompileClock() as clock:
        for r in check:
            dense.submit(Request(rid=r.rid, prompt=r.prompt.copy(),
                                 max_new_tokens=dense_new))
            want_out = dense.run()[-1].output
            got_out = by_rid[r.rid].output
            match += int(np.sum(got_out[:dense_new] == want_out))
            total += len(want_out)
            # last prompt position (chunk prefill), then the position
            # after DECODE_CHECK served tokens (paged decode steps: KV
            # writes, the tuned decode attention) vs the dense prefill
            # of the same tokens
            cont = got_out[:DECODE_CHECK]
            for name, extra in (("prompt", ()), ("decode", cont)):
                t0 = time.perf_counter()
                got = loop.prompt_logits(r.prompt, extra)
                log(f"paged scoring: {-(-len(r.prompt) // chunk)} chunks + "
                    f"{len(extra)} decode steps in "
                    f"{time.perf_counter() - t0}s")
                want = dense.prompt_logits(
                    np.concatenate([r.prompt, np.asarray(extra, np.int32)]))
                errs[name].append(float(np.max(np.abs(got - want))
                                        / np.max(np.abs(want))))
                argmax_equal.append(bool(np.argmax(got) == np.argmax(want)))
    log(f"dense reference compile: {clock.line()}")
    log(f"logit agreement vs dense ServeLoop (prompt lengths "
        f"{[len(r.prompt) for r in check]}, chunk {chunk}): "
        f"max_abs_diff/max_abs_logit last-prompt={errs['prompt']} "
        f"after-{DECODE_CHECK}-decode-steps={errs['decode']} "
        f"(tol {LOGIT_TOL}) argmax_equal={argmax_equal}")
    log(f"greedy token match fraction: {match}/{total} = {match / total}")
    log(f"peak_bytes_in_use={_peak_bytes()}")
    worst = max(errs["prompt"] + errs["decode"])
    if worst > LOGIT_TOL:
        raise AssertionError(
            f"paged logits disagree with the dense path: {errs}")
    return {"tokens": tokens, "wall_s": wall, "logit_rel_err": errs,
            "greedy_match": match / total}


def run(cfg, *, kernel_shape: dict, serve_shape: dict) -> dict:
    """Every phase in order; returns the result line's object."""
    device = phase_device()
    log(f"device: {device}")
    phase_kernel(**kernel_shape)
    phase_serve(cfg, **serve_shape)
    return {"ok": True, "device": device}


def main() -> int:
    cache_dir = compile_cache.enable_compile_cache()
    # always the checkout's own file, even where the XLA cache is
    # shared: a tuned winner is not keyed by the code that was tuned
    os.environ[autotune.CACHE_ENV] = os.path.join(
        compile_cache.DEFAULT_DIR, "tlmac_autotune.json")
    cfg = get_config(ARCH)
    min_len, max_len, s_max, chunk = serve_launch.FULL_SHAPE
    log(f"compile cache: {cache_dir}")
    try:
        result = run(
            cfg,
            kernel_shape=dict(B=SLOTS, KV=cfg.n_kv,
                              rep=cfg.n_heads // cfg.n_kv,
                              hd=cfg.d_model // cfg.n_heads, P=PAGE,
                              MB=-(-s_max // PAGE)),
            serve_shape=dict(min_len=min_len, max_len=max_len, s_max=s_max,
                             chunk=chunk),
        )
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
