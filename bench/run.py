"""The benchmark: one cell of ``BENCHMARK.json``, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``, whose ``kind`` names its
generator, ``bench/traffic/<kind>.py``).  Every metric is a reader of its
own, ``bench/metrics/<metric>.py``, found by the metric's name; so a cell,
a configuration, a traffic mix or a metric is added by adding files and
entries, and this file names none of them.

A run: the device is checked (a TPU, as many chips as the cell asks;
anything else exits 2 and prints no result); the weights are made on the
device from ``--seed``; the program's serving entry is built
(``repro.launch.serve.build_loop`` -> ``PagedServeLoop``) with the TLMAC
lookup serve linears and ``auto`` impl resolution, the decode attention
tuned once per checkout as the program's own smoke does; set-up sends the
traffic's warm prompts and every client's first request, which compiles
(or loads from the cache) the two forward shapes; then the window runs for
``--seconds`` and closes at the first ``step()`` boundary after it.  With
``--trace 1`` the window runs under the profiler and the per-layer
metrics are read from its trace; with ``--trace 0`` the end-to-end ones
are taken by the host clock.  Then the program's state is freed and the
sampled requests are compared with the plain reference (``check.py``).

Earlier lines say what was resolved, compiled and measured.  The last
lines of standard error give each compared number beside its limit; the
last line of standard output is the result object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# the platform a run must find; the CPU rehearsal script is the only
# place that changes it
REQUIRED_PLATFORM = "tpu"
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_START:.1f}s] {msg}",
          file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, trace: int) -> list:
    """The metrics this run reports: the cell's end-to-end metrics
    (``--trace 0``) or its per-layer metrics (``--trace 1``)."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def load_traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def wanted(readers, run, what: str) -> tuple:
    """The scopes or kernels the readers ask the trace reduction for."""
    out = []
    for r in readers:
        if hasattr(r, what):
            out += [x for x in getattr(r, what)(run) if x not in out]
    return tuple(out)


def check_devices(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM or len(devs) < chips:
        raise RuntimeError(
            f"need {chips} {REQUIRED_PLATFORM} device(s), found {len(devs)} "
            f"{devs[0].platform} ({devs[0].device_kind})")
    return devs


class CompileCount:
    """Counts XLA compilations (cache misses) and persistent-cache hits."""

    def __init__(self):
        self.compiles = self.hits = 0

    def __call__(self, name, *_, **__):
        if name == "/jax/compilation_cache/cache_misses":
            self.compiles += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def enable_caches() -> None:
    import jax

    from repro.kernels import autotune

    # always the checkout's own directory, at a fixed path: a cache set by
    # the machine would be shared by every checkout on it
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the tuned decode attention is this checkout's own, like the
    # program's smoke: a winner is not keyed by the code it tuned
    os.environ[autotune.CACHE_ENV] = os.path.join(CACHE_DIR,
                                                  "tlmac_autotune.json")


def tune_attention(loop, cfg) -> dict:
    """The program's decode-attention tuning at the loop's decode shape,
    once per checkout (the winner persists in the autotune file)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import autotune

    B, P, MB = loop.B, loop.spec.page_size, loop.spec.max_blocks
    KV, hd = cfg.n_kv, cfg.kv_head_dim
    key = autotune.attn_shape_key(B, KV, cfg.n_heads // KV, hd, MB, P)
    win = autotune.lookup(key)
    if win is None and jax.default_backend() == "tpu":
        rng = np.random.default_rng(0)
        n_pages = B * MB + 1
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        k, v = (jax.random.normal(kk, (n_pages, P, KV, hd), jnp.bfloat16)
                for kk in keys[:2])
        bt = jnp.asarray(1 + rng.permutation(B * MB).reshape(B, MB),
                         jnp.int32)
        pos = jnp.asarray(rng.integers(0, MB * P, B), jnp.int32)
        q = jax.random.normal(keys[2], (B, 1, cfg.n_heads, hd), jnp.bfloat16)
        win = autotune.tune_attention(q, k, v, bt, pos)
    return win or {"impl": autotune.ATTN_DEFAULT_IMPL}


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def traced_window(drv, seconds: float):
    """The window under the profiler; returns (t0, t1, xplane path, dir)."""
    import jax

    out = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        t0, t1 = drv.window(seconds)
        jax.block_until_ready(drv.loop.caches)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    return t0, t1, paths[0], out


def correctness(drv, plain, dims, ref_mod, spec, seed: int,
                control: bool = False):
    """The sampled requests against the reference; frees the program.
    Returns (correct, readings, limits, the control's readings or None):
    the control is the reference in the precision below the program's,
    put in the program's place on the same positions."""
    import numpy as np

    from bench import check

    want = spec["correct"]
    reqs = check.sample(list(drv.loop.done), seed,
                        int(want["sample_tokens"]))
    prog, served, keep, host = [], [], [], {}
    for r in reqs:
        js, rows = check.program_rows(drv.decodes, r)
        keep.append((r, js))
        served.append(np.asarray(r.output)[js])
        for rec, slot in rows:
            if id(rec) not in host:     # one copy per decode step
                host[id(rec)] = np.asarray(rec.logits).astype(np.float32)
            prog.append(host[id(rec)][slot])
    if not prog:
        raise RuntimeError("no decode position to compare: the window "
                           "finished no request")
    prog = np.stack(prog)
    served = np.concatenate(served).astype(np.int64)
    host.clear()
    # the program's state goes before the reference runs
    drv.decodes.clear()
    drv.loop.caches = None
    drv.loop.params = None
    t0 = time.perf_counter()
    params = ref_mod.expand(plain)
    f32, rnd, low = "float32", want["rounding"], want["control"]
    ref = {}
    for dtype in (f32, rnd, low) if control else (f32, rnd):
        out = []
        for r, js in keep:
            seq = np.concatenate([r.prompt, r.output[:-1]])
            lg = ref_mod.logits(params, dims, seq, len(r.prompt) - 1, dtype)
            out.append(np.asarray(lg, np.float32)[js])
        ref[dtype] = np.concatenate(out)
    nums = check.readings(prog, ref[f32], ref[rnd], served)
    dec = check.decorrelation(prog, ref[f32])
    per_req, at = [], 0
    for _, js in keep:
        per_req.append(round(float(dec[at:at + len(js)].mean()), 4)
                       if js else None)
        at += len(js)
    log(f"reference: {len(reqs)} requests, {len(served)} decode positions, "
        f"{time.perf_counter() - t0:.1f}s in {list(ref)}; decorrelation "
        f"{nums['decorrelation']} ({rnd} rounding alone "
        f"{nums['rounding_decorrelation']}), per request {per_req}")
    ctl = None
    if control:
        ctl = check.readings(ref[low], ref[f32], ref[rnd],
                             ref[low].argmax(-1))
    return (check.verdict(nums, want["limits"]), nums, want["limits"],
            ctl)


def prepare(cell: dict, seed: int):
    """Weights, the serve loop, the traffic and the clients of one run."""
    import jax

    from bench import model
    from bench.clients import Clients
    from repro.launch import serve as serve_launch

    spec = model.load_config(BENCH, cell["config"])
    cfg = model.program_config(spec)
    srv = spec["serving"]
    traffic = load_traffic(cell["traffic"])
    gen_mod = importlib.import_module(f"bench.traffic.{traffic['kind']}")
    ref_mod = importlib.import_module(f"bench.references.{spec['reference']}")
    serve_params, plain = model.make_weights(cfg, spec["weights"], seed)
    jax.block_until_ready(serve_params)
    loop = serve_launch.build_loop(serve_params, cfg, slots=srv["slots"],
                                   s_max=srv["s_max"],
                                   page_size=srv["page_size"],
                                   chunk=srv["chunk"])
    attn = tune_attention(loop, cfg)
    gen = gen_mod.Generator(traffic, vocab=cfg.vocab, slots=srv["slots"],
                            s_max=srv["s_max"], seed=seed)
    return types.SimpleNamespace(
        spec=spec, cfg=cfg, srv=srv, ref_mod=ref_mod, plain=plain,
        loop=loop, attn=attn, drv=Clients(loop, gen),
        dims=model.dims(cfg, spec))


def main(argv=None) -> int:
    args = parse(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find_cell(bench, args.workload)
    try:
        devices = check_devices(int(cell["chips"]))
    except RuntimeError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    import numpy as np

    from bench import counting
    from repro.kernels import ops

    enable_caches()
    clock = CompileCount()
    jax.monitoring.register_event_listener(clock)
    dev = devices[0]
    ctx = prepare(cell, args.seed)
    loop, drv = ctx.loop, ctx.drv
    drv.setup()
    jax.block_until_ready(loop.caches)
    setup_s = time.perf_counter() - T_START
    gemm = sorted({json.dumps(r["config"], sort_keys=True)
                   for r in ops.auto_resolutions()})
    log(f"device {dev.device_kind} x{len(devices)}; resolved lookup-GEMM "
        f"{gemm or ['xla-kscan (auto default)']} decode-attention {ctx.attn}")
    log(f"set-up {setup_s:.2f}s: compiles {clock.compiles} cache hits "
        f"{clock.hits}; shapes {loop.compiled_shapes()}")

    compiles0 = clock.compiles + clock.hits
    dec0, run0 = loop.decode_steps, loop.prefill_tokens_run
    saved0 = loop.prefill_tokens_saved
    if args.trace:
        t0, t1, xplane, trace_dir = traced_window(drv, args.seconds)
    else:
        t0, t1 = drv.window(args.seconds)
        jax.block_until_ready(loop.caches)
    in_window = clock.compiles + clock.hits - compiles0
    mem = peak_bytes(devices[:int(cell["chips"])])
    loop.check_compiled()

    run = types.SimpleNamespace(
        setup_s=setup_s, t0=t0, t1=t1, window_s=t1 - t0, clients=drv,
        loop=loop, slots=ctx.srv["slots"], page=ctx.srv["page_size"],
        chunk=ctx.srv["chunk"], dims=ctx.dims,
        decode_steps=loop.decode_steps - dec0,
        prefill_tokens_run=loop.prefill_tokens_run - run0,
        prefill_tokens_saved=loop.prefill_tokens_saved - saved0,
        peaks=counting.peaks(dev.device_kind)
        if dev.platform == "tpu" else None,
        attn=ctx.attn, trace=None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": mem}
    extra = {}
    chosen = cell_metrics(bench, cell["name"], args.trace)
    readers = [reader(m["name"]) for m in chosen]
    if args.trace:
        from bench import trace as trace_mod

        run.trace = trace_mod.reduce(
            xplane, chips=int(cell["chips"]),
            scopes=wanted(readers, run, "scopes"),
            kernels=wanted(readers, run, "kernels"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        extra["breakdown"] = run.trace["breakdown"]
    metrics = {}
    for m, r in zip(chosen, readers):
        value = r.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    ttft = drv.ttfts(t1)
    log(f"window {run.window_s:.3f}s: decode steps {run.decode_steps}, "
        f"prefill tokens run {run.prefill_tokens_run} saved "
        f"{run.prefill_tokens_saved}, tokens {drv.window_tokens(t0, t1)}, "
        f"requests sent {drv.attempted} "
        f"(ttft samples {len(ttft)}, median "
        f"{float(np.median(ttft)) if ttft else 'n/a'}s), "
        f"compiles in window {in_window}, peak_bytes_in_use {mem}")
    log(f"metrics {json.dumps(metrics)}")

    ok, nums, limits, _ = correctness(drv, ctx.plain, ctx.dims, ctx.ref_mod,
                                      ctx.spec, args.seed)
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    log(f"widest served-token gap below the reference's best "
        f"{nums['widest_gap']} over {nums['positions']} positions "
        "(recorded, not compared)")
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    result = dict(correct=bool(ok), attempted=drv.attempted,
                  failed=drv.failed, metrics=metrics, device=device,
                  **extra, checks=checks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
