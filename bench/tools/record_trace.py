"""Record the small device trace that ``bench/tests/test_trace.py`` reads.

    python bench/tools/record_trace.py OUT_DIR CONFIG

On one TPU: a one-layer cut of CONFIG (``bench/tests/data`` holds the
trace of ``minicpm-2b-l10``; vocabulary cut to 4096) served by
``PagedServeLoop`` with the Pallas flash-decode kernel, three short
requests, traced after a warm-up run.  Writes the ``.xplane.pb`` to
OUT_DIR and
prints the planes, lines and the stats of the first device events, so
that the trace's naming can be read by hand.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import model  # noqa: E402


def main() -> int:
    out = sys.argv[1]
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    from repro.launch import serve as serve_launch
    from repro.serve.loop import Request

    spec = model.load_config(os.path.join(ROOT, "bench"), sys.argv[2])
    cfg = dataclasses.replace(model.program_config(spec), n_layers=1,
                              vocab=4096, serve_paged_attn_impl="flash")
    params, _ = model.make_weights(cfg, spec["weights"], 1)
    loop = serve_launch.build_loop(params, cfg, slots=4, s_max=512,
                                   page_size=16, chunk=256)
    rng = np.random.default_rng(0)

    def serve(rid0):
        for i, n in enumerate((300, 100, 50)):
            with jax.profiler.TraceAnnotation("bench.submit"):
                loop.submit(Request(rid=rid0 + i, prompt=rng.integers(
                    0, cfg.vocab, n).astype(np.int32), max_new_tokens=6))
        while True:
            with jax.profiler.TraceAnnotation("bench.step"):
                more = loop.step()
            if not more:
                break

    serve(0)
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    serve(10)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, "probe.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print("trace", dst, os.path.getsize(dst))

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("PLANE", plane.name, lines)
        if not plane.name.startswith("/device"):
            continue
        for ln in plane.lines:
            for n, ev in enumerate(ln.events):
                if n >= 12:
                    break
                print("  ", ln.name, "|", ev.name, ev.start_ns, ev.duration_ns,
                      dict(ev.stats))
    seen = {}
    for plane in pd.planes:
        for ln in plane.lines:
            for ev in ln.events:
                text = ev.name + " " + str(dict(ev.stats))
                for word in ("bench.", "decode_step_paged", "prefill_chunk",
                             "pallas", "flash", "custom"):
                    if word in text and seen.get((plane.name, word), 0) < 3:
                        seen[(plane.name, word)] = seen.get(
                            (plane.name, word), 0) + 1
                        print("MATCH", word, "|", plane.name, "|", ln.name,
                              "|", ev.name, ev.start_ns, ev.duration_ns,
                              dict(ev.stats))
    return 0


if __name__ == "__main__":
    sys.exit(main())
