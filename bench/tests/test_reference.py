"""The plain reference against ``PagedServeLoop``'s own logits, on the CPU
at the configurations' smoke widths: prompts inside one chunk, across a
chunk boundary and over three chunks, then decode steps through the paged
cache.  The configurations quantise activations to 3-bit codes, so the
two agree to a correlation, not bit for bit (see ``bench/check.py``)."""

import dataclasses
import json
import os

import numpy as np
import pytest

from bench import check, model
from bench.references import dense_decoder as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    CONFIGS = [c["name"] for c in json.load(f)["configs"]]


def smoke(name, seed):
    from repro.configs import smoke_config

    spec = model.load_config(BENCH, name)
    model.program_config(spec)
    cfg = dataclasses.replace(smoke_config(spec["program_config"]),
                              serve_kv_dtype="fp")
    serve, plain = model.make_weights(cfg, spec["weights"], seed)
    return cfg, model.dims(cfg, spec), serve, plain


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_follows_the_paged_loop(name):
    from repro.launch import serve as serve_launch

    cfg, dims, serve, plain = smoke(name, 2 ** 31 + 3)
    dense = ref.expand(plain)
    loop = serve_launch.build_loop(serve, cfg, slots=4, s_max=256,
                                   page_size=16, chunk=64)
    rng = np.random.default_rng(0)
    dec = []
    for L in (5, 64, 150):
        prompt = rng.integers(0, cfg.vocab, L).astype(np.int32)
        cont = rng.integers(0, cfg.vocab, 3).astype(np.int32)
        got = loop.prompt_logits(prompt, cont)
        want = np.asarray(ref.logits(dense, dims,
                                     np.concatenate([prompt, cont]),
                                     L + 2))[-1]
        dec.append(float(check.decorrelation(got[None], want[None])[0]))
    assert max(dec) < 0.1, dec


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_sees_a_missing_layer(name):
    """The same comparison fails when the reference skips a layer."""
    import jax

    cfg, dims, _, plain = smoke(name, 5)
    dense = ref.expand(plain)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab, 80)
    full = np.asarray(ref.logits(dense, dims, tokens, 40))
    cut = dict(dense)
    for part in ("norm1", "norm2", "attn", "ffn"):
        cut[part] = jax.tree.map(lambda a: a[1:], dense[part])
    short = np.asarray(ref.logits(cut, dict(dims, n_layers=1), tokens, 40))
    assert check.decorrelation(short, full).mean() > 0.3
