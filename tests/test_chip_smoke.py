"""chip_smoke.py's logic on CPU: its phases at smoke size with the device
check stubbed, and its refusal to report a result without a TPU."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.configs import smoke_config

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_at_smoke_size(chip_smoke, monkeypatch, capsys):
    stub = {"platform": "cpu", "kind": "stub", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_device", lambda: stub)
    result = chip_smoke.run(
        smoke_config("codeqwen1.5-7b"),
        kernel_shape=dict(B=3, KV=4, rep=2, hd=16, P=8, MB=4),
        serve_shape=dict(min_len=4, max_len=40, s_max=64, chunk=16,
                         n_requests=5, max_new=6, slots=3),
    )
    assert result == {"ok": True, "device": stub}
    out = capsys.readouterr().out
    for line in ("flash_decode[fp]", "flash_decode[int8]",
                 "failed_candidates=[]", "served requests=5 tokens=30",
                 "resolved impls:", "logit agreement",
                 "greedy token match fraction: 12/12", "peak_bytes_in_use"):
        assert line in out, line


def test_serve_phase_fails_on_logit_disagreement(chip_smoke, monkeypatch):
    """A paged path that drifts from the dense reference fails the
    phase instead of reporting agreement."""
    from repro.serve.paged import PagedServeLoop

    real = PagedServeLoop.prompt_logits
    monkeypatch.setattr(PagedServeLoop, "prompt_logits",
                        lambda self, *a: real(self, *a) * 1.5)
    with pytest.raises(AssertionError, match="disagree with the dense"):
        chip_smoke.phase_serve(
            smoke_config("codeqwen1.5-7b"), min_len=4, max_len=12,
            s_max=32, chunk=8, n_requests=2, max_new=3, slots=2)


def _zero_v(cfg, q, k, v, bt, pos, **kw):
    return cfg, q, k, jnp.zeros_like(v), bt, pos


def _reversed_pages(cfg, q, k, v, bt, pos, **kw):
    return cfg, q, k, v, bt[:, ::-1], pos


@pytest.mark.parametrize("fault", [_zero_v, _reversed_pages],
                         ids=["zeroed-v-pages", "reversed-block-table"])
def test_serve_phase_catches_decode_attention_faults(chip_smoke, monkeypatch,
                                                     fault):
    """A fault in the paged decode attention alone (the prefill is
    untouched, so last-prompt logits still agree) fails the phase: the
    logits after the served tokens' decode steps are gated too."""
    from repro.kernels import paged

    real = paged.dispatch_attention

    def faulty(*args, **kw):
        return real(*fault(*args, **kw), **kw)

    monkeypatch.setattr(paged, "dispatch_attention", faulty)
    with pytest.raises(AssertionError, match="disagree with the dense"):
        chip_smoke.phase_serve(
            smoke_config("codeqwen1.5-7b"), min_len=4, max_len=40,
            s_max=64, chunk=16, n_requests=3, max_new=3, slots=2)


def _run(script_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=script_dir,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_exits_nonzero_alone(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
