"""chip_smoke.py at a tiny size on the CPU, and the float32 reference.

The smoke's phases are the chip run's code: here they drive the same
``Engine`` (paged KV, bucketed prefill) and ``Trainer`` paths with the
Pallas kernels in interpret mode, and the served logits are held to the
same bounds against ``repro.models.reference`` as on the chip.
"""
import json
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models.model import build_model
from repro.models.reference import dense_logits_f32

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")


def test_serve_phase_logits_match_reference(interpret):
    out = chip_smoke.serve_phase(
        reduced_config("qwen3-32b"), slots=2, cache_len=64, prefill_len=32,
        prefill_chunk=16, block_size=16, requests=3, min_prompt=4,
        max_new=4, check_kernels=False, log=lambda *_: None)
    assert out["ok"], out
    assert out["logit_rel_rms"] <= chip_smoke.LOGIT_REL_RMS_TOL


def test_train_phase_loss_falls(interpret):
    out = chip_smoke.train_phase(reduced_config("qwen3-32b"), seq=64,
                                 batch=2, steps=3, check_kernels=False,
                                 log=lambda *_: None)
    assert out["ok"] and len(out["losses"]) == 3


def test_reference_matches_prefill_f32():
    """At f32 weights the reference and the model's prefill agree to
    the model's bf16 activations."""
    cfg = reduced_config("qwen3-32b")
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(1))
    toks = np.random.default_rng(1).integers(2, cfg.vocab_size, 24)
    logits, _ = model.prefill(params, {"tokens": toks[None]})
    ref = dense_logits_f32(params, cfg, toks, rows=[23])
    err = np.asarray(logits, np.float32) - ref
    assert np.sqrt(np.mean(err ** 2) / np.mean(ref ** 2)) \
        <= chip_smoke.LOGIT_REL_RMS_TOL


def test_reference_rejects_other_families():
    with pytest.raises(NotImplementedError):
        dense_logits_f32({}, reduced_config("gemma-2b"), [1, 2], rows=[1])


def test_refuses_without_tpu_or_checkout(tmp_path):
    """No TPU: non-zero exit and no result line.  Outside a checkout:
    the same, before JAX is touched."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    run = lambda cwd, script: subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)
    out = run(ROOT, ROOT / "chip_smoke.py")
    assert out.returncode == 1 and "TPU" in out.stderr
    lonely = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", lonely)
    out2 = run(tmp_path, lonely)
    assert out2.returncode == 2
    for o in (out, out2):
        assert not any(line.startswith("{") for line in
                       o.stdout.splitlines()), o.stdout
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.stdout.strip().splitlines()[-1]
                   if out.stdout.strip() else "")
