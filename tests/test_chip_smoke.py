"""chip_smoke.py's phases at the qwen2-72b smoke config, on the CPU backend
(Pallas kernels interpreted). The script itself refuses to run without a
TPU, so its device check and driver are exercised only for that refusal;
the kernel-count floor is 0 here because interpreted kernels lower to
plain XLA ops."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.config import get_smoke_config
from repro.core.runtime import ModelRuntime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen2-72b").with_overrides(dtype="bf16",
                                                        param_dtype="bf16")


@pytest.fixture(scope="module")
def params(cfg):
    return ModelRuntime(cfg, key=jax.random.PRNGKey(0)).params


@pytest.fixture(scope="module")
def ref(cfg, params):
    ref, rec = chip_smoke.phase_serve_ref(cfg, params)
    assert rec["logits_finite"]
    return ref


def _within(rec):
    return (rec["finite"] and rec["max_abs_diff"] <= rec["tol"]
            and rec["decisive_equal"] == rec["decisive_tokens"])


def test_serve_ref_phase(ref):
    assert len(ref["tokens"]) == chip_smoke.N_REQ
    assert ref["logits"].shape[:2] == (1 + chip_smoke.DECODE_STEPS,
                                       chip_smoke.N_REQ)


def test_serve_pallas_phase(cfg, params, ref):
    out = chip_smoke.phase_serve_pallas(cfg, params, ref, min_kernels=0)
    assert _within(out["logits_vs_ref"])


def test_serve_int8_phase(cfg, params):
    out = chip_smoke.phase_serve_int8(cfg, params, min_kernels=0)
    assert _within(out["logits_vs_ref"])


def test_train_phase(cfg):
    out = chip_smoke.phase_train(cfg, steps=2, seq=32)
    assert len(out["losses"]) == len(out["ref_losses"]) == 2
    assert out["loss_max_abs_diff"] <= out["loss_tol"]
    assert out["grad_norm_max_rel_diff"] <= out["grad_norm_tol"]
    assert out["moment_max_rel_diff"] <= out["moment_tol"]


def test_compare_logits_rejects_a_wrong_path(ref):
    bad = ref["logits"].copy()
    bad[0, 0, 0] += 10 * abs(ref["logits"]).max()
    with pytest.raises(chip_smoke.PhaseError, match="logits differ"):
        chip_smoke.compare_logits(bad, ref["logits"], "mutated")


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
