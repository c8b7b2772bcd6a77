"""End-to-end train loop: learning, checkpoint/resume exactness, heartbeat."""
import os

import jax
import numpy as np
import pytest

from repro import optim
from repro.config import ModelConfig
from repro.core import peft as peft_lib
from repro.core.runtime import ModelRuntime
from repro.data import DataConfig
from repro.data.synthetic import lm_batch
from repro.models import api
from repro.train.loop import LoopConfig, train
from repro.train.steps import TrainStepConfig, build_train_step

CFG = ModelConfig(
    name="tiny-lm", family="decoder", num_layers=2, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
    mlp_type="swiglu", dtype="f32", param_dtype="f32", remat="none",
    attn_chunk=32)


def _tcfg():
    return TrainStepConfig(
        peft=peft_lib.PEFTConfig(method="gsoft", block_size=8),
        opt=optim.OptimizerConfig(learning_rate=3e-3),
        num_microbatches=2)


def _dcfg():
    return DataConfig(seq_len=32, global_batch=8, vocab_size=128)


def test_training_reduces_loss(tmp_path):
    loop = LoopConfig(steps=30, log_every=5, ckpt_every=100,
                      ckpt_dir=str(tmp_path),
                      heartbeat_path=str(tmp_path / "hb"))
    out = train(CFG, _tcfg(), _dcfg(), loop, log_fn=lambda s: None)
    h = out["history"]
    assert h[-1]["loss"] < h[0]["loss"] * 0.9
    assert os.path.exists(tmp_path / "hb")


def test_resume_is_exact(tmp_path):
    """3+3 steps with restart == 6 straight steps (deterministic data +
    checkpointed optimizer/adapters)."""
    loop_a = LoopConfig(steps=6, log_every=1, ckpt_every=100,
                        ckpt_dir=str(tmp_path / "a"))
    straight = train(CFG, _tcfg(), _dcfg(), loop_a, log_fn=lambda s: None)

    loop_b1 = LoopConfig(steps=3, log_every=1, ckpt_every=3,
                         ckpt_dir=str(tmp_path / "b"))
    train(CFG, _tcfg(), _dcfg(), loop_b1, log_fn=lambda s: None)
    loop_b2 = LoopConfig(steps=6, log_every=1, ckpt_every=3,
                         ckpt_dir=str(tmp_path / "b"))
    resumed = train(CFG, _tcfg(), _dcfg(), loop_b2, resume=True,
                    log_fn=lambda s: None)

    a = jax.tree.leaves(straight["trainable"])
    b = jax.tree.leaves(resumed["trainable"])
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-6, rtol=1e-6)


def test_full_finetune_mode(tmp_path):
    tcfg = TrainStepConfig(peft=peft_lib.PEFTConfig(method="full"),
                           opt=optim.OptimizerConfig(learning_rate=1e-3),
                           num_microbatches=1)
    loop = LoopConfig(steps=8, log_every=2, ckpt_every=100)
    out = train(CFG, tcfg, _dcfg(), loop, log_fn=lambda s: None)
    assert out["history"][-1]["loss"] < out["history"][0]["loss"]


# Adapters the train step applies where each layer uses its weight
# (materialize_tree(lazy=True): activation-side for the input-rotation
# methods, built per layer for the rest) against the weight-side tree.
LAZY_CASES = {
    "gsoft": {"method": "gsoft"},
    "gsoft-pallas": {"method": "gsoft", "use_pallas": True},
    "oft": {"method": "oft"},
    "boft": {"method": "boft"},
    "householder": {"method": "householder"},
    "givens": {"method": "givens"},
    "gsoft-scale": {"method": "gsoft", "use_scale": True},
    "lora": {"method": "lora"},
}
LAZY_TOL = 1e-4     # fp32; relative to each leaf's largest gradient


@pytest.mark.parametrize("case", list(LAZY_CASES))
def test_train_step_lazy_adapters_match_weight_side(case):
    """build_train_step's loss and adapter gradients equal those of the
    weight-side tree (materialize_tree(lazy=False)). SGD with lr 1 and no
    momentum, decay or clipping makes the step's update minus its
    gradient, so the gradient is read off the step itself."""
    pcfg = peft_lib.PEFTConfig(block_size=8, **LAZY_CASES[case])
    key = jax.random.PRNGKey(0)
    params = ModelRuntime(CFG, key=key).params
    adapters = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                               p.shape, p.dtype),
        peft_lib.init_peft(pcfg, params, key))
    batch = lm_batch(CFG, 2, 16)
    tcfg = TrainStepConfig(peft=pcfg, opt=optim.OptimizerConfig(
        kind="sgd", learning_rate=1.0, momentum=0.0, weight_decay=0.0,
        grad_clip=1e9))
    new, _, metrics = jax.jit(build_train_step(CFG, tcfg))(
        params, adapters, optim.init(tcfg.opt, adapters), batch)
    grads = jax.tree.map(lambda a, b: a - b, adapters, new)

    def weight_side(adp):
        eff = peft_lib.materialize_tree(pcfg, params, adp)
        return api.loss_fn(CFG, eff, batch)[0]

    loss, want = jax.jit(jax.value_and_grad(weight_side))(adapters)
    np.testing.assert_allclose(float(metrics["loss"]), float(loss),
                               rtol=1e-6)
    for path, g in peft_lib.flatten_paths(want).items():
        got = np.asarray(peft_lib.flatten_paths(grads)[path])
        scale = float(np.abs(np.asarray(g)).max())
        assert scale > 0, path
        assert np.abs(got - np.asarray(g)).max() <= LAZY_TOL * scale, path
