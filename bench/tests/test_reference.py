"""The plain reference against the program at small widths in float32:
prefill then paged decode logits (through the adapter bank), and the
GSOFT train loss and adapter gradients. The program runs its reference
kernel path here; the reference shares no code with it."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import cells, traffic, weights
from reference import qwen2 as ref
from small import small_cell, model_config

F32 = dict(dtype="f32", param_dtype="f32")


@pytest.fixture(scope="module")
def setup():
    cell = small_cell("qwen2-72b", "chat")
    cfg = model_config(cell).with_overrides(**F32)
    params = weights.make_params(cfg, 2**35 + 3)
    names = ["a", "b"]
    adapters = weights.make_adapters(cfg, 32, 2**35 + 3, names)
    return cell, cfg, params, names, adapters


def test_prefill_then_decode_logits(setup):
    sys.path.insert(0, str(cells.CHECKOUT))
    import chip_smoke
    from repro.core import peft as peft_lib
    from repro.core.runtime import ModelRuntime
    cell, cfg, params, names, adapters = setup
    pc = peft_lib.PEFTConfig(method="gsoft", block_size=32)
    rt = ModelRuntime(cfg, params).attach(
        {n: jax.tree.map(jnp.asarray, adapters[n]) for n in names}, pc)
    rng = np.random.default_rng(0)
    reqs = [{"prompt": rng.integers(0, 512, n).tolist(), "adapter": a}
            for n, a in ((40, "a"), (17, "b"), (33, None))]
    got, fed, _ = chip_smoke.forced_logits(rt, reqs)
    arch = ref.Arch.from_config(cell.config)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r["prompt"], fed[:, i]]).astype(np.int32)
        toks = np.zeros(ref.pad_to(len(seq)), np.int32)
        toks[:len(seq)] = seq
        ad = adapters[r["adapter"]] if r["adapter"] else None
        h = ref.hidden(arch, params, ad, toks, None)
        rows = np.arange(len(r["prompt"]) - 1,
                         len(r["prompt"]) + fed.shape[0])
        want = np.asarray(ref.head_block(h[rows], params["lm_head"]["w"],
                                         quant=None))
        np.testing.assert_allclose(got[:, i], want, atol=2e-3, rtol=0)


def test_train_loss_and_adapter_grads(setup):
    from repro.core import peft as peft_lib
    from repro.models import api
    cell, cfg, params, names, adapters = setup
    pc = peft_lib.PEFTConfig(method="gsoft", block_size=32)
    rows = traffic.train_rows({"seq_len": 32}, 5, 2, 512, 0)
    theta = jax.tree.map(jnp.asarray, adapters["a"])

    def program_loss(a):
        p = peft_lib.materialize_tree(pc, params, a, lazy=True)
        return api.loss_fn(cfg, p, {k: jnp.asarray(v)
                                    for k, v in rows.items()})[0]
    loss, grads = jax.value_and_grad(program_loss)(theta)
    arch = ref.Arch.from_config(cell.config)
    rloss, rgrads = ref.loss_and_grad(arch, params, theta,
                                      jnp.asarray(rows["tokens"]),
                                      jnp.asarray(rows["labels"]))
    assert abs(float(loss) - float(rloss)) < 1e-4
    for path in rgrads:
        for k in ("L", "R"):
            g, r = np.asarray(grads[path][k]), np.asarray(rgrads[path][k])
            assert np.abs(g - r).max() <= 1e-3 * np.abs(r).max(), path


def test_quantize_matches_program():
    from repro.quant.core import quantize_int8
    w = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 48), jnp.bfloat16)
    q, s = quantize_int8(w, axis=-1, batch_dims=1)
    mine = np.stack([np.asarray(ref.dequant(w[i], "int8")) for i in range(2)])
    np.testing.assert_array_equal(np.asarray(q, np.float32)
                                  * np.asarray(s), mine)


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_control_computed_in_lower_precision(quant):
    """A ``-wa`` control rounds the weights as the weight-only one does and
    the activations too, one scale per row; the weight-only control leaves
    the activations as they are."""
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    wa = quant + ref.ACTS
    np.testing.assert_array_equal(ref.dequant(w, wa), ref.dequant(w, quant))
    np.testing.assert_array_equal(ref.act(x, quant), x)
    xq = np.asarray(ref.act(x, wa))
    err = np.abs(xq - np.asarray(x)).max(axis=1)
    step = np.abs(np.asarray(x)).max(axis=1) / ref.QMAX[quant]
    assert 0 < err.max() and np.all(err <= step * (16 if quant == "fp8"
                                                    else 0.5) + 1e-7)
