"""Quantized serving subsystem (repro.quant + kernels/q_matmul):
round-trip error bounds, kernel-vs-reference numerics, quantized-runtime
decode equality, checkpoint round-trips, and the bf16-rotation invariant.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.config import get_smoke_config
from repro.core import peft as peft_lib
from repro.core.peft import PrefillRequest
from repro.core.runtime import ModelRuntime
from repro.kernels import ops, ref
from repro.kernels.q_matmul import gs_q_matmul_pallas, q_matmul_pallas
from repro.serve.engine import ServeEngine, StaticServeEngine
from repro.train.steps import build_decode_step

CFG = get_smoke_config("qwen2-72b")
RT = ModelRuntime(CFG, key=jax.random.PRNGKey(0))
PCFG = peft_lib.PEFTConfig(method="gsoft", block_size=8)


def _tuned_adapters(seed, scale=0.3):
    ad = peft_lib.init_peft(PCFG, RT.params, jax.random.PRNGKey(seed))
    return jax.tree.map(
        lambda a: a + scale * jax.random.normal(
            jax.random.PRNGKey(seed + 50), a.shape), ad)


# ---------------------------------------------------------------------------
# core: quantize/dequantize round trips
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bound(rng):
    """|dequant(quant(w)) - w| <= scale/2 elementwise, per granularity."""
    w = jnp.asarray(rng.normal(size=(64, 48)) * rng.uniform(0.1, 10),
                    jnp.float32)
    for axis in (None, -1, 0):
        q, s = quant.quantize_int8(w, axis=axis)
        err = np.abs(np.asarray(quant.dequantize_int8(q, s)) - np.asarray(w))
        bound = np.broadcast_to(np.asarray(s) / 2 + 1e-7, err.shape)
        assert (err <= bound).all(), axis


def test_per_channel_beats_per_tensor_on_ragged_scales(rng):
    """Columns with wildly different magnitudes are the case per-channel
    scales exist for: per-tensor burns the int8 range on the big column."""
    w = jnp.asarray(rng.normal(size=(32, 8))
                    * (10.0 ** np.arange(-4, 4))[None, :], jnp.float32)
    qt, st = quant.quantize_int8(w, axis=None)
    qc, sc = quant.quantize_int8(w, axis=-1)
    err_t = np.abs(np.asarray(quant.dequantize_int8(qt, st) - w)).max(axis=0)
    err_c = np.abs(np.asarray(quant.dequantize_int8(qc, sc) - w)).max(axis=0)
    # every small-magnitude column must round-trip (much) better
    assert (err_c[:6] < err_t[:6]).all()


def test_stacked_weights_get_per_layer_scales():
    """(L, K, N) stacked weights: scales keep the layer dim (scan-sliced
    alongside the codes) and each layer quantizes independently."""
    w = jnp.stack([jnp.ones((4, 6)) * 0.01, jnp.ones((4, 6)) * 100.0])
    q, s = quant.quantize_int8(w, axis=-1, batch_dims=1)
    assert s.shape == (2, 1, 6)
    np.testing.assert_allclose(
        np.asarray(quant.dequantize_int8(q, s)), np.asarray(w), rtol=1e-2)


def test_compression_reexport_is_the_same_function():
    """optim.compression re-exports quant.core — one implementation."""
    from repro.optim import compression
    assert compression.quantize_int8 is quant.quantize_int8
    assert compression.dequantize_int8 is quant.dequantize_int8


def test_error_feedback_still_converges_after_refactor(rng):
    """ef_compress semantics unchanged: accumulated error stays bounded."""
    from repro.optim.compression import ef_compress, init_error_buffer
    g = {"w": jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)}
    err = init_error_buffer(g)
    for _ in range(4):
        q, s, err = ef_compress(g, err)
    assert np.abs(np.asarray(err["w"])).max() < np.abs(np.asarray(g["w"])).max()


def test_fp8_stub_gated_on_dtype_support(rng):
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    q, s = quant.quantize_fp8(w, axis=-1)
    assert q.dtype == jnp.float8_e4m3fn
    err = np.abs(np.asarray(quant.dequantize_fp8(q, s)) - np.asarray(w))
    assert err.max() < 0.1 * np.abs(np.asarray(w)).max()


# ---------------------------------------------------------------------------
# kernels: q_matmul / gs_q_matmul vs reference
# ---------------------------------------------------------------------------

QMM_SHAPES = [
    # (T, K, N)
    (16, 32, 64),
    (128, 64, 128),
    (33, 48, 96),        # ragged T (padding path)
    (1, 64, 64),         # decode-shaped: single token
    (250, 24, 40),       # N not a multiple of the default tile
    (16, 4096, 1024),    # 2 K tiles x 2 N tiles: the fp32 accumulator path
]


@pytest.mark.parametrize("t,k,n", QMM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_q_matmul_kernel_vs_ref(t, k, n, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(t * 7 + n))
    x = jax.random.normal(kx, (t, k), dtype)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    q, scale = quant.quantize_int8(w, axis=-1)
    got = q_matmul_pallas(x, q, scale, interpret=True)
    want = ref.q_matmul_ref(x, q, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("r,b,t,n", [(4, 8, 16, 64), (8, 4, 33, 48),
                                     (2, 16, 1, 32)])
def test_gs_q_matmul_fused_kernel_vs_ref(r, b, t, n):
    d = r * b
    ks = jax.random.split(jax.random.PRNGKey(r * b + n), 4)
    L = jax.random.normal(ks[0], (r, b, b), jnp.float32)
    R = jax.random.normal(ks[1], (r, b, b), jnp.float32)
    x = jax.random.normal(ks[2], (t, d), jnp.float32)
    w = jax.random.normal(ks[3], (d, n), jnp.float32)
    q, scale = quant.quantize_int8(w, axis=-1)
    got = gs_q_matmul_pallas(L, R, x, q, scale, interpret=True)
    want = ref.gs_q_matmul_ref(L, R, x, q, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


def test_q_matmul_hypothesis_shapes():
    pytest.importorskip("hypothesis", reason="property sweep needs "
                        "hypothesis (pip install -e '.[dev]')")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 70), st.integers(1, 40), st.integers(1, 50),
           st.integers(0, 10 ** 6))
    def check(t, k, n, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(t, k)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        q, scale = quant.quantize_int8(w, axis=-1)
        got = q_matmul_pallas(x, q, scale, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.q_matmul_ref(x, q, scale)),
                                   atol=1e-4, rtol=1e-4)

    check()


def test_ops_dispatch_handles_leading_dims(rng):
    x = jnp.asarray(rng.normal(size=(2, 3, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    q, scale = quant.quantize_int8(w, axis=-1)
    got = ops.q_matmul(x, q, scale, use_pallas=True)
    assert got.shape == (2, 3, 16)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.q_matmul_ref(x.reshape(6, 32), q,
                                    scale)).reshape(2, 3, 16),
        atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# quantized runtime: decode equality / divergence bounds
# ---------------------------------------------------------------------------

def test_quantize_params_targets_only_hooked_projections():
    qp = quant.quantize_params(RT.params, quant.QuantConfig())
    flat = peft_lib.flatten_paths(qp, is_leaf=quant.is_quant_tensor)
    quantized = {p for p, l in flat.items() if quant.is_quant_tensor(l)}
    assert any(p.endswith("attn/wq") for p in quantized)
    assert any(p.endswith("mlp/wi") for p in quantized)
    assert "lm_head/w" in quantized
    # embeddings / norms stay float
    assert "embed/table" not in quantized
    assert not any("norm" in p for p in quantized)
    assert quant.tree_bytes(qp) < 0.5 * quant.tree_bytes(RT.params)


def test_quantized_greedy_rollout_divergence_bounded():
    """64-token greedy rollout: the int8 runtime must track the bf16
    reference. The divergence point is REPORTED via the assertion message
    and bounded: at least the first 16 tokens must match, and overall
    agreement must be >= 75% (on the smoke config it is exact today)."""
    qrt = RT.quantized("int8")
    outs = []
    for rt in (RT, qrt):
        eng = ServeEngine(rt, max_batch=1, max_len=96, eos_id=-1)
        eng.add_request([3, 4, 5, 6], max_new_tokens=64)
        outs.append(eng.run()[0])
    ref_toks, q_toks = outs
    first_div = next((i for i, (a, b) in enumerate(zip(ref_toks, q_toks))
                      if a != b), 64)
    agree = sum(a == b for a, b in zip(ref_toks, q_toks))
    assert first_div >= 16, (first_div, agree)
    assert agree >= 48, (first_div, agree)


def test_quantized_static_engine_matches_continuous():
    """Both engines serve the same quantized runtime identically."""
    qrt = RT.quantized("int8")
    outs = []
    for cls in (ServeEngine, StaticServeEngine):
        eng = cls(qrt, max_batch=2, max_len=48, eos_id=-1)
        rid = eng.add_request([5, 6, 7, 8], max_new_tokens=6)
        outs.append(eng.run()[rid])
    assert outs[0] == outs[1]


def test_quantized_runtime_guards():
    qrt = RT.quantized("int8")
    with pytest.raises(ValueError, match="already quantized"):
        qrt.quantized("int8")
    with pytest.raises(ValueError, match="already-quantized"):
        ModelRuntime(CFG, qrt.params, adapters=_tuned_adapters(3),
                     peft_cfg=PCFG)
    with pytest.raises(ValueError, match="unknown quantization mode"):
        RT.quantized("int4")
    # mode vs explicit qcfg must agree (silent override would serve the
    # wrong precision)
    with pytest.raises(ValueError, match="conflicts"):
        RT.quantized("fp8", qcfg=quant.QuantConfig(mode="int8"))


def test_with_bank_preserves_quantized_state():
    """Regression: quantize-then-bank must keep quant_cfg — a banked
    quantized runtime re-quantizing or checkpointing without it breaks."""
    qrt = RT.quantized("int8").attach({"a": _tuned_adapters(3)}, PCFG)
    assert qrt.is_quantized and qrt.quant_cfg.mode == "int8"
    with pytest.raises(ValueError, match="already quantized"):
        qrt.quantized("int8")


# ---------------------------------------------------------------------------
# multi-adapter bank over a quantized runtime
# ---------------------------------------------------------------------------

def test_adapter_bank_rotations_are_not_quantized():
    """Regression: quantization must never touch the GS rotations — the
    bank carries bf16/fp32 orthogonal blocks however the runtime's base
    weights are stored (QOFT rationale, DESIGN.md)."""
    qrt = RT.attach({"a": _tuned_adapters(3)}, PCFG).quantized("int8")
    assert quant.is_quantized_tree(qrt.params)
    bank_leaves = jax.tree_util.tree_leaves(
        qrt.bank.tree, is_leaf=quant.is_quant_tensor)
    assert bank_leaves, "bank unexpectedly empty"
    for leaf in bank_leaves:
        assert not quant.is_quant_tensor(leaf)
        assert jnp.issubdtype(leaf.dtype, jnp.floating)


def test_bank_vs_merged_equality_in_quantized_mode():
    """Acceptance: per-request rotation over int8 base weights == the
    adapter merged offline then quantized, within fp32-logit tolerance
    (both sides carry independent int8 rounding of W vs QW — measured
    max diff ~0.05 on logits with std ~1.0)."""
    adapters = {"a": _tuned_adapters(3)}
    qrt_bank = RT.attach(adapters, PCFG).quantized("int8")
    merged = ModelRuntime(CFG, RT.params, adapters=adapters["a"],
                          peft_cfg=PCFG).quantized("int8")
    tokens = jnp.asarray([[5], [9]], jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    step = build_decode_step(CFG)
    _, logits_bank, _ = step(qrt_bank.params, qrt_bank.bank.context([1, 1]),
                             tokens, qrt_bank.init_decode_state(2, 16), pos)
    _, logits_merged, _ = step(merged.params, None, tokens,
                               merged.init_decode_state(2, 16), pos)
    np.testing.assert_allclose(np.asarray(logits_bank, np.float32),
                               np.asarray(logits_merged, np.float32),
                               atol=0.15)


def test_quantized_multi_adapter_serving_end_to_end():
    """ServeEngine over a quantized banked runtime: per-request adapters
    produce distinct outputs; identity slot == bare quantized model; the
    bank built before or after quantization serves identically."""
    adapters = {"alice": _tuned_adapters(7), "bob": _tuned_adapters(11)}
    qrt = RT.attach(adapters, PCFG).quantized("int8")
    prompt = [3, 4, 5, 6]
    eng = ServeEngine(qrt, max_batch=3, max_len=48, eos_id=-1)
    rids = {name: eng.add_request(prompt, max_new_tokens=5, adapter=name)
            for name in ("alice", "bob", None)}
    results = eng.run()
    assert results[rids["alice"]] != results[rids["bob"]]
    plain = ServeEngine(RT.quantized("int8"), max_batch=1, max_len=48,
                        eos_id=-1)
    rid = plain.add_request(prompt, max_new_tokens=5)
    assert results[rids[None]] == plain.run()[rid]
    # quantize-then-bank == bank-then-quantize
    qrt2 = RT.quantized("int8").attach(adapters, PCFG)
    eng2 = ServeEngine(qrt2, max_batch=1, max_len=48, eos_id=-1)
    rid2 = eng2.add_request(prompt, max_new_tokens=5, adapter="alice")
    assert eng2.run()[rid2] == results[rids["alice"]]


def test_quantized_banked_pallas_fused_matches_ref_path():
    """The fused gs_q_matmul kernel path (use_pallas on both the bank and
    the quantization) serves the same tokens as the reference einsums."""
    adapters = {"a": _tuned_adapters(3)}
    pcfg_k = peft_lib.PEFTConfig(method="gsoft", block_size=8,
                                 use_pallas=True)
    qcfg_k = quant.QuantConfig(mode="int8", use_pallas=True)
    qrt_k = RT.attach(adapters, pcfg_k).quantized(qcfg=qcfg_k)
    qrt_ref = RT.attach(adapters, PCFG).quantized("int8")
    outs = []
    for rt in (qrt_k, qrt_ref):
        eng = ServeEngine(rt, max_batch=2, max_len=48, eos_id=-1)
        rid = eng.add_request([3, 4, 5, 6], max_new_tokens=4, adapter="a")
        outs.append(eng.run()[rid])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_quantized_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    qrt = RT.quantized("int8")
    CheckpointManager(str(tmp_path)).save_quantized(1, qrt.params,
                                                    qrt.quant_cfg)
    rt2 = ModelRuntime.load_quantized(str(tmp_path), CFG)
    assert rt2.quant_cfg == qrt.quant_cfg
    for a, b in zip(jax.tree_util.tree_leaves(qrt.params),
                    jax.tree_util.tree_leaves(rt2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_checkpoint_quantizes_on_load(tmp_path):
    """A plain float checkpoint loads through the same entry point and is
    quantized on the way in — identical to quantizing offline."""
    from repro.checkpoint.manager import CheckpointManager
    CheckpointManager(str(tmp_path)).save(1, RT.params)
    rt2 = ModelRuntime.load_quantized(str(tmp_path), CFG)
    offline = RT.quantized("int8")
    for a, b in zip(jax.tree_util.tree_leaves(rt2.params),
                    jax.tree_util.tree_leaves(offline.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quantized_checkpoint_mode_conflict_raises(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    qrt = RT.quantized("int8")
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_quantized(1, qrt.params, qrt.quant_cfg)
    with pytest.raises(ValueError, match="conflicts"):
        mgr.restore_quantized(
            jax.eval_shape(lambda k: RT.params, 0),
            qcfg=quant.QuantConfig(mode="int8", per_channel=False))


def test_checkpoint_use_pallas_is_loader_choice(tmp_path):
    """use_pallas is execution strategy, not data layout: a checkpoint
    saved on one backend restores under the loader's kernel choice (same
    codes/scales) instead of erroring or silently downgrading."""
    from repro.checkpoint.manager import CheckpointManager
    qrt = RT.quantized("int8")           # saved with use_pallas=False
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_quantized(1, qrt.params, qrt.quant_cfg)
    base = jax.eval_shape(lambda k: RT.params, 0)
    tree, used = mgr.restore_quantized(
        base, qcfg=quant.QuantConfig(mode="int8", use_pallas=True))
    assert used.use_pallas
    # and via the runtime facade, a config with use_pallas=True flows in
    rt2 = ModelRuntime.load_quantized(
        str(tmp_path), CFG.with_overrides(use_pallas=True))
    assert rt2.quant_cfg.use_pallas
    leaves = [l for l in jax.tree_util.tree_leaves(
        rt2.params, is_leaf=quant.is_quant_tensor)
        if quant.is_quant_tensor(l)]
    assert leaves and all(l.meta.use_pallas for l in leaves)
    # float checkpoints inherit the loading model config's kernel path too
    mgr2 = CheckpointManager(str(tmp_path / "f"))
    mgr2.save(1, RT.params)
    rt3 = ModelRuntime.load_quantized(
        str(tmp_path / "f"), CFG.with_overrides(use_pallas=True))
    assert rt3.quant_cfg.use_pallas


# ---------------------------------------------------------------------------
# hygiene: one quantization implementation
# ---------------------------------------------------------------------------

def test_no_direct_compression_quantize_imports():
    """Mirrors the CI grep: quantize_int8 lives in repro.quant.core; only
    optim/compression.py (the re-export) may import it from there."""
    res = subprocess.run(
        ["grep", "-rn", "--include=*.py",
         r"from repro\.optim\.compression import", "src/repro",
         "benchmarks", "examples"],
        capture_output=True, text=True, cwd=str(_repo_root()))
    offenders = [ln for ln in res.stdout.splitlines() if "quantize" in ln]
    assert not offenders, offenders


def _repo_root():
    import pathlib
    return pathlib.Path(__file__).resolve().parents[1]


def test_cli_exposes_quantize_flag():
    """launch/serve.py --quantize is wired (smoke: help text)."""
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--help"],
        capture_output=True, text=True, cwd=str(_repo_root()),
        env={**__import__("os").environ,
             "PYTHONPATH": str(_repo_root() / "src")})
    assert "--quantize" in res.stdout
