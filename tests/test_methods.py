"""MethodOps registry: every parametrization is a first-class record —
unknown methods fail loud listing what IS registered, every method
identity-inits, weight-side merge and activation-side application agree,
heterogeneous (mixed-method) banks serve each tenant exactly like its solo
merged run (also over int8 base weights), checkpoints round-trip per-name
method metadata, and raw ``method ==`` dispatch cannot creep back outside
``core/methods.py``."""
import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_smoke_config
from repro.core import adapters as ad
from repro.core import methods as methods_lib
from repro.core import peft as peft_lib
from repro.core.orthogonal import orthogonality_error
from repro.core.peft import PrefillRequest
from repro.core.runtime import ModelRuntime
from repro.serve.engine import ServeEngine, StaticServeEngine
from repro.store import AdapterStore, load_adapter_checkpoints

CFG = get_smoke_config("qwen2-72b")
RT = ModelRuntime(CFG, key=jax.random.PRNGKey(0))
PARAMS = RT.params

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"

MIXED_CFGS = {
    "alice": peft_lib.PEFTConfig(method="gsoft", block_size=8),
    "bob": peft_lib.PEFTConfig(method="boft", block_size=8),
    "carol": peft_lib.PEFTConfig(method="householder", reflections=4),
}


def _spec(method, d_in=16, d_out=16, **kw):
    kw.setdefault("reflections", 4)
    return ad.AdapterSpec(method=method, d_in=d_in, d_out=d_out,
                          block_size=4, **kw)


def _noisy(params, seed=3, scale=0.3):
    return jax.tree.map(
        lambda a: a + scale * jax.random.normal(jax.random.PRNGKey(seed),
                                                a.shape), params)


def _tuned_adapters(seed, cfg, scale=0.3):
    adp = peft_lib.init_peft(cfg, PARAMS, jax.random.PRNGKey(seed))
    return jax.tree.map(
        lambda a: a + scale * jax.random.normal(
            jax.random.PRNGKey(seed + 50), a.shape), adp)


def _mixed_adapters():
    return {n: _tuned_adapters(i * 7 + 3, c)
            for i, (n, c) in enumerate(MIXED_CFGS.items())}


def _solo(prompt, max_new, adapters=None, cfg=None, quantize=False):
    """Single-request reference: batch of one, offline-merged adapter."""
    rt = (ModelRuntime(CFG, PARAMS, adapters=adapters, peft_cfg=cfg)
          if adapters is not None else RT)
    if quantize:
        rt = rt.quantized("int8")
    eng = StaticServeEngine(rt, max_batch=1, max_len=48, eos_id=-1)
    rid = eng.add_request(list(prompt), max_new_tokens=max_new)
    return eng.run()[rid]


# ---------------------------------------------------------------------------
# registry surface
# ---------------------------------------------------------------------------

def test_registry_has_explicit_entries():
    assert methods_lib.registered() == ["boft", "double_gsoft", "givens",
                                        "gsoft", "householder", "lora", "oft"]


def test_unknown_method_raises_keyerror_listing_registered():
    with pytest.raises(KeyError, match="monarch") as ei:
        methods_lib.get("monarch")
    for m in ("gsoft", "boft", "householder", "lora"):
        assert m in str(ei.value)
    # the public dispatchers fail the same way
    with pytest.raises(KeyError, match="monarch"):
        ad.init_adapter(_spec("monarch"), jax.random.PRNGKey(0))
    with pytest.raises(KeyError, match="monarch"):
        peft_lib.build_adapter_bank(
            dataclasses.replace(MIXED_CFGS["alice"], method="monarch"),
            PARAMS, {})


def test_full_none_are_training_regimes_not_methods():
    assert not peft_lib.PEFTConfig(method="full").is_peft
    assert not peft_lib.PEFTConfig(method="none").is_peft
    t, f = methods_lib.trainable_split("full", {"w": 1}, {})
    assert t == {"w": 1} and f == {}
    t, f = methods_lib.trainable_split("none", {"w": 1}, {})
    assert t == {} and f == {"w": 1}
    with pytest.raises(KeyError, match="retnofit"):
        methods_lib.trainable_split("retnofit", {}, {})


# ---------------------------------------------------------------------------
# per-method numerics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", methods_lib.registered())
def test_identity_init_every_method(method):
    """W_eff == W at step 0 for every registered method."""
    spec = _spec(method, d_in=16, d_out=24)
    p = ad.init_adapter(spec, jax.random.PRNGKey(0))
    W = jax.random.normal(jax.random.PRNGKey(1), (16, 24))
    np.testing.assert_allclose(np.asarray(ad.materialize(spec, p, W)),
                               np.asarray(W), atol=1e-6)


@pytest.mark.parametrize("method", [m for m in methods_lib.registered()
                                    if methods_lib.get(m)
                                    .apply_activation_side is not None])
def test_merge_vs_activation_side_equality(method):
    """x @ (Q W) == (x Q) @ W — the weight-side/activation-side contract
    every banked serving path relies on."""
    spec = _spec(method, d_in=16, d_out=24)
    p = _noisy(ad.init_adapter(spec, jax.random.PRNGKey(0)))
    W = jax.random.normal(jax.random.PRNGKey(1), (16, 24))
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 16))
    y_merge = x @ ad.materialize(spec, p, W)
    y_act = ad.apply_activation_side(spec, p, x) @ W
    np.testing.assert_allclose(np.asarray(y_act), np.asarray(y_merge),
                               atol=1e-4)


@pytest.mark.parametrize("method", methods_lib.registered())
def test_param_count_analytic_matches_init(method):
    for batch, use_scale in (((), False), ((3,), True)):
        spec = _spec(method, batch=batch, use_scale=use_scale)
        p = ad.init_adapter(spec, jax.random.PRNGKey(0))
        counted = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(p))
        assert ad.num_adapter_params(spec) == counted, (method, batch)


def test_householder_rejects_odd_reflections():
    with pytest.raises(ValueError, match="EVEN"):
        ad.init_adapter(_spec("householder", reflections=3),
                        jax.random.PRNGKey(0))


def test_orthogonality_error_sweep():
    """hypothesis sweep: merged rotation of EVERY orthogonal method stays
    orthogonal (error <= 1e-4) across random params / dims / block sizes."""
    pytest.importorskip("hypothesis",
                        reason="property sweep needs hypothesis")
    from hypothesis import given, settings, strategies as st

    orth = [m for m in methods_lib.registered()
            if methods_lib.get(m).orthogonal]

    @settings(max_examples=25, deadline=None)
    @given(method=st.sampled_from(orth),
           d=st.sampled_from([8, 16, 32]),
           b=st.sampled_from([2, 4, 8]),
           seed=st.integers(0, 2 ** 16))
    def check(method, d, b, seed):
        spec = ad.AdapterSpec(method=method, d_in=d, d_out=d, block_size=b,
                              reflections=4)
        p = ad.init_adapter(spec, jax.random.PRNGKey(seed))
        p = jax.tree.map(
            lambda a: a + 0.5 * jax.random.normal(
                jax.random.PRNGKey(seed + 1), a.shape), p)
        Q = ad.merge(spec, p, jnp.eye(d, dtype=jnp.float32))
        assert float(orthogonality_error(Q)) <= 1e-4

    check()


# ---------------------------------------------------------------------------
# heterogeneous banks (the acceptance path)
# ---------------------------------------------------------------------------

def test_mixed_method_bank_matches_solo_merged_runs():
    """gsoft + boft + householder tenants in ONE bank: every request's
    tokens equal its adapter's solo offline-merged run; the identity slot
    serves the base model."""
    adapters = _mixed_adapters()
    rt = RT.attach(adapters, MIXED_CFGS)
    assert rt.bank.bank_methods == ("boft", "gsoft", "householder")
    prompt = [3, 4, 5, 6]
    eng = ServeEngine(rt, max_batch=4, max_len=48, eos_id=-1)
    rids = {n: eng.add_request(prompt, max_new_tokens=5, adapter=n)
            for n in ("alice", "bob", "carol", None)}
    results = eng.run()
    for name in ("alice", "bob", "carol"):
        ref = _solo(prompt, 5, adapters[name], MIXED_CFGS[name])
        assert results[rids[name]] == ref, name
    assert results[rids[None]] == _solo(prompt, 5)
    assert len({tuple(results[r]) for r in rids.values()}) == 4


def _forced_logits(rt, prompt, feed, adapter=None):
    """Prefill logits at the prompt's last token, then one decode step per
    token of ``feed`` (teacher forcing, so two runtimes see the same
    inputs): (1 + len(feed), V) fp32."""
    ctx = rt.context([rt.slot(adapter)])
    state = rt.decode_state(1, 48)
    req = PrefillRequest(batch={"tokens": jnp.asarray([prompt], jnp.int32)},
                         last_idx=jnp.asarray([len(prompt) - 1], jnp.int32),
                         ctx=ctx)
    logits, state = rt.prefill_fn()(rt.params, req, state)
    rows = [logits[0, -1]]
    for i, tok in enumerate(feed):
        _, logits, state = rt.decode_fn()(
            rt.params, ctx, jnp.asarray([[tok]], jnp.int32), state,
            jnp.asarray([len(prompt) + i], jnp.int32))
        rows.append(logits[0, -1])
    return np.asarray(jnp.stack(rows), np.float32)


# Banked int8 rotates x, then multiplies by int8(W); the solo reference
# merges Q into W and quantizes int8(Q W). Both carry int8 rounding of
# different matrices, so their logits agree to the quantization noise, not
# exactly: 1.1-1.5% of the logit scale (max |logit|) on this config, about
# what separates the float bank from the int8 solo run. The bound is 3%.
# A request served on any other slot of the bank (another tenant, or the
# identity slot 0) misses by 72-133% of the scale on the same inputs.
INT8_BANK_RTOL = 0.03
INT8_BANK_SLOTS = ("alice", "bob", "carol", None)


def test_mixed_method_bank_quantized_int8():
    """The same heterogeneous bank over int8 base weights: each request's
    logits (prefill, then teacher-forced decode) equal its adapter's solo
    merged-then-quantized run within INT8_BANK_RTOL of the logit scale, and
    greedy tokens agree wherever the reference's top-2 margin exceeds twice
    that tolerance. Served on any other slot, the same inputs miss by more
    than the tolerance, so the bound tells a mis-routed adapter apart.
    Rotations stay float for every method (QOFT recipe)."""
    adapters = _mixed_adapters()
    qrt = RT.attach(adapters, MIXED_CFGS).quantized("int8")
    prompt = [3, 4, 5, 6]
    for name in INT8_BANK_SLOTS:
        solo = (ModelRuntime(CFG, PARAMS, adapters=adapters[name],
                             peft_cfg=MIXED_CFGS[name])
                if name else RT).quantized("int8")
        feed = _solo(prompt, 5, adapters.get(name), MIXED_CFGS.get(name),
                     quantize=True)[:4]
        want = _forced_logits(solo, prompt, feed)
        got = _forced_logits(qrt, prompt, feed, adapter=name)
        tol = INT8_BANK_RTOL * np.abs(want).max()
        err = np.abs(got - want).max()
        assert err <= tol, (name, err, tol)
        top2 = np.sort(want, axis=-1)[:, -2:]
        decisive = top2[:, 1] - top2[:, 0] > 2 * tol
        np.testing.assert_array_equal(got.argmax(-1)[decisive],
                                      want.argmax(-1)[decisive])
        for other in INT8_BANK_SLOTS:
            if other != name:
                wrong = _forced_logits(qrt, prompt, feed, adapter=other)
                assert np.abs(wrong - want).max() > tol, (name, other)
    # the bank's factors are never quantized, whatever the method
    for leaf in jax.tree.leaves(qrt.bank.tree):
        assert jnp.issubdtype(leaf.dtype, jnp.floating)


def test_bank_rejects_weight_side_only_methods():
    """Satellite regression: the old blanket "gsoft only" error is gone —
    capability comes from the registry, and the refusal names the method
    and the reason (lora: weight-side only)."""
    with pytest.raises(ValueError, match=r"'lora'.*weight-side"):
        RT.attach({"t": _tuned_adapters(3, MIXED_CFGS["alice"])},
                     {"t": peft_lib.PEFTConfig(method="lora")})
    with pytest.raises(ValueError, match="double_gsoft.*output-side"):
        RT.attach({}, peft_lib.PEFTConfig(method="double_gsoft"))
    # bankable non-gsoft methods are now ACCEPTED (the old error path
    # rejected everything but gsoft)
    bank = peft_lib.build_adapter_bank(
        peft_lib.PEFTConfig(method="boft", block_size=8), PARAMS, {})
    assert bank.num_slots == 1


def test_bank_config_consistency_errors():
    gs_cfg = MIXED_CFGS["alice"]
    other_targets = dataclasses.replace(gs_cfg, target_patterns=(r".*/wq$",))
    with pytest.raises(ValueError, match="target_patterns"):
        peft_lib.build_adapter_bank(
            {"a": gs_cfg, "b": other_targets}, PARAMS,
            {"a": _tuned_adapters(1, gs_cfg),
             "b": _tuned_adapters(2, other_targets)})
    with pytest.raises(ValueError, match="one config per adapter"):
        peft_lib.build_adapter_bank({"a": gs_cfg}, PARAMS,
                                    {"a": {}, "b": {}})
    # same method, different config -> one stack per method is violated
    gs16 = dataclasses.replace(gs_cfg, block_size=16)
    with pytest.raises(ValueError, match="one stack"):
        peft_lib.build_adapter_bank(
            {"a": gs_cfg, "b": gs16}, PARAMS,
            {"a": _tuned_adapters(1, gs_cfg),
             "b": _tuned_adapters(2, gs16)})


def test_checkpoint_roundtrip_preserves_method_metadata(tmp_path):
    """AdapterStore.save -> load_adapter_checkpoints keeps each adapter's
    method + spec (mixed-method bank), and the restored bank serves
    identical tokens."""
    adapters = _mixed_adapters()
    AdapterStore.from_adapters(adapters, MIXED_CFGS).save(str(tmp_path))
    restored, cfgs = load_adapter_checkpoints([str(tmp_path)])
    assert isinstance(cfgs, dict)
    assert {n: c.method for n, c in cfgs.items()} == {
        "alice": "gsoft", "bob": "boft", "carol": "householder"}
    assert cfgs == MIXED_CFGS
    prompt = [4, 5, 6]
    outs = []
    for adp, cfg in ((adapters, MIXED_CFGS), (restored, cfgs)):
        eng = ServeEngine(RT.attach(adp, cfg), max_batch=1, max_len=32,
                          eos_id=-1)
        rids = [eng.add_request(prompt, max_new_tokens=3, adapter=n)
                for n in ("bob", "carol")]
        res = eng.run()
        outs.append([res[r] for r in rids])
    assert outs[0] == outs[1]
    # homogeneous saves still load as ONE config (back-compat surface)
    single = peft_lib.PEFTConfig(method="gsoft", block_size=8)
    AdapterStore.from_adapters(
        {"x": _tuned_adapters(9, single)}, single).save(str(tmp_path / "homo"))
    _, cfg2 = load_adapter_checkpoints([str(tmp_path / "homo")])
    assert cfg2 == single


# ---------------------------------------------------------------------------
# extensibility: a new parametrization is ONE registry entry
# ---------------------------------------------------------------------------

def test_new_method_is_one_registry_entry_and_quant_gate():
    """Registering a record is all it takes to train/serve a new method;
    the quant_compatible flag gates quantized serving."""
    probe = dataclasses.replace(
        methods_lib.get("householder"), method="probe_hoft",
        quant_compatible=False)
    methods_lib.register(probe)
    try:
        cfg = peft_lib.PEFTConfig(method="probe_hoft", reflections=4)
        spec = peft_lib.spec_for(cfg, (16, 16))
        p = ad.init_adapter(spec, jax.random.PRNGKey(0))
        W = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
        np.testing.assert_allclose(np.asarray(ad.materialize(spec, p, W)),
                                   np.asarray(W), atol=1e-6)
        adapters = {"t": _tuned_adapters(5, cfg)}
        bank_rt = RT.attach(adapters, cfg)       # banks fine
        assert bank_rt.bank.bank_methods == ("probe_hoft",)
        with pytest.raises(ValueError, match="probe_hoft"):
            bank_rt.quantized("int8")               # ...but not over int8
        with pytest.raises(ValueError, match="probe_hoft"):
            RT.quantized("int8").attach(adapters, cfg)
    finally:
        del methods_lib._METHODS["probe_hoft"]
        peft_lib.spec_for.cache_clear()


# ---------------------------------------------------------------------------
# the grep guard, mirrored in-tree (CI lint step "method-registry
# dispatch guard")
# ---------------------------------------------------------------------------

def test_no_method_string_dispatch_outside_registry():
    """Raw ``method ==`` / ``spec.method ==`` dispatch outside
    core/methods.py forks the registry — models/api and serve must hold
    zero method-string conditionals."""
    pat = re.compile(r"\bmethod\s*==")
    offenders = []
    for path in SRC.rglob("*.py"):
        if path.name == "methods.py" and path.parent.name == "core":
            continue
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{path.relative_to(SRC)}:{i}: {line.strip()}")
    assert not offenders, "\n".join(offenders)
