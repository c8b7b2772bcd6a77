"""Gradient correctness of the differentiable Pallas kernel path.

The custom-VJP rules in kernels/dispatch.py (backward = Pallas kernels in
interpret mode on CPU) are checked three ways:
  * oracle-VJP comparison: jax.grad through the kernel path vs jax.grad
    through the pure-jnp ref.py path, swept over shapes/dtypes including the
    padding path (non-divisible token counts) and group_tile edge cases;
  * jax.test_util.check_grads numerical differentiation (rev mode);
  * end-to-end: the gradient of a GSOFT adapter loss with use_pallas=True
    matches the reference-path gradient to <= 1e-4 (acceptance criterion).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from repro.core import adapters as ad
from repro.core import peft as peft_lib
from repro.kernels import dispatch, ops, ref
from repro.kernels.gs_fused import (gs_fused_T_pallas, gs_fused_bwd_pallas,
                                    gs_fused_grads_pallas)
from repro.models.layers import qlinear

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 1e-4 if dtype == jnp.float32 else 6e-2


def _assert_trees_close(a, b, tol):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x, np.float32), np.asarray(y, np.float32),
        atol=tol, rtol=tol), a, b)


# ---------------------------------------------------------------------------
# bdmm
# ---------------------------------------------------------------------------

BDMM_GRAD_SHAPES = [
    # (r, bo, bi, T) — ragged T exercises the zero-padding path
    (4, 8, 8, 16),
    (2, 8, 4, 33),       # rectangular blocks + padding
    (3, 5, 9, 64),       # odd sizes
    (16, 4, 4, 250),
]


@pytest.mark.parametrize("r,bo,bi,t", BDMM_GRAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bdmm_grads_vs_oracle(r, bo, bi, t, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    blocks = jax.random.normal(k1, (r, bo, bi), dtype)
    x = jax.random.normal(k2, (t, r * bi), dtype)
    cot = jax.random.normal(k3, (t, r * bo), dtype)

    def loss(w, xx, up):
        return jnp.sum(ops.bdmm(w, xx, use_pallas=up).astype(jnp.float32) *
                       cot.astype(jnp.float32))

    gw0, gx0 = jax.grad(loss, argnums=(0, 1))(blocks, x, False)
    gw1, gx1 = jax.grad(loss, argnums=(0, 1))(blocks, x, True)
    _assert_trees_close((gw0, gx0), (gw1, gx1), _tol(dtype))


@pytest.mark.parametrize("group_tile", [1, 2, 4])
@pytest.mark.parametrize("token_tile", [8, 32, 128])
def test_bdmm_grads_tilings(token_tile, group_tile):
    """group_tile edge cases: 1 (no grouping), r (single group step), and a
    non-divisor (5 -> rounded down internally)."""
    r, bo, bi, t = 4, 8, 8, 40
    blocks = jax.random.normal(KEY, (r, bo, bi))
    x = jax.random.normal(jax.random.PRNGKey(1), (t, r * bi))
    tun = dispatch.Tuning(token_tile=token_tile, group_tile=group_tile)

    def loss(w, xx, up, tu=None):
        return jnp.sum(ops.bdmm(w, xx, use_pallas=up, tuning=tu) ** 2)

    want = jax.grad(loss, argnums=(0, 1))(blocks, x, False)
    got = jax.grad(loss, argnums=(0, 1))(blocks, x, True, tun)
    _assert_trees_close(want, got, 1e-4)


def test_bdmm_grads_group_tile_nondivisor():
    r, bo, bi, t = 6, 4, 4, 17
    blocks = jax.random.normal(KEY, (r, bo, bi))
    x = jax.random.normal(jax.random.PRNGKey(2), (t, r * bi))
    tun = dispatch.Tuning(token_tile=16, group_tile=5)   # 5 does not divide 6

    def loss(w, xx):
        return jnp.sum(ops.bdmm(w, xx, use_pallas=True, tuning=tun) ** 2)

    want = jax.grad(lambda w, xx: jnp.sum(ops.bdmm(w, xx) ** 2),
                    argnums=(0, 1))(blocks, x)
    got = jax.grad(loss, argnums=(0, 1))(blocks, x)
    _assert_trees_close(want, got, 1e-4)


def test_bdmm_check_grads_numerical():
    blocks = jax.random.normal(KEY, (3, 4, 4)) * 0.5
    x = jax.random.normal(jax.random.PRNGKey(3), (10, 12)) * 0.5
    check_grads(lambda w, xx: ops.bdmm(w, xx, use_pallas=True),
                (blocks, x), order=1, modes=("rev",), atol=1e-2, rtol=1e-2)


# ---------------------------------------------------------------------------
# gs_transform / gs_transform_T
# ---------------------------------------------------------------------------

GS_GRAD_SHAPES = [(4, 4, 16), (2, 16, 33), (8, 8, 100), (4, 32, 20)]


@pytest.mark.parametrize("r,b,t", GS_GRAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gs_transform_grads_vs_oracle(r, b, t, dtype):
    ks = jax.random.split(KEY, 4)
    L = jax.random.normal(ks[0], (r, b, b), dtype)
    R = jax.random.normal(ks[1], (r, b, b), dtype)
    x = jax.random.normal(ks[2], (t, r * b), dtype)
    cot = jax.random.normal(ks[3], (t, r * b), dtype)

    def loss(p, xx, up):
        y = ops.gs_transform(p["L"], p["R"], xx, use_pallas=up)
        return jnp.sum(y.astype(jnp.float32) * cot.astype(jnp.float32))

    g0 = jax.grad(loss, argnums=(0, 1))({"L": L, "R": R}, x, False)
    g1 = jax.grad(loss, argnums=(0, 1))({"L": L, "R": R}, x, True)
    _assert_trees_close(g0, g1, _tol(dtype) * (1 + b // 8))


@pytest.mark.parametrize("r,b,t", GS_GRAD_SHAPES)
def test_gs_transform_T_grads_vs_oracle(r, b, t):
    ks = jax.random.split(KEY, 4)
    L = jax.random.normal(ks[0], (r, b, b))
    R = jax.random.normal(ks[1], (r, b, b))
    x = jax.random.normal(ks[2], (t, r * b))
    cot = jax.random.normal(ks[3], (t, r * b))

    def loss(p, xx, up):
        return jnp.sum(ops.gs_transform_T(p["L"], p["R"], xx,
                                          use_pallas=up) * cot)

    g0 = jax.grad(loss, argnums=(0, 1))({"L": L, "R": R}, x, False)
    g1 = jax.grad(loss, argnums=(0, 1))({"L": L, "R": R}, x, True)
    _assert_trees_close(g0, g1, 1e-4)


def test_gs_transform_check_grads_numerical():
    r, b, t = 2, 4, 9
    ks = jax.random.split(KEY, 3)
    L = jax.random.normal(ks[0], (r, b, b)) * 0.5
    R = jax.random.normal(ks[1], (r, b, b)) * 0.5
    x = jax.random.normal(ks[2], (t, r * b)) * 0.5
    check_grads(lambda *a: ops.gs_transform(*a, use_pallas=True),
                (L, R, x), order=1, modes=("rev",), atol=1e-2, rtol=1e-2)
    check_grads(lambda *a: ops.gs_transform_T(*a, use_pallas=True),
                (L, R, x), order=1, modes=("rev",), atol=1e-2, rtol=1e-2)


def test_gs_fused_T_kernel_vs_oracle():
    """Transpose rotation kernel == R^T P^T L^T P x oracle == gs_apply_T."""
    from repro.core import gs
    r, b, t = 4, 8, 33
    ks = jax.random.split(KEY, 3)
    L = jax.random.normal(ks[0], (r, b, b))
    R = jax.random.normal(ks[1], (r, b, b))
    x = jax.random.normal(ks[2], (t, r * b))
    got = gs_fused_T_pallas(L, R, x, interpret=True)
    want = ref.gs_fused_T_ref(L, R, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    lay = gs.gsoft_layout(r * b, b)
    np.testing.assert_allclose(np.asarray(want),
                               np.asarray(gs.gs_apply_T(lay, L, R, x)),
                               atol=1e-5)


def test_gs_fused_bwd_kernel_vs_autodiff():
    """The fused (dx, dL, dR) kernel against XLA autodiff of the oracle,
    with multiple token tiles so the in-place fp32 accumulation is hit."""
    r, b, t = 4, 8, 50
    ks = jax.random.split(KEY, 4)
    L = jax.random.normal(ks[0], (r, b, b))
    R = jax.random.normal(ks[1], (r, b, b))
    x = jax.random.normal(ks[2], (t, r * b))
    dy = jax.random.normal(ks[3], (t, r * b))
    dx, dL, dR = gs_fused_bwd_pallas(L, R, x, dy, token_tile=8,
                                     interpret=True)
    gL, gR, gx = jax.grad(
        lambda L_, R_, x_: jnp.sum(ref.gs_fused_ref(L_, R_, x_) * dy),
        argnums=(0, 1, 2))(L, R, x)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(gx), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dL), np.asarray(gL), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dR), np.asarray(gR), atol=1e-4)
    # grads-only variant (no dx slab) agrees
    dL2, dR2 = gs_fused_grads_pallas(L, R, x, dy, token_tile=8,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(dL2), np.asarray(dL), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dR2), np.asarray(dR), atol=1e-6)


# ---------------------------------------------------------------------------
# GSOFT adapter loss end-to-end (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["gsoft", "double_gsoft"])
def test_gsoft_adapter_loss_grad_matches_reference(method):
    """jax.grad of an adapter loss with use_pallas=True vs the reference
    path, fp32, <= 1e-4 (interpret mode on CPU)."""
    spec = ad.AdapterSpec(method=method, d_in=32, d_out=24, block_size=8,
                          block_size_out=4)
    spec_pallas = dataclasses.replace(spec, use_pallas=True)
    key = jax.random.PRNGKey(7)
    params = ad.init_adapter(spec, key)
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(key, p.shape), params)
    W = jax.random.normal(jax.random.PRNGKey(8), (32, 24))
    x = jax.random.normal(jax.random.PRNGKey(9), (16, 32))
    tgt = jax.random.normal(jax.random.PRNGKey(10), (16, 24))

    def loss(p, s):
        w_eff = ad.materialize(s, p, W)
        return jnp.mean((x @ w_eff - tgt) ** 2)

    assert np.isclose(float(loss(params, spec)),
                      float(loss(params, spec_pallas)), atol=1e-5)
    g_ref = jax.grad(loss)(params, spec)
    g_ker = jax.grad(loss)(params, spec_pallas)
    _assert_trees_close(g_ref, g_ker, 1e-4)


def test_peft_tree_grad_matches_reference():
    """materialize_tree(lazy=True) applied through qlinear (the train-step
    path) with use_pallas=True: adapter grads through a whole params tree
    match the reference path."""
    params = {
        "attn": {"wq": jax.random.normal(KEY, (32, 32)),
                 "wo": jax.random.normal(jax.random.PRNGKey(1), (32, 32))},
    }
    cfg = peft_lib.PEFTConfig(method="gsoft", block_size=8)
    cfg_pallas = dataclasses.replace(cfg, use_pallas=True)
    adapters = peft_lib.init_peft(cfg, params, jax.random.PRNGKey(2))
    adapters = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(KEY, p.shape), adapters)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 32))

    def loss(adp, c):
        eff = peft_lib.materialize_tree(c, params, adp, lazy=True)
        assert isinstance(eff["attn"]["wq"], peft_lib.AdaptedWeight)
        h = jnp.tanh(qlinear(x, eff["attn"]["wq"]))
        return jnp.mean(qlinear(h, eff["attn"]["wo"]) ** 2)

    g0 = jax.grad(loss)(adapters, cfg)
    g1 = jax.grad(loss)(adapters, cfg_pallas)
    _assert_trees_close(g0, g1, 1e-4)


# ---------------------------------------------------------------------------
# dispatch registry semantics
# ---------------------------------------------------------------------------

def test_dispatch_tuning_precedence():
    key = dispatch.gs_key(4, 8, jnp.float32)
    try:
        assert dispatch.get_tuning(key).token_tile == 128      # heuristic
        dispatch._TUNED[key] = dispatch.Tuning(token_tile=64)
        assert dispatch.get_tuning(key).token_tile == 64       # autotuned
        dispatch.install_tunings((("gs", 4, 8, 32),))          # config wins
        assert dispatch.get_tuning(key).token_tile == 32
    finally:
        dispatch.clear_tunings()


def test_dispatch_install_replaces_previous_config():
    """install_tunings is per-config: a later install clears the previous
    config's entries instead of accumulating them."""
    key_a = dispatch.gs_key(4, 8, jnp.float32)
    key_b = dispatch.gs_key(2, 16, jnp.float32)
    try:
        dispatch.install_tunings((("gs", 4, 8, 32),))
        assert dispatch.get_tuning(key_a).token_tile == 32
        dispatch.install_tunings((("gs", 2, 16, 64),))
        assert dispatch.get_tuning(key_b).token_tile == 64
        assert dispatch.get_tuning(key_a).token_tile == 128   # back to default
    finally:
        dispatch.clear_tunings()


def test_dispatch_autotune_caches():
    try:
        tun = dispatch.autotune_gs(2, 4, 16, token_tiles=(8, 16), iters=1)
        assert dispatch.gs_key(2, 4, jnp.float32) in dispatch._TUNED
        assert dispatch.autotune_gs(2, 4, 16, token_tiles=(8, 16),
                                    iters=1) == tun
        tun_b = dispatch.autotune_bdmm(2, 4, 4, 16, token_tiles=(8, 16),
                                       iters=1)
        assert tun_b.token_tile in (8, 16)
    finally:
        dispatch.clear_tunings()


def test_dispatch_tuned_result_is_used_and_correct():
    """A registered tuning actually drives the launch and stays correct."""
    r, b, t = 2, 8, 12
    ks = jax.random.split(KEY, 3)
    L = jax.random.normal(ks[0], (r, b, b))
    R = jax.random.normal(ks[1], (r, b, b))
    x = jax.random.normal(ks[2], (t, r * b))
    try:
        dispatch.register_tuning(dispatch.gs_key(r, b, jnp.float32),
                                 dispatch.Tuning(token_tile=4))
        y = ops.gs_transform(L, R, x, use_pallas=True)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(ref.gs_fused_ref(L, R, x)),
                                   atol=1e-5)
    finally:
        dispatch.clear_tunings()
