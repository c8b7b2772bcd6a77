"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept across shapes and dtypes, plus oracle-vs-core consistency checks."""
import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gs
from repro.kernels import dispatch, ref
from repro.kernels.bdmm import bdmm_pallas, bdmm_rows_pallas
from repro.kernels.gs_fused import gs_fused_pallas
from repro.kernels.ssd import ssd_pallas
from repro.kernels import ops
from repro.obs.metrics import REGISTRY

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=1e-5, rtol=1e-5) if dtype == jnp.float32 else \
        dict(atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# bdmm
# ---------------------------------------------------------------------------

BDMM_SHAPES = [
    # (r, b_out, b_in, T)
    (4, 8, 8, 16),
    (8, 16, 16, 128),
    (2, 8, 4, 33),       # rectangular blocks, ragged T (padding path)
    (16, 4, 4, 250),
    (1, 32, 32, 7),
    (3, 5, 9, 64),       # odd sizes
]


@pytest.mark.parametrize("r,bo,bi,t", BDMM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bdmm_kernel_vs_ref(r, bo, bi, t, dtype):
    k1, k2 = jax.random.split(KEY)
    blocks = jax.random.normal(k1, (r, bo, bi), dtype)
    x = jax.random.normal(k2, (t, r * bi), dtype)
    got = bdmm_pallas(blocks, x, interpret=True)
    want = ref.bdmm_ref(blocks, x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_bdmm_ref_vs_core():
    """Oracle agrees with core.gs.block_diag_matmul (same contract)."""
    blocks = jax.random.normal(KEY, (4, 8, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 32))
    np.testing.assert_allclose(np.asarray(ref.bdmm_ref(blocks, x)),
                               np.asarray(gs.block_diag_matmul(blocks, x)),
                               atol=1e-5)


@pytest.mark.parametrize("token_tile", [8, 32, 128])
@pytest.mark.parametrize("group_tile", [1, 2, 4])
def test_bdmm_tilings(token_tile, group_tile):
    blocks = jax.random.normal(KEY, (4, 8, 8))
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 32))
    got = bdmm_pallas(blocks, x, token_tile=token_tile,
                      group_tile=group_tile, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.bdmm_ref(blocks, x)), atol=1e-5)


# ---------------------------------------------------------------------------
# gs_fused
# ---------------------------------------------------------------------------

GS_SHAPES = [(4, 4, 16), (8, 8, 128), (2, 16, 33), (16, 16, 64), (4, 32, 20)]


@pytest.mark.parametrize("r,b,t", GS_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gs_fused_kernel_vs_ref(r, b, t, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    L = jax.random.normal(k1, (r, b, b), dtype)
    R = jax.random.normal(k2, (r, b, b), dtype)
    x = jax.random.normal(k3, (t, r * b), dtype)
    got = np.asarray(gs_fused_pallas(L, R, x, interpret=True), np.float32)
    # fp32 ground truth: the fused kernel keeps fp32 through the middle (no
    # inter-stage bf16 rounding), so compare against the fp32 oracle with a
    # magnitude-scaled bf16 tolerance rather than the twice-rounded bf16 ref.
    want = np.asarray(ref.gs_fused_ref(L.astype(jnp.float32),
                                       R.astype(jnp.float32),
                                       x.astype(jnp.float32)), np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    else:
        atol = 0.02 * np.abs(want).max()
        np.testing.assert_allclose(got, want, atol=atol, rtol=0.03)


def test_gs_fused_ref_vs_core_gsoft():
    """Oracle must equal core.gs.gs_apply on the GSOFT layout — the kernel
    therefore computes exactly the paper's Q."""
    r, b = 8, 8
    d = r * b
    L = jax.random.normal(KEY, (r, b, b))
    R = jax.random.normal(jax.random.PRNGKey(3), (r, b, b))
    x = jax.random.normal(jax.random.PRNGKey(4), (5, d))
    lay = gs.gsoft_layout(d, b)
    np.testing.assert_allclose(np.asarray(ref.gs_fused_ref(L, R, x)),
                               np.asarray(gs.gs_apply(lay, L, R, x)),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------

SSD_SHAPES = [
    # (T, H, P, N, chunk)
    (32, 2, 8, 8, 8),
    (64, 1, 16, 16, 16),
    (128, 4, 8, 16, 32),
    (16, 3, 4, 4, 16),    # single chunk
    (48, 2, 8, 8, 16),
]


def _ssd_inputs(t, h, p, n, dtype=jnp.float32):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (t, h, p), dtype)
    loga = -jnp.abs(jax.random.normal(ks[1], (t, h), dtype)) * 0.3
    B = jax.random.normal(ks[2], (t, h, n), dtype) * 0.5
    C = jax.random.normal(ks[3], (t, h, n), dtype) * 0.5
    return x, loga, B, C


@pytest.mark.parametrize("t,h,p,n,chunk", SSD_SHAPES)
def test_ssd_chunked_ref_vs_sequential(t, h, p, n, chunk):
    x, loga, B, C = _ssd_inputs(t, h, p, n)
    seq = ref.ssd_ref(x, loga, B, C)
    chk = ref.ssd_chunked_ref(x, loga, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(chk),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("t,h,p,n,chunk", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_vs_ref(t, h, p, n, chunk, dtype):
    x, loga, B, C = _ssd_inputs(t, h, p, n, dtype)
    got = ssd_pallas(x, loga, B, C, chunk=chunk, interpret=True)
    want = ref.ssd_ref(x, loga, B, C)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_state_continuity_across_chunks():
    """The scratch-carried state must make chunked == unchunked exactly."""
    x, loga, B, C = _ssd_inputs(64, 2, 8, 8)
    y1 = ssd_pallas(x, loga, B, C, chunk=8, interpret=True)
    y2 = ssd_pallas(x, loga, B, C, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------

def test_ops_bdmm_batched_dims():
    blocks = jax.random.normal(KEY, (4, 8, 8))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 32))
    for up in (False, True):
        y = ops.bdmm(blocks, x, use_pallas=up)
        assert y.shape == (2, 3, 32)


def test_ops_gs_transform_paths_agree():
    r, b = 4, 8
    L = jax.random.normal(KEY, (r, b, b))
    R = jax.random.normal(jax.random.PRNGKey(6), (r, b, b))
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, r * b))
    y0 = ops.gs_transform(L, R, x, use_pallas=False)
    y1 = ops.gs_transform(L, R, x, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)


# (B, r, b, T): few-token rows (decode) and longer ones; r = 40 and 20
# overhang a group tile of 16 (the kernel cases) or 32 (ops' own tiles)
BANKED_SHAPES = [(3, 4, 8, 5), (2, 8, 16, 33), (1, 2, 8, 7), (1, 40, 8, 1),
                 (3, 40, 8, 2), (32, 20, 8, 1)]


@pytest.mark.parametrize("bsz,r,b,t", BANKED_SHAPES)
def test_ops_bdmm_banked_paths_agree(bsz, r, b, t):
    """Per-row blocks (multi-adapter serving): vmapped Pallas path == ref."""
    blocks = jax.random.normal(KEY, (bsz, r, b, b))
    x = jax.random.normal(jax.random.PRNGKey(8), (bsz, t, r * b))
    y0 = ops.bdmm_banked(blocks, x, use_pallas=False)
    y1 = ops.bdmm_banked(blocks, x, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)


@pytest.mark.parametrize("bsz,r,b,t", BANKED_SHAPES)
@pytest.mark.parametrize("transpose", [False, True])
def test_bdmm_rows_kernel_vs_ref(bsz, r, b, t, transpose):
    """The row-batched kernel == the per-row reference, with either block
    axis contracted, over row tiles (2) and group tiles (16) that overhang
    B and r."""
    blocks = jax.random.normal(KEY, (bsz, r, b, b))
    x = jax.random.normal(jax.random.PRNGKey(8), (bsz, t, r * b))
    got = bdmm_rows_pallas(blocks, x, transpose=transpose, row_tile=2,
                           group_tile=16, interpret=True)
    want = ref.bdmm_banked_ref(
        jnp.swapaxes(blocks, -1, -2) if transpose else blocks, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def _path_counts():
    snap = REGISTRY.snapshot("gs_banked_path.")
    return {k.split(".", 1)[1]: v for k, v in snap.items()}


@pytest.mark.parametrize("bsz,r,b,t", BANKED_SHAPES)
@pytest.mark.parametrize("path", ["rows", "per_row"])
def test_ops_gs_banked_transform_T_paths_agree(bsz, r, b, t, path,
                                               monkeypatch):
    """Per-row transpose rotation: both Pallas paths, each forced on every
    shape, agree with the reference AND with the single-row core
    application per batch row, and count the path they took."""
    monkeypatch.setattr(dispatch, "ROWS_MAX_TOKENS",
                        1 << 30 if path == "rows" else 0)
    L = jax.random.normal(KEY, (bsz, r, b, b))
    R = jax.random.normal(jax.random.PRNGKey(9), (bsz, r, b, b))
    x = jax.random.normal(jax.random.PRNGKey(10), (bsz, t, r * b))
    before = _path_counts().get(path, 0)
    y0 = ops.gs_banked_transform_T(L, R, x, use_pallas=False)
    y1 = ops.gs_banked_transform_T(L, R, x, use_pallas=True)
    assert _path_counts()[path] == before + 1
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)
    lay = gs.gsoft_layout(r * b, b)
    for i in range(bsz):
        want = gs.gs_apply_T(lay, L[i], R[i], x[i])
        np.testing.assert_allclose(np.asarray(y0[i]), np.asarray(want),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(y1[i]), np.asarray(want),
                                   atol=1e-5)


def test_gs_banked_path_by_shape():
    """Decode rows (32 x 1 token) take the row-batched kernel, a prompt
    chunk (1 x 256 tokens) the per-row one: the rule reads the shape."""
    d, b = 8192, 32
    r = d // b
    assert dispatch.banked_gs_rows_tiles(1, 32, r, b, 2) == (16, 32)
    assert dispatch.banked_gs_rows_tiles(256, 1, r, b, 2) is None
    for bsz, t, path in ((32, 1, "rows"), (1, 256, "per_row")):
        L = jax.ShapeDtypeStruct((bsz, r, b, b), jnp.bfloat16)
        x = jax.ShapeDtypeStruct((bsz, t, d), jnp.bfloat16)
        before = _path_counts().get(path, 0)
        jax.eval_shape(lambda l, rr, xx: ops.gs_banked_transform_T(
            l, rr, xx, use_pallas=True), L, L, x)
        assert _path_counts()[path] == before + 1


def test_gs_banked_rows_grads_vs_ref():
    """jax.grad through the row-batched path (custom VJP, per-row backward
    kernels) equals jax.grad through the reference, in x, L and R."""
    bsz, r, b, t = 3, 40, 8, 1
    ks = jax.random.split(KEY, 4)
    L = jax.random.normal(ks[0], (bsz, r, b, b))
    R = jax.random.normal(ks[1], (bsz, r, b, b))
    x = jax.random.normal(ks[2], (bsz, t, r * b))
    cot = jax.random.normal(ks[3], (bsz, t, r * b))
    assert dispatch.banked_gs_rows_tiles(t, bsz, r, b, 4) is not None

    def loss(use_pallas):
        return lambda l, rr, xx: jnp.sum(cot * ops.gs_banked_transform_T(
            l, rr, xx, use_pallas=use_pallas))

    got = jax.grad(loss(True), argnums=(0, 1, 2))(L, R, x)
    want = jax.grad(loss(False), argnums=(0, 1, 2))(L, R, x)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-4)


_MLIR_DTYPES = {"bf16": "bf16", "f32": "f32", "i32": "s32", "i8": "s8"}


def _mlir_shapes(text: str):
    out = []
    for m in re.finditer(r"tensor<([\dx]*)x?([a-z]+\d*)>", text):
        dims = tuple(int(v) for v in m.group(1).split("x") if v)
        out.append((_MLIR_DTYPES[m.group(2)], dims))
    return out


def _bench_flops():
    path = pathlib.Path(__file__).resolve().parents[1] / "bench" / \
        "harness" / "flops.py"
    spec = importlib.util.spec_from_file_location("bench_flops", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("bsz,t,d", [(32, 1, 8192), (32, 1, 29568),
                                     (1, 256, 8192)])
def test_banked_rotation_kernels_keep_the_counted_form(bsz, t, d,
                                                       monkeypatch):
    """Every Pallas call of the banked GS rotation, lowered for the TPU at
    qwen2-72b widths, has two operands (x and (..., r, b, b) blocks) and a
    result shaped and typed like x: the form the benchmark's
    ``bench/harness/flops.py`` counts as ``gs_rotation``, at 2*B*T*d*b
    operations per stage. A kernel that leaves the form would silence
    ``gs_rotation_roofline``."""
    from repro.core import adapters
    flops = _bench_flops()
    b, slots = 32, 33
    r = d // b
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    bank = jax.ShapeDtypeStruct((slots, r, b, b), jnp.float32)
    ids = jax.ShapeDtypeStruct((bsz,), jnp.int32)
    x = jax.ShapeDtypeStruct((bsz, t, d), jnp.bfloat16)
    fn = jax.jit(lambda e, i, xx: adapters.gs_rotate_banked(
        e, i, xx, use_pallas=True))
    text = jax.export.export(fn, platforms=["tpu"])(
        {"L": bank, "R": bank}, ids, x).mlir_module()
    calls = [line for line in text.splitlines()
             if "stablehlo.custom_call @tpu_custom_call" in line]
    assert len(calls) == 2
    for line in calls:
        sig = line.rsplit(" : (", 1)[1]
        ops_text, res_text = sig.split(") -> ", 1)
        opnds, res = _mlir_shapes(ops_text), _mlir_shapes(res_text)
        assert len(opnds) == 2 and res == [opnds[0]]
        assert opnds[1][1][-3:] == (r, b, b)
        assert flops.classify(res, opnds) == "gs_rotation"
        cost = flops.kernel_cost("gs_rotation", res, opnds)
        assert cost[0] == 2.0 * bsz * t * d * b


def test_ops_ssd_batched():
    x, loga, B, C = _ssd_inputs(32, 2, 8, 8)
    xb = jnp.stack([x, x * 0.5])
    lb = jnp.stack([loga, loga])
    Bb = jnp.stack([B, B])
    Cb = jnp.stack([C, C])
    for up in (False, True):
        y = ops.ssd(xb, lb, Bb, Cb, chunk=8, use_pallas=up)
        assert y.shape == xb.shape
        np.testing.assert_allclose(np.asarray(y[0]),
                                   np.asarray(ref.ssd_ref(x, loga, B, C)),
                                   atol=1e-4, rtol=1e-3)
