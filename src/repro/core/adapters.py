"""PEFT adapters: GSOFT / Double GSOFT (the paper), plus the classes it
unifies or compares against — OFT (block-diagonal), BOFT (block butterfly),
Householder products (HOFT), and LoRA.

All adapters are *functional*: an ``AdapterSpec`` (static dataclass) plus a
params pytree.  The framework applies them **weight-side**:

    W_eff = materialize(spec, params, W_frozen)

inside the jitted step — for orthogonal methods W_eff = Q @ W (Q acts on the
input dim, preserving the frozen weight's output geometry), for Double GSOFT
W_eff = Q_U @ W @ Q_V, for LoRA W_eff = W + (alpha/r) A B.  Identity init
guarantees W_eff == W at step 0.  ``merge`` bakes the adapter into the weight
for inference (zero overhead — paper §6.1).

Per-method behavior is defined by the implementation functions in this
module, *wired* by the ``MethodOps`` records in ``core.methods`` — the
public entry points below (``init_adapter`` / ``materialize`` / ``merge`` /
``apply_activation_side`` / ``num_adapter_params``) dispatch exclusively
through that registry; an unknown method raises a ``KeyError`` naming what
is registered.

Weights with leading batch dims (e.g. stacked MoE experts (E, d_in, d_out))
get independent adapters per batch element, vmapped.

Weight convention: W has shape (d_in, d_out), used as y = x @ W.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops

from . import gs
from .gs import gsoft_layout, pick_block_size
from .orthogonal import cayley, skew
from .permutations import PermSpec, apply_perm

Array = jnp.ndarray
Params = Dict[str, Array]


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdapterSpec:
    """Static description of one adapter attached to one weight."""
    method: str                    # any name registered in core.methods
    d_in: int
    d_out: int
    block_size: int = 32           # orthogonal methods (input side)
    block_size_out: int = 0        # double_gsoft output side (0 -> same rule)
    rank: int = 8                  # lora
    alpha: float = 16.0            # lora scaling
    boft_factors: int = 2          # BOFT m
    reflections: int = 4           # householder factor count (even)
    givens_rounds: int = 4         # givens brick-wall round count
    neumann_order: Optional[int] = None   # approximate Cayley (perf option)
    use_scale: bool = False        # learnable per-output magnitude
    use_pallas: bool = False       # GS rotations via the Pallas kernel path
    # leading batch dims of the weight (scan-stacked layers, MoE experts, ...)
    batch: Tuple[int, ...] = ()

    def resolved_block(self, d: int, b: int) -> int:
        return b if d % b == 0 and (d // b) <= b else pick_block_size(d, b)


# ---------------------------------------------------------------------------
# BOFT butterfly permutations
# ---------------------------------------------------------------------------

def butterfly_sigma(d: int, b: int, level: int) -> np.ndarray:
    """Gather order for BOFT butterfly level (1-indexed).

    Half-blocks of size b/2 are paired at half-block stride 2^(level-1):
    level 1 groups contiguous blocks; deeper levels pair at doubling
    distance, reaching density at m = 1 + log2(d/b) (BOFT's bound).
    """
    if b % 2 and level > 1:
        raise ValueError("BOFT butterfly needs even block size")
    h = b // 2 if b > 1 else 1
    nh = d // h
    s = 2 ** (level - 1)
    if nh % (2 * s):
        raise ValueError(f"butterfly level {level} invalid for d={d}, b={b}: "
                         f"{nh} half-blocks not divisible by {2 * s}")
    order = []
    for base in range(0, nh, 2 * s):
        for off in range(s):
            p1, p2 = base + off, base + off + s
            order.extend(range(p1 * h, (p1 + 1) * h))
            order.extend(range(p2 * h, (p2 + 1) * h))
    return np.asarray(order)


def max_butterfly_levels(d: int, b: int) -> int:
    """Deepest valid level: level l tiles the d/(b/2) half-blocks into
    groups of 2^l, so it needs 2^l | num_half_blocks (hypothesis-found edge:
    r not a power of two caps the depth)."""
    nh = d // max(b // 2, 1)
    lvl = 0
    while nh % (2 ** (lvl + 1)) == 0 and 2 ** (lvl + 1) <= nh:
        lvl += 1
    return max(1, lvl)


def _boft_depth(spec: AdapterSpec, b: int) -> int:
    return min(spec.boft_factors, max_butterfly_levels(spec.d_in, b))


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _maybe_batch(shape: Tuple[int, ...], batch: Tuple[int, ...]) -> Tuple[int, ...]:
    return tuple(batch) + shape


def _stack_slots(spec: AdapterSpec, identity: Params, processed) -> Params:
    """Stack [per-slot factors] along a new A axis placed after any
    scan-stacked weight batch dims (so the model's layer scan slices the
    bank alongside the weights). ``processed``: list of Params-or-None
    (None -> this method's identity, i.e. the slot belongs to the base
    model or to an adapter of a different method)."""
    axis = len(spec.batch)
    out: Params = {}
    for key, ident in identity.items():
        out[key] = jnp.stack([ident if p is None else p[key]
                              for p in processed], axis=axis)
    return out


# ---------------------------------------------------------------------------
# GSOFT  (Q = P^T L P R — the paper's two-factor GS rotation)
# ---------------------------------------------------------------------------

def _gs_rotate(d: int, b: int, L_k: Array, R_k: Array, W: Array,
               neumann: Optional[int], transpose_side: bool,
               use_pallas: bool = False) -> Array:
    """Apply Q = P^T L P R (orthogonal GS) to W.

    transpose_side=False:  Q @ W    (Q on rows / input dim)
    transpose_side=True:   W @ Q    (Q on columns / output dim)

    use_pallas routes the rotation through the fused GS kernels (forward
    AND backward via their custom-VJP rules); the columns/rows of W play
    the token role on the kernel's lane axis.

    Perf (§Perf iteration A): the Cayley solve stays fp32 but the rotated
    blocks are cast to W's dtype before the block matmuls — bf16 weights
    rotate in bf16, halving the weight-sized HBM traffic of the
    materialization. Orthogonality error at bf16 is ~1e-2 relative
    (benchmarks/micro_gs.py) on blocks whose product preserves norms.
    """
    lay = gsoft_layout(d, b)
    L = cayley(skew(L_k), neumann_order=neumann).astype(W.dtype)
    R = cayley(skew(R_k), neumann_order=neumann).astype(W.dtype)
    if transpose_side:
        if use_pallas:
            return kernel_ops.gs_transform_T(L, R, W, use_pallas=True)
        return gs.gs_apply_T(lay, L, R, W)       # rows w -> w^T Q, i.e. W @ Q
    if use_pallas:
        WT = jnp.swapaxes(W, -1, -2)             # columns of W as "tokens"
        return jnp.swapaxes(kernel_ops.gs_transform(L, R, WT,
                                                    use_pallas=True), -1, -2)
    return gs.gs_matmul(lay, L, R, W)            # Q @ W


def gsoft_init(spec: AdapterSpec, key: jax.Array, dtype=jnp.float32) -> Params:
    del key  # orthogonal methods start at Q = I (K = 0)
    b_in = spec.resolved_block(spec.d_in, spec.block_size)
    lay = gsoft_layout(spec.d_in, b_in)
    return {"L": jnp.zeros(_maybe_batch(lay.lspec.param_shape, spec.batch), dtype),
            "R": jnp.zeros(_maybe_batch(lay.rspec.param_shape, spec.batch), dtype)}


def gsoft_materialize(spec: AdapterSpec, params: Params, W: Array) -> Array:
    b = spec.resolved_block(spec.d_in, spec.block_size)
    return _gs_rotate(spec.d_in, b, params["L"], params["R"], W,
                      spec.neumann_order, transpose_side=False,
                      use_pallas=spec.use_pallas)


def gsoft_apply_T(spec: AdapterSpec, params: Params, x: Array) -> Array:
    """x -> x Q = (Q^T x^T)^T: rotate the activations instead of the weight."""
    b = spec.resolved_block(spec.d_in, spec.block_size)
    lay = gsoft_layout(spec.d_in, b)
    # rotate in the activation dtype (bf16 activations, bf16 blocks), as
    # the weight-side path rotates in the weight dtype
    L = cayley(skew(params["L"]), neumann_order=spec.neumann_order)
    R = cayley(skew(params["R"]), neumann_order=spec.neumann_order)
    L, R = L.astype(x.dtype), R.astype(x.dtype)
    if spec.use_pallas:
        return kernel_ops.gs_transform_T(L, R, x, use_pallas=True)
    return gs.gs_apply_T(lay, L, R, x)


def gsoft_param_count(spec: AdapterSpec) -> int:
    b = spec.resolved_block(spec.d_in, spec.block_size)
    return 2 * (spec.d_in // b) * b * b


def gsoft_bank_build(spec: AdapterSpec, params_by_slot) -> Params:
    """{"L": (..., A, r, b, b), "R": ...} of PRE-ORTHOGONALIZED blocks (the
    Cayley map runs once at build time — adapters are frozen when serving)."""
    b = spec.resolved_block(spec.d_in, spec.block_size)
    lay = gsoft_layout(spec.d_in, b)
    eye = jnp.broadcast_to(jnp.eye(b, dtype=jnp.float32),
                           _maybe_batch(lay.lspec.param_shape, spec.batch))
    processed = [None if p is None else
                 {k: cayley(skew(p[k].astype(jnp.float32)),
                            neumann_order=spec.neumann_order)
                  for k in ("L", "R")}
                 for p in params_by_slot]
    return _stack_slots(spec, {"L": eye, "R": eye}, processed)


def gsoft_bank_shard_axes(factor: str, shape) -> "int | None":
    """Serve-time TP hook (``MethodOps.bank_shard_axes``): a GSOFT bank
    stack {"L"/"R": (..., A, r, b, b)} may split its BLOCK axis r over the
    mesh 'model' axis — the per-row gather (``jnp.take`` over A) and the
    blockwise transform are both elementwise in r, so the split needs no
    collectives until the (already TP-sharded) base matmul. Only worth it
    for banks that outgrow replication (thousands of resident slots)."""
    if factor in ("L", "R") and len(shape) >= 4:
        return len(shape) - 3            # ...the r (block) axis
    return None


def gs_rotate_banked(entry: Params, ids: Array, x: Array,
                     use_pallas: bool = False) -> Array:
    """Per-row-indexed activation-side GSOFT: row i of x gets x_i Q_{ids[i]}.

    ``entry``: a ``gsoft_bank_build`` stack — {"L": (A, r, b, b), "R": ...}
    pre-orthogonalized blocks over A bank slots; slot 0 is the identity.
    Any scan-stacked layer dims have already been sliced off by the model's
    layer scan. ids: (B,) int32 slot per batch row; x: (B, T, d).

    Cost is O(B*T*b*d) — the same per-token scaling argument that makes GS
    rotations serviceable per-request where a dense OFT rotation (O(d^2))
    would not be.
    """
    # ids are bank slots, always in range: "clip" lets the gather fuse with
    # the cast, where the default "fill" adds a masking pass over the blocks
    L = jnp.take(entry["L"], ids, axis=0, mode="clip").astype(x.dtype)
    R = jnp.take(entry["R"], ids, axis=0, mode="clip").astype(x.dtype)
    return kernel_ops.gs_banked_transform_T(L, R, x, use_pallas=use_pallas)


def gsoft_quant_fuse(entry: Params, ids: Array, dtype) -> Tuple[Array, Array]:
    """Per-row (L, R) blocks in ``dtype`` for the fused rotate + quantized
    matmul kernel (``ops.gs_q_matmul_banked`` — rotations stay bf16 over
    int8 base weights, QOFT rationale in DESIGN.md)."""
    L = jnp.take(entry["L"], ids, axis=0).astype(dtype)
    R = jnp.take(entry["R"], ids, axis=0).astype(dtype)
    return L, R


# ---------------------------------------------------------------------------
# Double GSOFT  (W_eff = Q_U W Q_V)
# ---------------------------------------------------------------------------

def double_gsoft_init(spec: AdapterSpec, key: jax.Array,
                      dtype=jnp.float32) -> Params:
    p = gsoft_init(spec, key, dtype)
    b_out = spec.resolved_block(spec.d_out,
                                spec.block_size_out or spec.block_size)
    lay_v = gsoft_layout(spec.d_out, b_out)
    p["L_v"] = jnp.zeros(_maybe_batch(lay_v.lspec.param_shape, spec.batch), dtype)
    p["R_v"] = jnp.zeros(_maybe_batch(lay_v.rspec.param_shape, spec.batch), dtype)
    return p


def double_gsoft_materialize(spec: AdapterSpec, params: Params,
                             W: Array) -> Array:
    b_in = spec.resolved_block(spec.d_in, spec.block_size)
    Wf = _gs_rotate(spec.d_in, b_in, params["L"], params["R"], W,
                    spec.neumann_order, transpose_side=False,
                    use_pallas=spec.use_pallas)
    b_out = spec.resolved_block(spec.d_out,
                                spec.block_size_out or spec.block_size)
    return _gs_rotate(spec.d_out, b_out, params["L_v"], params["R_v"], Wf,
                      spec.neumann_order, transpose_side=True,
                      use_pallas=spec.use_pallas)


def double_gsoft_param_count(spec: AdapterSpec) -> int:
    b_out = spec.resolved_block(spec.d_out,
                                spec.block_size_out or spec.block_size)
    return gsoft_param_count(spec) + 2 * (spec.d_out // b_out) * b_out * b_out


# ---------------------------------------------------------------------------
# OFT  (block-diagonal Q)
# ---------------------------------------------------------------------------

def oft_init(spec: AdapterSpec, key: jax.Array, dtype=jnp.float32) -> Params:
    del key
    b = spec.resolved_block(spec.d_in, spec.block_size)
    r = spec.d_in // b
    return {"K": jnp.zeros(_maybe_batch((r, b, b), spec.batch), dtype)}


def oft_materialize(spec: AdapterSpec, params: Params, W: Array) -> Array:
    """Block-diagonal orthogonal Q @ W (OFT)."""
    Q = cayley(skew(params["K"]), neumann_order=spec.neumann_order)
    WT = jnp.swapaxes(W, -1, -2)                 # (d_out, d_in)
    return jnp.swapaxes(gs.block_diag_matmul(Q, WT), -1, -2)


def oft_apply_T(spec: AdapterSpec, params: Params, x: Array) -> Array:
    Q = cayley(skew(params["K"]), neumann_order=spec.neumann_order)
    return gs.block_diag_matmul(jnp.swapaxes(Q, -1, -2), x)


def oft_param_count(spec: AdapterSpec) -> int:
    b = spec.resolved_block(spec.d_in, spec.block_size)
    return (spec.d_in // b) * b * b


def oft_bank_build(spec: AdapterSpec, params_by_slot) -> Params:
    b = spec.resolved_block(spec.d_in, spec.block_size)
    r = spec.d_in // b
    eye = jnp.broadcast_to(jnp.eye(b, dtype=jnp.float32),
                           _maybe_batch((r, b, b), spec.batch))
    processed = [None if p is None else
                 {"Q": cayley(skew(p["K"].astype(jnp.float32)),
                              neumann_order=spec.neumann_order)}
                 for p in params_by_slot]
    return _stack_slots(spec, {"Q": eye}, processed)


def oft_rotate_banked(entry: Params, ids: Array, x: Array,
                      use_pallas: bool = False) -> Array:
    """Per-row x_i Q_{ids[i]} for block-diagonal Q: a banked bdmm with the
    per-row blocks transposed (row-vector application). Pallas path =
    the vmapped bdmm kernel (``dispatch.bdmm_key``)."""
    Q = jnp.take(entry["Q"], ids, axis=0).astype(x.dtype)      # (B, r, b, b)
    return kernel_ops.bdmm_banked(jnp.swapaxes(Q, -1, -2), x,
                                  use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# BOFT  (butterfly product Q = B_m .. B_1)
# ---------------------------------------------------------------------------

def boft_init(spec: AdapterSpec, key: jax.Array, dtype=jnp.float32) -> Params:
    del key
    b = spec.resolved_block(spec.d_in, spec.block_size)
    m = _boft_depth(spec, b)
    r = spec.d_in // b
    return {"K": jnp.zeros(_maybe_batch((m, r, b, b), spec.batch), dtype)}


def boft_materialize(spec: AdapterSpec, params: Params, W: Array) -> Array:
    """Q = B_m .. B_1 with butterfly factors; returns Q @ W."""
    b = spec.resolved_block(spec.d_in, spec.block_size)
    d = spec.d_in
    m = params["K"].shape[0]
    Q = cayley(skew(params["K"]), neumann_order=spec.neumann_order)
    WT = jnp.swapaxes(W, -1, -2)                 # columns of W as vectors
    y = WT
    for lvl in range(m):
        sig = butterfly_sigma(d, b, lvl + 1)
        spec_p = PermSpec.from_sigma(sig)
        y = apply_perm(y, spec_p)                # group
        y = gs.block_diag_matmul(Q[lvl], y)      # rotate
        y = apply_perm(y, spec_p.inverse())      # scatter back
    return jnp.swapaxes(y, -1, -2)


def boft_apply_T(spec: AdapterSpec, params: Params, x: Array) -> Array:
    """x -> x Q = (Q^T x^T)^T: levels in reverse order, blocks transposed."""
    b = spec.resolved_block(spec.d_in, spec.block_size)
    m = params["K"].shape[0]
    Q = cayley(skew(params["K"]), neumann_order=spec.neumann_order)
    y = x
    for lvl in reversed(range(m)):
        sig = butterfly_sigma(spec.d_in, b, lvl + 1)
        spec_p = PermSpec.from_sigma(sig)
        y = apply_perm(y, spec_p)
        y = gs.block_diag_matmul(jnp.swapaxes(Q[lvl], -1, -2), y)
        y = apply_perm(y, spec_p.inverse())
    return y


def boft_param_count(spec: AdapterSpec) -> int:
    b = spec.resolved_block(spec.d_in, spec.block_size)
    return _boft_depth(spec, b) * (spec.d_in // b) * b * b


def boft_bank_build(spec: AdapterSpec, params_by_slot) -> Params:
    b = spec.resolved_block(spec.d_in, spec.block_size)
    m = _boft_depth(spec, b)
    r = spec.d_in // b
    eye = jnp.broadcast_to(jnp.eye(b, dtype=jnp.float32),
                           _maybe_batch((m, r, b, b), spec.batch))
    processed = [None if p is None else
                 {"Q": cayley(skew(p["K"].astype(jnp.float32)),
                              neumann_order=spec.neumann_order)}
                 for p in params_by_slot]
    return _stack_slots(spec, {"Q": eye}, processed)


def boft_rotate_banked(entry: Params, ids: Array, x: Array,
                       use_pallas: bool = False) -> Array:
    """Per-row x_i Q_{ids[i]} for butterfly Q: per level, a static butterfly
    permutation sandwiching a banked bdmm (levels reversed, blocks
    transposed — the row-vector application). The block matmuls ride the
    vmapped bdmm Pallas kernel; the permutations are free gathers."""
    Q = jnp.take(entry["Q"], ids, axis=0).astype(x.dtype)  # (B, m, r, b, b)
    m, b = Q.shape[1], Q.shape[-1]
    d = x.shape[-1]
    y = x
    for lvl in reversed(range(m)):
        sig = butterfly_sigma(d, b, lvl + 1)
        spec_p = PermSpec.from_sigma(sig)
        y = apply_perm(y, spec_p)
        y = kernel_ops.bdmm_banked(jnp.swapaxes(Q[:, lvl], -1, -2), y,
                                   use_pallas=use_pallas)
        y = apply_perm(y, spec_p.inverse())
    return y


# ---------------------------------------------------------------------------
# Householder products  (HOFT: Q = H_1 .. H_k,  H_i = I - 2 v_i v_i^T)
# ---------------------------------------------------------------------------

def _hh_reflections(spec: AdapterSpec) -> int:
    k = spec.reflections
    if k <= 0 or k % 2:
        raise ValueError(
            f"householder needs a positive EVEN reflection count (identity "
            f"init is a product of paired reflections); got {k}")
    return k


def _hh_identity(spec: AdapterSpec, k: int) -> Array:
    """Reflection vectors whose product is exactly I: k (even) copies of
    e_1 — H(e_1)^2 = I with no rounding (each application negates one
    coordinate slab exactly)."""
    v = jnp.zeros(_maybe_batch((k, spec.d_in), spec.batch), jnp.float32)
    return v.at[..., 0].set(1.0)


def _hh_unit(v: Array) -> Array:
    """Safe fp32 unit vectors over the last axis. A (near-)zero vector
    falls back to e_1 so H stays EXACTLY orthogonal for every parameter
    value — the method never leaves the orthogonal group."""
    v32 = v.astype(jnp.float32)
    n2 = jnp.sum(v32 * v32, axis=-1, keepdims=True)
    e0 = jnp.zeros_like(v32).at[..., :1].set(1.0)
    v32 = jnp.where(n2 > 1e-12, v32, e0)
    return v32 * jax.lax.rsqrt(jnp.sum(v32 * v32, axis=-1, keepdims=True))


def householder_init(spec: AdapterSpec, key: jax.Array,
                     dtype=jnp.float32) -> Params:
    del key
    k = _hh_reflections(spec)
    return {"V": _hh_identity(spec, k).astype(dtype)}


def householder_materialize(spec: AdapterSpec, params: Params,
                            W: Array) -> Array:
    """Q @ W applied reflection by reflection: H W = W - 2 v (v^T W), no
    dense Q ever materializes — O(k d n) total, and d_in needs NO block
    divisibility (Householder's selling point over blocked classes)."""
    k = _hh_reflections(spec)
    Vu = _hh_unit(params["V"]).astype(W.dtype)           # (k, d)
    Wf = W
    for i in reversed(range(k)):                         # Q W = H_1(..H_k W)
        v = Vu[i]
        Wf = Wf - 2.0 * jnp.outer(v, v @ Wf)
    return Wf


def householder_apply_T(spec: AdapterSpec, params: Params, x: Array) -> Array:
    """x -> x Q = ((x H_1) H_2).. H_k;  x H = x - 2 (x.v) v."""
    k = _hh_reflections(spec)
    Vu = _hh_unit(params["V"])
    y = x
    for i in range(k):
        v = Vu[i].astype(x.dtype)
        y = y - 2.0 * (y @ v)[..., None] * v
    return y


def householder_param_count(spec: AdapterSpec) -> int:
    return _hh_reflections(spec) * spec.d_in


def householder_bank_build(spec: AdapterSpec, params_by_slot) -> Params:
    """{"V": (..., A, k, d)} PRE-NORMALIZED unit reflection vectors; the
    identity slot holds k copies of e_1 (product = I exactly)."""
    k = _hh_reflections(spec)
    ident = _hh_identity(spec, k)
    processed = [None if p is None else {"V": _hh_unit(p["V"])}
                 for p in params_by_slot]
    return _stack_slots(spec, {"V": ident}, processed)


def householder_rotate_banked(entry: Params, ids: Array, x: Array,
                              use_pallas: bool = False) -> Array:
    """Per-row x_i Q_{ids[i]} for Householder products. No dedicated Pallas
    kernel exists (the op is O(k d) per token, bandwidth-trivial next to
    the projection matmul) — ``ops.householder_banked`` is the reference
    einsum fallback on every backend."""
    V = jnp.take(entry["V"], ids, axis=0).astype(x.dtype)  # (B, k, d)
    return kernel_ops.householder_banked(V, x, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# Givens rounds  (GOFT: Q = G_m .. G_1, each G_l one brick-wall round of
# disjoint 2x2 plane rotations — quasi-Givens orthogonal fine-tuning,
# arXiv 2404.04316)
# ---------------------------------------------------------------------------

def _givens_num_rounds(spec: AdapterSpec) -> int:
    m = spec.givens_rounds
    if m <= 0:
        raise ValueError(f"givens needs a positive round count; got {m}")
    return m


def _givens_pairs(d: int, level: int) -> np.ndarray:
    """Left indices of round ``level``'s disjoint neighbor pairs (i, i+1).

    Brick-wall layout: even rounds pair (0,1),(2,3),..; odd rounds shift by
    one — (1,2),(3,4),.. — so two consecutive rounds couple every coordinate
    with both neighbors (odd-even transposition network). Boundary elements
    with no partner stay fixed, which also handles odd d."""
    off = level % 2
    return off + 2 * np.arange((d - off) // 2)


def _givens_apply(theta: Array, y: Array, transpose: bool) -> Array:
    """Apply Q = G_{m-1}..G_0 (or Q^T) to vectors on the last axis of y.

    theta: (m, d//2) angles — round l consumes its first ``len(pairs(l))``
    columns (odd rounds have one fewer pair; the tail is ignored and stays
    zero from init). Q^T = reversed rounds with negated angles. Rotations
    run in fp32 (angles are tiny; the cos/sin and pair updates are exact
    enough that Q stays orthogonal to fp32 roundoff for ANY theta — like
    Householder, the method never leaves the orthogonal group)."""
    m = theta.shape[0]
    d = y.shape[-1]
    t32 = theta.astype(jnp.float32)
    c_all, s_all = jnp.cos(t32), jnp.sin(t32)
    y32 = y.astype(jnp.float32)
    for lvl in (reversed(range(m)) if transpose else range(m)):
        ii = _givens_pairs(d, lvl)
        if ii.size == 0:
            continue
        c = c_all[lvl, :ii.size]
        s = -s_all[lvl, :ii.size] if transpose else s_all[lvl, :ii.size]
        a, b = y32[..., ii], y32[..., ii + 1]
        y32 = y32.at[..., ii].set(c * a - s * b)
        y32 = y32.at[..., ii + 1].set(s * a + c * b)
    return y32.astype(y.dtype)


def givens_init(spec: AdapterSpec, key: jax.Array, dtype=jnp.float32) -> Params:
    del key  # theta = 0 -> every round is I -> Q = I
    m = _givens_num_rounds(spec)
    return {"theta": jnp.zeros(
        _maybe_batch((m, spec.d_in // 2), spec.batch), dtype)}


def givens_materialize(spec: AdapterSpec, params: Params, W: Array) -> Array:
    """Q @ W round by round on the columns of W — O(m d n) total, no dense
    Q, and (like Householder) no block-divisibility constraint on d_in."""
    del spec
    WT = jnp.swapaxes(W, -1, -2)
    WT = _givens_apply(params["theta"], WT, transpose=False)
    return jnp.swapaxes(WT, -1, -2)


def givens_apply_T(spec: AdapterSpec, params: Params, x: Array) -> Array:
    """x -> x Q = (Q^T x^T)^T: rounds reversed, angles negated."""
    del spec
    return _givens_apply(params["theta"], x, transpose=True)


def givens_param_count(spec: AdapterSpec) -> int:
    return _givens_num_rounds(spec) * (spec.d_in // 2)


def givens_bank_build(spec: AdapterSpec, params_by_slot) -> Params:
    """{"c"/"s": (..., A, m, d//2)} PRE-EVALUATED cos/sin stacks (the
    trig runs once at build time); the identity slot is c = 1, s = 0."""
    m = _givens_num_rounds(spec)
    p = spec.d_in // 2
    ident = {"c": jnp.ones(_maybe_batch((m, p), spec.batch), jnp.float32),
             "s": jnp.zeros(_maybe_batch((m, p), spec.batch), jnp.float32)}
    processed = [None if pr is None else
                 {"c": jnp.cos(pr["theta"].astype(jnp.float32)),
                  "s": jnp.sin(pr["theta"].astype(jnp.float32))}
                 for pr in params_by_slot]
    return _stack_slots(spec, ident, processed)


def givens_rotate_banked(entry: Params, ids: Array, x: Array,
                         use_pallas: bool = False) -> Array:
    """Per-row x_i Q_{ids[i]} for Givens rounds. Like Householder, the op
    is O(m d) per token — bandwidth-trivial — so ``ops.givens_banked`` is
    the reference implementation on every backend."""
    C = jnp.take(entry["c"], ids, axis=0)               # (B, m, p)
    S = jnp.take(entry["s"], ids, axis=0)
    return kernel_ops.givens_banked(C, S, x, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# LoRA  (low-rank residual — the non-orthogonal baseline)
# ---------------------------------------------------------------------------

def lora_init(spec: AdapterSpec, key: jax.Array, dtype=jnp.float32) -> Params:
    import math
    ka, _ = jax.random.split(key)
    a = jax.random.normal(ka, _maybe_batch((spec.d_in, spec.rank), spec.batch),
                          dtype) * (1.0 / math.sqrt(spec.d_in))
    return {"A": a,
            "B": jnp.zeros(_maybe_batch((spec.rank, spec.d_out), spec.batch),
                           dtype)}


def lora_materialize(spec: AdapterSpec, params: Params, W: Array) -> Array:
    scale = spec.alpha / spec.rank
    return W + scale * (params["A"] @ params["B"]).astype(W.dtype)


def lora_param_count(spec: AdapterSpec) -> int:
    return spec.rank * (spec.d_in + spec.d_out)


# ---------------------------------------------------------------------------
# public entry points — registry dispatch only (no method conditionals)
# ---------------------------------------------------------------------------

def init_adapter(spec: AdapterSpec, key: jax.Array, dtype=jnp.float32) -> Params:
    """Initialize adapter params. Orthogonal methods start at Q = I;
    LoRA starts at A ~ N, B = 0. Either way W_eff(init) == W."""
    from . import methods
    p = methods.get(spec.method).init_params(spec, key, dtype)
    if spec.use_scale:
        p["scale"] = jnp.ones(_maybe_batch((spec.d_out,), spec.batch), dtype)
    return p


def num_adapter_params(spec: AdapterSpec) -> int:
    from . import methods
    n = methods.get(spec.method).param_count(spec)
    if spec.use_scale:
        n += spec.d_out
    return n * int(np.prod(spec.batch)) if spec.batch else n


def materialize(spec: AdapterSpec, params: Params, W: Array) -> Array:
    """W_eff from frozen W + adapter params. Differentiable w.r.t. params."""
    from . import methods
    if spec.batch:
        inner = dataclasses.replace(spec, batch=tuple(spec.batch[1:]))
        fn = lambda p, w: materialize(inner, p, w)
        return jax.vmap(fn)(params, W)
    dtype = W.dtype
    Wf = methods.get(spec.method).materialize(spec, params, W)
    if spec.use_scale:
        Wf = Wf * params["scale"][None, :].astype(dtype)
    return Wf.astype(dtype)


def merge(spec: AdapterSpec, params: Params, W: Array) -> Array:
    """Bake the adapter into the weight (inference; no runtime overhead)."""
    return materialize(spec, params, W)


def apply_activation_side(spec: AdapterSpec, params: Params, x: Array) -> Array:
    """For input-rotation methods, y = x @ (Q W) == (x Q) @ W: rotate the
    activations instead of the weight (wins when tokens << d_out)."""
    from . import methods
    ops = methods.get(spec.method)
    if ops.apply_activation_side is None:
        raise ValueError(f"activation-side not defined for {spec.method}")
    return ops.apply_activation_side(spec, params, x)
