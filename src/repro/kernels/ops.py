"""Dispatch layer for the Pallas kernels.

``use_pallas`` semantics (plumbed from model configs):
  * False  — pure-jnp reference path (XLA). Always used by launch/dryrun.py:
             TPU kernels cannot lower on the CPU dry-run backend, and the
             reference path is semantically identical (tests prove it).
  * True   — pl.pallas_call: compiled on TPU, interpreted on the CPU
             backend so tests exercise the kernel bodies there
             (``dispatch.interpret_mode``; any other backend raises).

Both paths are differentiable: the reference path via XLA autodiff, the
Pallas path via the custom-VJP rules in dispatch.py (backward passes are
Pallas kernels too, so ``jax.grad`` of a GSOFT loss never round-trips the
activation slab through HBM more than once per direction).

Launch geometry (token/group tiles) is resolved per (shape, dtype, backend)
by ``dispatch.get_tuning`` — config overrides > autotuned > heuristic; pass
``tuning=`` to pin a call site explicitly.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs.metrics import REGISTRY
from . import dispatch, ref
from .dispatch import Tuning, interpret_mode
from .flash_attention import flash_attention, paged_flash_decode
from .ssd import ssd_pallas

Array = jnp.ndarray


def bdmm(blocks: Array, x: Array, use_pallas: bool = False,
         tuning: Optional[Tuning] = None) -> Array:
    """Block-diagonal matmul; supports leading batch dims on x."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use_pallas:
        r, bo, bi = blocks.shape
        tun = tuning or dispatch.get_tuning(dispatch.bdmm_key(r, bo, bi,
                                                              x.dtype))
        y = dispatch.bdmm_diff(tun, interpret_mode(), blocks, x2)
    else:
        y = ref.bdmm_ref(blocks, x2)
    return y.reshape(lead + (y.shape[-1],))


def _gs_2d(L: Array, x: Array):
    r, b, _ = L.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    tun = dispatch.get_tuning(dispatch.gs_key(r, b, x.dtype))
    return lead, x2, tun


def gs_transform(L: Array, R: Array, x: Array, use_pallas: bool = False,
                 tuning: Optional[Tuning] = None) -> Array:
    """y = P^T L P R x (GSOFT rotation) over the last dim of x."""
    lead, x2, tun = _gs_2d(L, x)
    if use_pallas:
        y = dispatch.gs_diff(tuning or tun, interpret_mode(), L, R, x2)
    else:
        y = ref.gs_fused_ref(L, R, x2)
    return y.reshape(lead + (x.shape[-1],))


def gs_transform_T(L: Array, R: Array, x: Array, use_pallas: bool = False,
                   tuning: Optional[Tuning] = None) -> Array:
    """y = R^T P^T L^T P x (transpose rotation Q^T x) over the last dim.

    Used for activation-side adapters (x Q) and the output-side factor of
    Double GSOFT (W Q).
    """
    lead, x2, tun = _gs_2d(L, x)
    if use_pallas:
        y = dispatch.gs_T_diff(tuning or tun, interpret_mode(), L, R, x2)
    else:
        y = ref.gs_fused_T_ref(L, R, x2)
    return y.reshape(lead + (x.shape[-1],))


def bdmm_banked(blocks: Array, x: Array, use_pallas: bool = False,
                tuning: Optional[Tuning] = None) -> Array:
    """Per-row block-diagonal matmul: blocks (B, r, bo, bi), x (B, T, r*bi).

    Row i uses its own block set — the serving-side primitive behind
    per-request adapter rotations. The Pallas path vmaps the bdmm kernel
    over the row axis (one grid dim per row)."""
    if use_pallas:
        _, r, bo, bi = blocks.shape
        tun = tuning or dispatch.get_tuning(dispatch.bdmm_key(r, bo, bi,
                                                              x.dtype))
        interp = interpret_mode()
        return jax.vmap(
            lambda bb, xx: dispatch.bdmm_diff(tun, interp, bb, xx))(blocks, x)
    return ref.bdmm_banked_ref(blocks, x)


def gs_banked_transform_T(L: Array, R: Array, x: Array,
                          use_pallas: bool = False,
                          tuning: Optional[Tuning] = None) -> Array:
    """Per-row transpose GSOFT rotation y[i] = Q_i^T x[i] (= x[i] Q_i as a
    row vector), Q_i = P^T L_i P R_i.

    L, R: (B, r, b, b) pre-gathered per-row orthogonal blocks; x: (B, T, d).
    This is the continuous-batching engine's multi-adapter hot path: each
    decode slot rotates its activations with its own adapter at O(b*d) per
    token instead of re-merging an O(d^2) weight set per request.

    On the Pallas path the shape picks the kernel
    (``dispatch.banked_gs_rows_tiles``): rows of few tokens (decode) take
    the row-batched kernel, one launch over all rows per stage; longer rows
    (prompt chunks, image lanes) vmap the per-row ``gs_T`` kernel, whose
    geometry ``tuning`` pins. Each lowering counts its path in the metrics
    registry (``gs_banked_path.rows`` / ``gs_banked_path.per_row``)."""
    if use_pallas:
        bsz, r, b, _bb = L.shape
        interp = interpret_mode()
        tiles = dispatch.banked_gs_rows_tiles(x.shape[1], bsz, r, b,
                                              L.dtype.itemsize)
        if tiles is not None:
            REGISTRY.counter("gs_banked_path.rows").inc()
            return dispatch.gs_T_rows_diff(*tiles, interp, L, R, x)
        REGISTRY.counter("gs_banked_path.per_row").inc()
        tun = tuning or dispatch.get_tuning(dispatch.gs_key(r, b, x.dtype))
        return jax.vmap(
            lambda l, rr, xx: dispatch.gs_T_diff(tun, interp, l, rr, xx))(
                L, R, x)
    return ref.gs_banked_T_ref(L, R, x)


def householder_banked(V: Array, x: Array, use_pallas: bool = False) -> Array:
    """Per-row Householder-product rotation y[i] = x[i] Q_{i} (HOFT bank).

    V: (B, k, d) pre-normalized unit reflection vectors; x: (B, T, d).
    There is NO dedicated Pallas kernel for this transform: it is O(k*d)
    per token — bandwidth-trivial next to the projection matmul it
    precedes — so the reference einsum is the implementation on every
    backend (``use_pallas`` is accepted for hook uniformity and ignored;
    the method registers ``banked_kernel=""`` — see
    ``dispatch.BANKED_KEYS``)."""
    del use_pallas
    return ref.householder_banked_ref(V, x)


def givens_banked(C: Array, S: Array, x: Array,
                  use_pallas: bool = False) -> Array:
    """Per-row Givens-round rotation y[i] = x[i] Q_{i} (GOFT bank).

    C, S: (B, m, d//2) pre-evaluated cos/sin round stacks; x: (B, T, d).
    Like the Householder bank, the transform is O(m*d) per token —
    bandwidth-trivial next to the projection matmul — so the reference
    gather/rotate is the implementation on every backend (``use_pallas``
    accepted for hook uniformity and ignored; ``banked_kernel=""``)."""
    del use_pallas
    return ref.givens_banked_ref(C, S, x)


def q_matmul(x: Array, q: Array, scale: Array, use_pallas: bool = False,
             tuning: Optional[Tuning] = None) -> Array:
    """Quantized-weight matmul y = x @ dequant(q, scale) with the dequant
    in the epilogue. x: (..., K); q: (K, N) int8/fp8; scale broadcastable
    (1, N) / scalar. The serving hot path of ``ModelRuntime.quantized``."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use_pallas and q.dtype == jnp.int8:
        k, n = q.shape
        tun = tuning or dispatch.get_tuning(dispatch.qmm_key(k, n, x.dtype))
        y = dispatch.q_matmul_pallas(x2, q, scale,
                                     token_tile=tun.token_tile,
                                     n_tile=tun.group_tile,
                                     interpret=interpret_mode())
    else:
        # fp8 codes (and the no-kernel path) run the reference einsum
        y = ref.q_matmul_ref(x2, q, scale)
    return y.reshape(lead + (y.shape[-1],))


def gs_q_matmul(L: Array, R: Array, x: Array, q: Array, scale: Array,
                use_pallas: bool = False,
                tuning: Optional[Tuning] = None) -> Array:
    """Fused activation-side GS rotation + quantized matmul:
    y = (x Q_gs) @ dequant(q, scale). L, R: (r, b, b); x: (..., d=r*b)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use_pallas and q.dtype == jnp.int8:
        r, b, _ = L.shape
        n = q.shape[-1]
        tun = tuning or dispatch.get_tuning(
            dispatch.gs_qmm_key(r, b, n, x.dtype))
        y = dispatch.gs_q_matmul_pallas(L, R, x2, q, scale,
                                        token_tile=tun.token_tile,
                                        n_tile=tun.group_tile,
                                        interpret=interpret_mode())
    else:
        y = ref.gs_q_matmul_ref(L, R, x2, q, scale)
    return y.reshape(lead + (y.shape[-1],))


def gs_q_matmul_banked(L: Array, R: Array, x: Array, q: Array, scale: Array,
                       use_pallas: bool = False,
                       tuning: Optional[Tuning] = None) -> Array:
    """Per-row fused rotate+quantized-matmul (multi-adapter quantized
    serving): L, R (B, r, b, b) pre-gathered per-row GS blocks, x (B, T, d),
    ONE shared quantized base weight q (d, N). Row i computes
    (x_i Q_i) @ W_q — bf16 rotation, int8 base matmul, one kernel."""
    if use_pallas and q.dtype == jnp.int8:
        _, r, b, _bb = L.shape
        n = q.shape[-1]
        tun = tuning or dispatch.get_tuning(
            dispatch.gs_qmm_key(r, b, n, x.dtype))
        interp = interpret_mode()
        return jax.vmap(
            lambda l, rr, xx: dispatch.gs_q_matmul_pallas(
                l, rr, xx, q, scale, token_tile=tun.token_tile,
                n_tile=tun.group_tile, interpret=interp))(L, R, x)
    xr = ref.gs_banked_T_ref(L, R, x)
    bsz, t, d = xr.shape
    y = ref.q_matmul_ref(xr.reshape(bsz * t, d), q, scale)
    return y.reshape(bsz, t, y.shape[-1])


def paged_attention(q: Array, k_pages: Array, v_pages: Array, table: Array,
                    kv_len: Array, *, scale: float = 0.0,
                    use_pallas: bool = False) -> Array:
    """Single-token decode attention through a KV page table.

    q: (B, H, D) one query per row; k_pages / v_pages: (P, page, K, D)
    shared page pools; table: (B, W) int32 page ids (unused entries point
    at the garbage page 0); kv_len: (B,) valid prefix length per row.
    The serving engine's paged decode hot path (ISSUE 7 / vLLM-style)."""
    if use_pallas:
        b, h, d = q.shape
        _, page, kh, _ = k_pages.shape
        # fixed launch geometry today, but resolve through the registry so
        # the persisted tuning cache covers this call site too
        dispatch.get_tuning(dispatch.paged_attn_key(h, kh, d, page, q.dtype))
        return paged_flash_decode(q, k_pages, v_pages, table, kv_len,
                                  scale=scale, interpret=interpret_mode())
    return ref.paged_attn_ref(q, k_pages, v_pages, table, kv_len, scale=scale)


def ssd(x: Array, loga: Array, B: Array, C: Array, chunk: int = 64,
        use_pallas: bool = False) -> Array:
    """Mamba2 SSD scan. Accepts (T,H,P) or batched (N,T,H,P) inputs."""
    if x.ndim == 4:
        fn = partial(ssd, chunk=chunk, use_pallas=use_pallas)
        return jax.vmap(fn)(x, loga, B, C)
    if use_pallas:
        t = x.shape[0]
        q = chunk
        while t % q:
            q //= 2
        return ssd_pallas(x, loga, B, C, chunk=max(q, 1),
                          interpret=interpret_mode())
    return ref.ssd_chunked_ref(x, loga, B, C,
                               chunk=dispatch.pick_chunk(x.shape[0], chunk))


def flash_mha(q: Array, k: Array, v: Array, *, causal: bool = True,
              use_pallas: bool = False, blk: int = 128) -> Array:
    """Multi-head attention over (B, S, H, D) activations with GQA support
    (kv heads broadcast to query heads). Kernel path keeps scores in VMEM."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    if kh != h:
        rep = h // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qh = jnp.swapaxes(q, 1, 2)          # (B, H, S, D)
    kkh = jnp.swapaxes(k, 1, 2)
    vvh = jnp.swapaxes(v, 1, 2)
    if use_pallas:
        fn = lambda qq, kk, vv: flash_attention(
            qq, kk, vv, causal=causal, blk_q=blk, blk_k=blk,
            interpret=interpret_mode())
    else:
        fn = lambda qq, kk, vv: ref.flash_ref(qq, kk, vv, causal=causal)
    out = jax.vmap(fn)(qh, kkh, vvh)
    return jnp.swapaxes(out, 1, 2)
