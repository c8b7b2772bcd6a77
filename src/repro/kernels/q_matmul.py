"""Pallas TPU kernels: quantized-weight matmul for the serving hot path.

The decode step is memory-bandwidth-bound: every token re-reads the whole
weight tree from HBM. Storing base weights as int8 codes + fp32
per-output-channel scales halves (vs bf16) the bytes the matmul streams;
the kernel widens the int8 tile to the activation dtype IN VMEM (a VPU
cast, no extra HBM traffic), runs the MXU dot into an fp32 accumulator,
and applies the scales in the epilogue before the output cast — the
dequantized float weight never exists in HBM.

Grid: (token tiles, out-channel tiles, contraction tiles), contraction
innermost. Tiles along N and K are multiples of 128 (the lane width) or
the whole dimension, which is what Mosaic accepts for the (tt, K) and
(K, N) blocks; tiling K keeps the down projection of a 29568-wide MLP
inside the scoped VMEM limit.

``gs_q_matmul`` is the adapter-serving composition: the activation-side
GSOFT rotation x·Q (kernels/gs_fused.py, bf16) followed by this kernel.
Rotations stay bf16 per the QOFT/OFTv2 rationale (int8 would break Cayley
orthogonality; the factors are O(r·b²) anyway — the memory win lives in
the O(d²) base weights).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .gs_fused import gs_fused_T_pallas

Array = jnp.ndarray

LANE = 128


def _lane_tile(n: int, cap: int) -> int:
    """Largest multiple of 128 that divides n and is <= cap; n itself when
    n is not a multiple of 128 (one block then spans the dimension)."""
    if n % LANE:
        return n
    t = max(min(cap, n) // LANE, 1) * LANE
    while n % t:
        t -= LANE
    return t


def default_n_tile(n: int, cap: int = 512) -> int:
    """Out-channel tile: a multiple of 128 dividing n, or the full n."""
    return _lane_tile(n, cap)


def default_k_tile(k: int) -> int:
    """Contraction tile: a multiple of 128 dividing k, at most 2048, or the
    full k."""
    return _lane_tile(k, 2048)


def _q_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # (tt, kt)
    w = q_ref[...].astype(x.dtype)                   # int8 -> bf16 in VMEM
    acc_ref[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _out():                                      # epilogue dequant
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def q_matmul_pallas(x: Array, q: Array, scale: Array, *,
                    token_tile: int = 128, n_tile: int = 0,
                    interpret: bool = False) -> Array:
    """x: (T, K); q: (K, N) int8; scale: (1, N) or scalar fp32 -> (T, N).

    ``n_tile`` of 0 picks the default; any other value is rounded down to a
    legal tile (a multiple of 128 dividing N). K is tiled by
    ``default_k_tile``."""
    t, k = x.shape
    kq, n = q.shape
    assert k == kq, (x.shape, q.shape)
    s = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(1, -1)
                         if jnp.ndim(scale) else
                         jnp.full((1, n), scale, jnp.float32), (1, n))
    nt = default_n_tile(n, n_tile or 512)
    kt = default_k_tile(k)
    tt = min(token_tile, t)
    pad = (-t) % tt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    tp = x.shape[0]
    out = pl.pallas_call(
        _q_matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((tp, n), x.dtype),
        grid=(tp // tt, n // nt, k // kt),
        in_specs=[
            pl.BlockSpec((tt, kt), lambda ti, ni, ki: (ti, ki)),
            pl.BlockSpec((kt, nt), lambda ti, ni, ki: (ki, ni)),
            pl.BlockSpec((1, nt), lambda ti, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((tt, nt), lambda ti, ni, ki: (ti, ni)),
        scratch_shapes=[pltpu.VMEM((tt, nt), jnp.float32)],
        interpret=interpret,
    )(x, q, s)
    return out[:t] if pad else out


def gs_q_matmul_pallas(L: Array, R: Array, x: Array, q: Array, scale: Array,
                       *, token_tile: int = 128, n_tile: int = 0,
                       interpret: bool = False) -> Array:
    """(x Q_gs) @ W_q. L, R: (r, b, b); x: (T, d=r*b); q: (d, N).

    The rotation runs in the activation dtype and its result feeds the
    quantized matmul kernel, matching ``ref.gs_q_matmul_ref``."""
    r, b, _ = L.shape
    assert x.shape[-1] == r * b == q.shape[0], (L.shape, x.shape, q.shape)
    xr = gs_fused_T_pallas(L, R, x, token_tile=token_tile,
                           interpret=interpret)
    return q_matmul_pallas(xr, q, scale, token_tile=token_tile,
                           n_tile=n_tile, interpret=interpret)
