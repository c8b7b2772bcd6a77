"""GSOFT rotations on the Pallas path:  y = P^T L P R x  and its transpose.

Each group stage is one launch of the block-diagonal ``bdmm`` kernel
(kernels/bdmm.py): tokens on sublanes, whole 128-lane groups of weight
columns per grid step. The P_(r,d) shuffle between the stages is a
reshape + transpose of the (T, d) activation that XLA performs between the
launches. The shuffle is kept out of the kernel bodies on purpose: inside
a kernel it splits the lane dimension, (T, r*b) -> (T, r, b) with b < 128,
and Mosaic refuses that shape cast at real widths (d 8192, b 32).
``gs_fused_T_rows_pallas`` is the per-row (banked) transpose rotation for
rows of few tokens: each stage is one ``bdmm_rows`` launch over all rows.

Intermediates round to ``x.dtype`` between stages, exactly as the oracle
``ref.gs_fused_ref`` does. The backward pass is built from the same two
kernels: ``bdmm`` for the activation gradient and ``bdmm_dblocks`` for the
factor gradients (fp32, accumulated over token tiles in VMEM).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from .bdmm import bdmm_dblocks_pallas, bdmm_pallas, bdmm_rows_pallas

Array = jnp.ndarray


def _shuffle(y: Array, r: int, b: int) -> Array:
    """P (k = r): flat feature g*b + i moves to i*r + g."""
    lead = y.shape[:-1]
    return y.reshape(lead + (r, b)).swapaxes(-1, -2).reshape(lead + (r * b,))


def _unshuffle(y: Array, r: int, b: int) -> Array:
    """P^T (k = b): the inverse of ``_shuffle``."""
    lead = y.shape[:-1]
    return y.reshape(lead + (b, r)).swapaxes(-1, -2).reshape(lead + (r * b,))


def _bd(token_tile: int, interpret: bool):
    return functools.partial(bdmm_pallas, token_tile=token_tile,
                             interpret=interpret)


def gs_fused_pallas(L: Array, R: Array, x: Array, *, token_tile: int = 128,
                    interpret: bool = False) -> Array:
    """L, R: (r, b, b); x: (T, d=r*b) -> (T, d). y = P^T L P R x."""
    r, b, _ = L.shape
    assert x.shape[-1] == r * b, (L.shape, x.shape)
    bd = _bd(token_tile, interpret)
    return _unshuffle(bd(L, _shuffle(bd(R, x), r, b)), r, b)


def gs_fused_T_pallas(L: Array, R: Array, x: Array, *, token_tile: int = 128,
                      interpret: bool = False) -> Array:
    """Transpose rotation  y = R^T P^T L^T P x  (= Q^T x).

    This is both the VJP of gs_fused_pallas w.r.t. x and the activation-side
    adapter application (x Q = (Q^T x^T)^T).
    """
    r, b, _ = L.shape
    assert x.shape[-1] == r * b, (L.shape, x.shape)
    bd = _bd(token_tile, interpret)
    y = _unshuffle(bd(jnp.swapaxes(L, -1, -2), _shuffle(x, r, b)), r, b)
    return bd(jnp.swapaxes(R, -1, -2), y)


def gs_fused_T_rows_pallas(L: Array, R: Array, x: Array, *,
                           row_tile: int = 16, group_tile: int = 32,
                           interpret: bool = False) -> Array:
    """Per-row transpose rotation  y[i] = R_i^T P^T L_i^T P x[i]  for rows of
    few tokens: L, R (B, r, b, b) as gathered, x (B, T, d=r*b).

    Each stage is one ``bdmm_rows`` launch over all rows that contracts the
    blocks' first axis, so L^T and R^T are never materialised.
    """
    r, b = L.shape[1], L.shape[2]
    bd = functools.partial(bdmm_rows_pallas, transpose=True,
                           row_tile=row_tile, group_tile=group_tile,
                           interpret=interpret)
    return bd(R, _unshuffle(bd(L, _shuffle(x, r, b)), r, b))


def _gs_bwd(L: Array, R: Array, x: Array, dy: Array, *, token_tile: int,
            interpret: bool, with_dx: bool):
    r, b, _ = L.shape
    assert x.shape[-1] == r * b and dy.shape == x.shape
    bd = _bd(token_tile, interpret)
    dblocks = functools.partial(bdmm_dblocks_pallas, bo=b, bi=b,
                                token_tile=token_tile, interpret=interpret)
    v = _shuffle(bd(R, x), r, b)                    # forward: P R x
    dw = _shuffle(dy, r, b)                         # y = P^T w  =>  dw = P dy
    dL = dblocks(dw, v)                             # sum_t dw_g v_g^T
    du = _unshuffle(bd(jnp.swapaxes(L, -1, -2), dw), r, b)   # P^T L^T dw
    dR = dblocks(du, x)                             # sum_t du_g x_g^T
    if not with_dx:
        return dL, dR
    return bd(jnp.swapaxes(R, -1, -2), du), dL, dR  # dx = R^T du


def gs_fused_bwd_pallas(L: Array, R: Array, x: Array, dy: Array, *,
                        token_tile: int = 128, interpret: bool = False):
    """Backward of  y = P^T L P R x.

    Returns (dx, dL, dR) with dx in x.dtype and dL, dR in fp32:
        dx = Q^T dy,   dL[g] = sum_t (P dy)_g (P R x)_g^T,
        dR[g] = sum_t (P^T L^T P dy)_g x_g^T.
    """
    return _gs_bwd(L, R, x, dy, token_tile=token_tile, interpret=interpret,
                   with_dx=True)


def gs_fused_grads_pallas(L: Array, R: Array, x: Array, dy: Array, *,
                          token_tile: int = 128, interpret: bool = False):
    """Factor gradients only: (dL, dR) of <dy, P^T L P R x>. No dx is
    computed (used by the gs_T VJP, which gets its dx from the forward
    rotation of dy instead)."""
    return _gs_bwd(L, R, x, dy, token_tile=token_tile, interpret=interpret,
                   with_dx=False)
