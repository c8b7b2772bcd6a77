"""Pallas TPU kernel: block-diagonal matmul (the GS "group" primitive).

y[t, g*bo:(g+1)*bo] = blocks[g] @ x[t, g*bi:(g+1)*bi]

TPU mapping (DESIGN §3): paper-scale GS blocks (b in 8..128) are smaller than
the 128x128 MXU, so putting the *block* dim on the systolic array wastes it.
Instead we put tokens on the lane axis (token_tile rows per grid step) and
process ``group_tile`` consecutive blocks per grid step, issuing one
(token_tile x bi) @ (bi x bo) dot per block — contraction dim bi stays on
sublanes, tokens saturate lanes.  One HBM read of x / one write of y total.

VMEM per grid step:
    token_tile * group_tile * (bi + bo) * dtype  +  group_tile * bo * bi * 4

Rows that carry few tokens and blocks of their own (per-request adapters at
decode) leave that mapping's token tile nearly empty: vmapped over rows, it
runs a grid of r / group_tile one-token steps per row, and the fixed cost
of a grid step is the whole kernel. ``bdmm_rows_pallas`` takes all rows in
one launch instead, with large steps (many rows x many blocks), and
computes on the VPU: no block is shared between rows, so the MXU would
reload weights for every row and token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl

Array = jnp.ndarray


def _bdmm_kernel(x_ref, w_ref, o_ref, *, group_tile: int, bi: int, bo: int):
    x = x_ref[...]                       # (tt, group_tile * bi)
    for g in range(group_tile):          # static unroll
        xg = x[:, g * bi:(g + 1) * bi]
        w = w_ref[g]                     # (bo, bi)
        yg = jax.lax.dot_general(
            xg, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[:, g * bo:(g + 1) * bo] = yg.astype(o_ref.dtype)


def default_group_tile(r: int, bi: int) -> int:
    """Heuristic: >= 128 lanes of weight columns per grid step, capped at r,
    rounded down to a divisor of r."""
    group_tile = max(1, min(r, 128 // max(bi, 1) or 1))
    while r % group_tile:
        group_tile -= 1
    return group_tile


def bdmm_pallas(blocks: Array, x: Array, *, token_tile: int = 128,
                group_tile: int = 0, interpret: bool = False) -> Array:
    """blocks: (r, bo, bi); x: (T, r*bi) -> (T, r*bo)."""
    r, bo, bi = blocks.shape
    t, d = x.shape
    assert d == r * bi, (blocks.shape, x.shape)
    if group_tile <= 0:
        group_tile = default_group_tile(r, bi)
    while r % group_tile:
        group_tile -= 1
    tt = min(token_tile, t)
    pad = (-t) % tt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    tp = x.shape[0]

    grid = (tp // tt, r // group_tile)
    out = pl.pallas_call(
        functools.partial(_bdmm_kernel, group_tile=group_tile, bi=bi, bo=bo),
        out_shape=jax.ShapeDtypeStruct((tp, r * bo), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tt, group_tile * bi), lambda ti, gi: (ti, gi)),
            pl.BlockSpec((group_tile, bo, bi), lambda ti, gi: (gi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tt, group_tile * bo), lambda ti, gi: (ti, gi)),
        interpret=interpret,
    )(x, blocks)
    return out[:t] if pad else out


def _bdmm_dw_kernel(dy_ref, x_ref, dw_ref, *, group_tile: int,
                    bo: int, bi: int):
    ti = pl.program_id(1)
    dy = dy_ref[...]                     # (tt, group_tile * bo)
    x = x_ref[...]                       # (tt, group_tile * bi)
    for g in range(group_tile):          # static unroll
        dyg = dy[:, g * bo:(g + 1) * bo]
        xg = x[:, g * bi:(g + 1) * bi]
        dw = jax.lax.dot_general(         # (bo, bi): contract over tokens
            dyg, xg, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(ti == 0)
        def _init():
            dw_ref[g] = dw

        @pl.when(ti != 0)
        def _acc():
            dw_ref[g] += dw


def bdmm_dblocks_pallas(dy: Array, x: Array, *, bo: int, bi: int,
                        token_tile: int = 128, group_tile: int = 0,
                        interpret: bool = False) -> Array:
    """Token-contraction backward of bdmm: the gradient w.r.t. the blocks.

    dy: (T, r*bo); x: (T, r*bi)  ->  dblocks (r, bo, bi) in fp32:
        dblocks[g, i, j] = sum_t dy[t, g*bo + i] * x[t, g*bi + j]

    Grid is (group steps, token steps) with tokens innermost, so each
    (group_tile, bo, bi) output block is revisited across consecutive token
    steps and accumulated in place (fp32) — one HBM read of dy and x total.
    """
    t, dyd = dy.shape
    assert dyd % bo == 0 and x.shape[-1] % bi == 0
    r = dyd // bo
    assert x.shape == (t, r * bi), (dy.shape, x.shape, bo, bi)
    if group_tile <= 0:
        group_tile = default_group_tile(r, max(bi, bo))
    while r % group_tile:
        group_tile -= 1
    tt = min(token_tile, t)
    pad = (-t) % tt
    if pad:                               # zero rows contribute zero gradient
        dy = jnp.pad(dy, ((0, pad), (0, 0)))
        x = jnp.pad(x, ((0, pad), (0, 0)))
    tp = dy.shape[0]

    grid = (r // group_tile, tp // tt)
    return pl.pallas_call(
        functools.partial(_bdmm_dw_kernel, group_tile=group_tile, bo=bo,
                          bi=bi),
        out_shape=jax.ShapeDtypeStruct((r, bo, bi), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tt, group_tile * bo), lambda gi, ti: (ti, gi)),
            pl.BlockSpec((tt, group_tile * bi), lambda gi, ti: (ti, gi)),
        ],
        out_specs=pl.BlockSpec((group_tile, bo, bi), lambda gi, ti: (gi, 0, 0)),
        interpret=interpret,
    )(dy, x)


def _bdmm_rows_kernel(x_ref, w_ref, o_ref, *, transpose: bool):
    # x_ref (rt, T, gt, bi), w_ref (rt, gt, bo, bi) [(rt, gt, bi, bo) when
    # transposed], o_ref (rt, T, gt, bo). Products widen to fp32 and are
    # summed in fp32, then round once to the output dtype.
    def row(i, carry):
        w = w_ref[i].astype(jnp.float32)

        def token(t, c):
            x = x_ref[i, t].astype(jnp.float32)              # (gt, bi)
            if transpose:
                y = jnp.sum(w * x[:, :, None], axis=1)
            else:
                y = jnp.sum(w * x[:, None, :], axis=2)
            o_ref[i, t] = y.astype(o_ref.dtype)
            return c

        return jax.lax.fori_loop(0, x_ref.shape[1], token, carry)

    jax.lax.fori_loop(0, w_ref.shape[0], row, 0)


def bdmm_rows_pallas(blocks: Array, x: Array, *, transpose: bool = False,
                     row_tile: int = 16, group_tile: int = 32,
                     interpret: bool = False) -> Array:
    """Per-row block-diagonal matmul, all rows in one launch.

    blocks: (B, r, bo, bi), or (B, r, bi, bo) with ``transpose`` (the blocks'
    other axis is contracted, so a caller applying B^T passes B as it is);
    x: (B, T, r*bi) -> (B, T, r*bo). Row i uses its own blocks[i].

    The kernel sees x as (B, T, r, bi), reshaped by XLA, so no lane split
    happens inside it. The grid runs over (row tiles, group tiles); the last
    tile of either may overhang, and its padding only reaches outputs that
    are dropped (every output reads its own block and input group alone).
    """
    bsz, r, b1, b2 = blocks.shape
    bo, bi = (b2, b1) if transpose else (b1, b2)
    t = x.shape[1]
    assert x.shape == (bsz, t, r * bi), (blocks.shape, x.shape, transpose)
    rt = min(row_tile, bsz)
    # the group tile is x's second-minor block dim: a multiple of 16 (a
    # bf16 sublane tile) or all of r
    gt = r if r <= group_tile else max(16, group_tile // 16 * 16)
    out = pl.pallas_call(
        functools.partial(_bdmm_rows_kernel, transpose=transpose),
        out_shape=jax.ShapeDtypeStruct((bsz, t, r, bo), x.dtype),
        grid=(pl.cdiv(bsz, rt), pl.cdiv(r, gt)),
        in_specs=[
            pl.BlockSpec((rt, t, gt, bi), lambda i, g: (i, 0, g, 0)),
            pl.BlockSpec((rt, gt, b1, b2), lambda i, g: (i, g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rt, t, gt, bo), lambda i, g: (i, 0, g, 0)),
        interpret=interpret,
        name="bdmm_rows",
    )(x.reshape(bsz, t, r, bi), blocks)
    return out.reshape(bsz, t, r * bo)
