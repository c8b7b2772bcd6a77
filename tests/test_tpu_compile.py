"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel on argument SHAPES and compiles it
for a described (not attached) v5e chip, so Mosaic's refusals — lane
splits, illegal block shapes, scoped-VMEM overruns — surface here instead
of on the chip. Widths are qwen2-72b's (d_model 8192, d_ff 29568, 64 q /
8 kv heads of 128, GS blocks of 32). Every call passes ``interpret=False``
and goes straight to the kernel: ``kernels.ops`` would ask the CPU backend
and take its interpret branch.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bdmm import bdmm_pallas
from repro.kernels.dispatch import banked_gs_rows_tiles
from repro.kernels.flash_attention import paged_flash_decode
from repro.kernels.gs_fused import (gs_fused_T_pallas, gs_fused_T_rows_pallas,
                                    gs_fused_bwd_pallas, gs_fused_pallas)
from repro.kernels.q_matmul import gs_q_matmul_pallas, q_matmul_pallas

D, FF, B = 8192, 29568, 32
BF16, I8, F32 = jnp.bfloat16, jnp.int8, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for the described chip cannot be read back from the
    # persistent cache without the chip; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


GS_SHAPES = [((D // B, B, B), BF16), ((D // B, B, B), BF16),
             ((128, D), BF16)]


def test_gs_fused_compiles(one_chip):
    _compile(gs_fused_pallas, one_chip, *GS_SHAPES)


def test_gs_fused_T_compiles(one_chip):
    _compile(gs_fused_T_pallas, one_chip, *GS_SHAPES)


def test_gs_fused_bwd_compiles(one_chip):
    _compile(gs_fused_bwd_pallas, one_chip, *GS_SHAPES, ((128, D), BF16))


def test_gs_fused_block8_compiles(one_chip):
    _compile(gs_fused_T_pallas, one_chip, ((D // 8, 8, 8), BF16),
             ((D // 8, 8, 8), BF16), ((128, D), BF16))


# (K, N): attention/out projections, MLP up, MLP down
QMM_SHAPES = [(D, D), (D, FF), (FF, D)]


@pytest.mark.parametrize("k,n", QMM_SHAPES)
def test_q_matmul_compiles(one_chip, k, n):
    _compile(q_matmul_pallas, one_chip, ((128, k), BF16), ((k, n), I8),
             ((1, n), F32))


@pytest.mark.parametrize("k,n", QMM_SHAPES)
def test_gs_q_matmul_compiles(one_chip, k, n):
    _compile(gs_q_matmul_pallas, one_chip, ((k // B, B, B), BF16),
             ((k // B, B, B), BF16), ((128, k), BF16), ((k, n), I8),
             ((1, n), F32))


def test_gs_q_matmul_banked_decode_compiles(one_chip):
    """Per-row factors, one decode token per row, as gs_q_matmul_banked
    vmaps it over an 8-slot batch."""
    fn = jax.vmap(gs_q_matmul_pallas, in_axes=(0, 0, 0, None, None))
    _compile(fn, one_chip, ((8, D // B, B, B), BF16),
             ((8, D // B, B, B), BF16), ((8, 1, D), BF16), ((D, FF), I8),
             ((1, FF), F32))


def test_bdmm_banked_compiles(one_chip):
    _compile(jax.vmap(bdmm_pallas), one_chip, ((8, D // B, B, B), BF16),
             ((8, 1, D), BF16))


def test_gs_banked_T_decode_compiles(one_chip):
    _compile(jax.vmap(gs_fused_T_pallas), one_chip,
             ((8, D // B, B, B), BF16), ((8, D // B, B, B), BF16),
             ((8, 1, D), BF16))


@pytest.mark.parametrize("d", [D, FF])
def test_gs_banked_T_rows_decode_compiles(one_chip, d):
    """The row-batched banked rotation at decode: 32 rows of one token, in
    the tiles the dispatch rule picks (d_ff's r = 924 overhangs them)."""
    rows, r = 32, d // B
    row_tile, group_tile = banked_gs_rows_tiles(1, rows, r, B, 2)
    fn = functools.partial(gs_fused_T_rows_pallas, row_tile=row_tile,
                           group_tile=group_tile)
    _compile(fn, one_chip, ((rows, r, B, B), BF16), ((rows, r, B, B), BF16),
             ((rows, 1, d), BF16))


def test_paged_flash_decode_compiles(one_chip):
    pages, page, kv, hd, rows, width = 129, 16, 8, 128, 8, 16
    _compile(paged_flash_decode, one_chip, ((rows, 64, hd), BF16),
             ((pages, page, kv, hd), BF16), ((pages, page, kv, hd), BF16),
             ((rows, width), jnp.int32), ((rows,), jnp.int32))
