"""Pallas TPU kernels for the framework's compute hot-spots.

  bdmm     — block-diagonal (grouped) matmul: the GS "group" primitive
  gs_fused — GSOFT rotation P^T L P R x, its transpose rotation Q^T x,
             and the backward (dx, dL, dR), built from bdmm launches with
             the P shuffle between them
  q_matmul — int8-weight matmul with the dequant in the epilogue
  ssd      — Mamba2 state-space-dual chunked scan (mamba2/zamba2 archs)

Each kernel has a pure-jnp oracle in ref.py; ops.py is the jit-friendly
dispatch used by the model code.

``use_pallas`` semantics
------------------------
``False`` (default) runs the reference path — identical math via XLA, used
on backends where Mosaic cannot lower (launch/dryrun.py pins it off).
``True`` runs ``pl.pallas_call``: compiled on TPU, interpreted on the CPU
backend so tests exercise the kernel bodies there; any other backend raises
(``dispatch.interpret_mode``).
Both settings are fully differentiable: the Pallas path installs the
``jax.custom_vjp`` rules from dispatch.py, whose backward passes are Pallas
kernels too (transposed-blocks bdmm + token-contraction for bdmm; the
transpose rotation R^T P^T L^T P plus fused per-factor gradients for
gs_fused).

Autotuner overrides
-------------------
Launch geometry (token/group tiles) resolves per (shape, dtype, backend)
in dispatch.py: explicit ``tuning=`` argument and config overrides
(``ModelConfig.kernel_tunings``, installed via ``dispatch.install_tunings``)
take precedence, then cached ``dispatch.autotune_*`` search results, then
shape heuristics. Autotuning is eager (times real launches) — trigger it
from warmup/benchmark code, never inside jit.
"""
from .ops import bdmm, flash_mha, gs_transform, gs_transform_T, ssd
from . import dispatch, ref
