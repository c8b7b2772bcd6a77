"""Shared int8/fp8 quantization primitives (weights AND gradients).

``quantize_int8`` / ``dequantize_int8`` are THE one implementation of
symmetric int8 quantization in the repo: gradient compression
(``optim.compression``, per-tensor, error feedback) and the serving-side
weight quantization (``quant.weights``, per-channel) both call them. The
``axis`` argument selects the granularity:

  * ``axis=None`` — per-tensor: one scalar scale (the gradient-compression
    setting; matches the historical ``optim.compression.quantize_int8``).
  * ``axis=k``    — per-channel: one scale per slice along axis ``k``,
    computed with ``keepdims`` so the scale broadcasts against ``q``
    (and survives ``lax.scan`` slicing of stacked layer weights).

``QuantTensor`` is the pytree node a quantized weight becomes: int8 (or
fp8) codes + fp32 scales as children, the logical dtype/mode/kernel-path
as static aux data — so quantized parameter trees flow through ``jit``,
``lax.scan`` and the checkpoint manager like any other params.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

INT8_MAX = 127.0
# fp8 e4m3 finite max (jax calls it float8_e4m3fn); the fp8 path is a
# STUB: it exists so the scale/metadata plumbing is exercised, and runs
# only via the reference matmul.
FP8_MAX = 448.0


def _absmax_scale(x32: Array, axis: Optional[int], qmax: float,
                  batch_dims: int = 0) -> Array:
    if axis is None and batch_dims == 0:
        amax = jnp.max(jnp.abs(x32))                 # per-tensor scalar
    else:
        keep = {axis % x32.ndim} if axis is not None else set()
        reduce_axes = tuple(a for a in range(batch_dims, x32.ndim)
                            if a not in keep)
        amax = jnp.max(jnp.abs(x32), axis=reduce_axes or None, keepdims=True)
    return jnp.maximum(amax, 1e-12) / qmax


# jitted so that quantizing a weight outside jit (ModelRuntime.quantized)
# fuses the fp32 widening into the reduction and the rounding: a bf16
# weight never gets a materialized fp32 copy on the device.
@functools.partial(jax.jit, static_argnames=("axis", "batch_dims"))
def quantize_int8(x: Array, axis: Optional[int] = None,
                  batch_dims: int = 0) -> Tuple[Array, Array]:
    """Symmetric int8 quantization -> (q int8, scale fp32).

    ``axis=None``: per-tensor scalar scale (gradient compression).
    ``axis=k``: per-channel scales along ``k`` (keepdims, broadcastable).
    ``batch_dims``: leading axes treated as independent tensors (stacked
    layer weights) — scales keep those dims so ``lax.scan`` slices them
    alongside the codes.
    """
    x32 = x.astype(jnp.float32)
    scale = _absmax_scale(x32, axis, INT8_MAX, batch_dims)
    q = jnp.clip(jnp.round(x32 / scale), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("axis", "batch_dims"))
def quantize_fp8(x: Array, axis: Optional[int] = None,
                 batch_dims: int = 0) -> Tuple[Array, Array]:
    """fp8 (e4m3) cast with absmax scaling — stub path (reference
    matmul only)."""
    x32 = x.astype(jnp.float32)
    scale = _absmax_scale(x32, axis, FP8_MAX, batch_dims)
    q = (x32 / scale).astype(jnp.float8_e4m3fn)
    return q, scale


def dequantize_fp8(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# QuantTensor: the pytree node a quantized weight becomes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantMeta:
    """Static (hashable, jit-cache-key) description of a QuantTensor."""
    mode: str = "int8"            # int8 | fp8
    dtype: str = "bfloat16"       # logical dtype of the original weight
    axis: Optional[int] = -1      # channel axis (None = per-tensor)
    use_pallas: bool = False      # matmuls via the q_matmul Pallas kernels


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True)
class QuantTensor:
    """A quantized weight: codes + scales as pytree children, meta static.

    Mirrors the logical weight's ``shape``/``ndim`` so shape-driven code
    (PEFT spec inference, scan stacking) keeps working; ``scale`` keeps the
    same rank as ``q`` (keepdims) so ``lax.scan`` slices both coherently
    for stacked layer weights.
    """
    q: Array                      # int8 / fp8 codes, original weight shape
    scale: Array                  # fp32, keepdims-broadcastable against q
    meta: QuantMeta = QuantMeta()

    def tree_flatten_with_keys(self):
        return (((jax.tree_util.GetAttrKey("q"), self.q),
                 (jax.tree_util.GetAttrKey("scale"), self.scale)), self.meta)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(q=children[0], scale=children[1], meta=aux)

    # -- logical-weight mirror -------------------------------------------------
    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def dtype(self):
        return jnp.dtype(self.meta.dtype)

    @property
    def nbytes(self) -> int:
        return (int(self.q.size) * self.q.dtype.itemsize
                + int(self.scale.size) * self.scale.dtype.itemsize)

    def dequantize(self, dtype=None) -> Array:
        w = self.q.astype(jnp.float32) * self.scale
        return w.astype(dtype or self.dtype)


def is_quant_tensor(x: Any) -> bool:
    return isinstance(x, QuantTensor)


def quantize_tensor(w: Array, mode: str = "int8",
                    axis: Optional[int] = -1,
                    use_pallas: bool = False) -> QuantTensor:
    """One weight -> QuantTensor (per-channel along ``axis`` by default).
    Leading dims beyond the trailing (d_in, d_out) matrix are stacked
    layers — each gets independent scales (scan-sliceable keepdims)."""
    batch_dims = max(w.ndim - 2, 0)
    if mode == "int8":
        q, scale = quantize_int8(w, axis=axis, batch_dims=batch_dims)
    elif mode == "fp8":
        q, scale = quantize_fp8(w, axis=axis, batch_dims=batch_dims)
    else:
        raise ValueError(f"unknown quantization mode {mode!r} "
                         "(have: int8, fp8)")
    meta = QuantMeta(mode=mode, dtype=jnp.dtype(w.dtype).name, axis=axis,
                     use_pallas=use_pallas)
    return QuantTensor(q=q, scale=scale, meta=meta)
