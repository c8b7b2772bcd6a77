"""Shared neural building blocks (functional, tree-of-arrays params).

Conventions:
  * activations (B, S, D); weights (d_in, d_out) used as y = x @ W
    (scan-stacked weights get a leading layer dim)
  * param init in fp32-computed numpy-free jax PRNG, cast to cfg.param_dtype
  * every function takes an explicit ``shard`` callback
    (activation-name -> sharding constraint), identity by default
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.peft import AdaptedWeight
from repro.kernels import ops as kernel_ops
from repro.quant.core import QuantTensor

Array = jnp.ndarray
Shard = Callable[[Array, str], Array]


def no_shard(x: Array, name: str) -> Array:
    return x


def qlinear(x: Array, w, rot: Optional[Callable[[str, Array], Array]] = None,
            name: str = "", cast: bool = False) -> Array:
    """The QuantizedLinear hook — every base-weight projection on the
    attention/MLP/head path routes through here.

    ``w`` is either a plain weight array (y = x @ w, unchanged numerics)
    or a ``QuantTensor`` (int8/fp8 codes + scales), in which case the
    matmul dispatches through ``kernels.ops.q_matmul`` with the dequant in
    the epilogue. ``rot(name, x)`` is the optional per-request adapter
    rotation — method-generic (any banked ``core.methods`` entry), bf16,
    never quantized. When the weight is quantized, the rotator's
    ``quant_rotation`` hook splits the work: methods with a fused kernel
    (GSOFT) hand back per-row factors so rotation + base matmul collapse
    into one ``gs_q_matmul_banked`` call — the rotated slab never leaves
    VMEM on the Pallas path — while the other method stacks (OFT / BOFT /
    Householder) apply to the activations first.

    ``cast=True`` pre-casts a PLAIN weight to the activation dtype (the
    lm_head/patch_proj call sites, whose weights may be wider than the
    activations); quantized matmuls already return ``x.dtype``.

    An ``AdaptedWeight`` (the train step's lazily adapted weight) is
    applied here, inside the layer that uses it.
    """
    if isinstance(w, AdaptedWeight):
        x, w = w.apply(x)
    if isinstance(w, QuantTensor):
        factors = None
        if rot is not None:
            if hasattr(rot, "quant_rotation"):
                x, factors = rot.quant_rotation(name, x, x.dtype)
            else:
                x = rot(name, x)
        if factors is not None:
            return kernel_ops.gs_q_matmul_banked(
                factors[0], factors[1], x, w.q, w.scale,
                use_pallas=w.meta.use_pallas)
        return kernel_ops.q_matmul(x, w.q, w.scale,
                                   use_pallas=w.meta.use_pallas)
    if rot is not None:
        x = rot(name, x)
    return x @ (w.astype(x.dtype) if cast else w)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * s).astype(dtype)


def stacked_dense_init(key, n: int, d_in: int, d_out: int, dtype,
                       scale: Optional[float] = None):
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (n, d_in, d_out), jnp.float32) * s
    return w.astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    """fp32 statistics; the normalized tensor drops to the input dtype
    BEFORE the scale multiply (§Perf iteration H2b: one fewer fp32
    activation-sized pass per norm; scale is a per-channel vector so the
    bf16 multiply loses < 1 ulp of bf16)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = (x32 * jax.lax.rsqrt(var + eps)).astype(dt)
    return y * (1.0 + scale).astype(dt)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                            # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, f: int, mlp_type: str, dtype) -> Dict[str, Array]:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"wi": dense_init(k1, d, f, dtype),
         "wo": dense_init(k3, f, d, dtype)}
    if mlp_type in ("swiglu", "geglu"):
        p["wg"] = dense_init(k2, d, f, dtype)
    return p


def init_stacked_mlp(key, n: int, d: int, f: int, mlp_type: str, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"wi": stacked_dense_init(k1, n, d, f, dtype),
         "wo": stacked_dense_init(k3, n, f, d, dtype)}
    if mlp_type in ("swiglu", "geglu"):
        p["wg"] = stacked_dense_init(k2, n, d, f, dtype)
    return p


def apply_mlp(p: Dict[str, Array], x: Array, mlp_type: str,
              shard: Shard = no_shard,
              rot: Optional[Callable[[str, Array], Array]] = None) -> Array:
    """``rot(name, x)`` optionally rotates the inputs of projection ``name``
    (wi/wg/wo) — activation-side GSOFT for per-request adapters. Every
    projection goes through the ``qlinear`` hook, so int8-quantized base
    weights (``ModelRuntime.quantized``) serve transparently."""
    h = shard(qlinear(x, p["wi"], rot, "wi"), "act_ff")
    if mlp_type == "swiglu":
        h = jax.nn.silu(shard(qlinear(x, p["wg"], rot, "wg"), "act_ff")) * h
    elif mlp_type == "geglu":
        h = jax.nn.gelu(shard(qlinear(x, p["wg"], rot, "wg"), "act_ff"),
                        approximate=True) * h
    elif mlp_type == "gelu":
        h = jax.nn.gelu(h, approximate=True)
    else:
        raise ValueError(mlp_type)
    return shard(qlinear(h, p["wo"], rot, "wo"), "act_d")


# ---------------------------------------------------------------------------
# vocab-sharded cross entropy
# ---------------------------------------------------------------------------

def softcap(logits: Array, cap: float) -> Array:
    if cap <= 0:
        return logits
    return jnp.tanh(logits / cap) * cap


def cross_entropy(logits: Array, labels: Array, valid: Optional[Array] = None,
                  vocab_size: int = 0) -> Tuple[Array, Array]:
    """Mean CE over valid tokens. logits (B, S, Vp) may be vocab-padded and
    vocab-sharded (sharding-friendly: max/logsumexp reduce over the sharded
    axis lower to small all-reduces, never a full-vocab gather).

    Returns (loss, accuracy)."""
    b, s, vp = logits.shape
    l32 = logits.astype(jnp.float32)
    if vocab_size and vocab_size < vp:
        pad_mask = jnp.arange(vp) >= vocab_size
        l32 = jnp.where(pad_mask[None, None, :], -1e30, l32)
    m = jax.lax.stop_gradient(jnp.max(l32, axis=-1, keepdims=True))
    shifted = l32 - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(l32, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    pred = jnp.argmax(l32, axis=-1)
    correct = (pred == labels).astype(jnp.float32)
    if valid is None:
        valid = jnp.ones_like(nll)
    valid = valid.astype(jnp.float32)
    denom = jnp.maximum(valid.sum(), 1.0)
    return (nll * valid).sum() / denom, (correct * valid).sum() / denom
