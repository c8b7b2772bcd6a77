"""Weights and adapters made from the seed by the benchmark, not by the
program: one jitted call on the device makes every base weight in the
type it is served in, laid out as the program's decoder tree; adapters
are GSOFT generator blocks. The reference reads the same arrays.

Distributions: embedding N(0, 1); each projection N(0, 1 / d_in); QKV
biases N(0, 0.02^2); norm gains ``1 + g`` with g ~ N(0, 0.1^2) (the
program stores the gain's offset from 1).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

BIAS_STD = 0.02
GAIN_STD = 0.1
ADAPTER_STD = 0.1          # generator entries; K = A - A^T, Q = cayley(K)


def root_key(seed: int, stream: int):
    import jax
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def shapes(cfg) -> Dict[str, Any]:
    """{path: (shape, std or None for a norm gain, kind)} of the decoder
    tree: layers stacked on a leading axis."""
    L, d, f = cfg.num_layers, cfg.d_model, cfg.d_ff
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.d_head
    vp = cfg.padded_vocab()
    out = {
        "embed/table": ((vp, d), 1.0),
        "final_norm": ((d,), "gain"),
        "lm_head/w": ((d, vp), d ** -0.5),
        "layers/attn_norm": ((L, d), "gain"),
        "layers/mlp_norm": ((L, d), "gain"),
        "layers/attn/wq": ((L, d, H * hd), d ** -0.5),
        "layers/attn/wk": ((L, d, K * hd), d ** -0.5),
        "layers/attn/wv": ((L, d, K * hd), d ** -0.5),
        "layers/attn/wo": ((L, H * hd, d), (H * hd) ** -0.5),
        "layers/mlp/wi": ((L, d, f), d ** -0.5),
        "layers/mlp/wg": ((L, d, f), d ** -0.5),
        "layers/mlp/wo": ((L, f, d), f ** -0.5),
    }
    if cfg.qkv_bias:
        out["layers/attn/bq"] = ((L, H * hd), BIAS_STD)
        out["layers/attn/bk"] = ((L, K * hd), BIAS_STD)
        out["layers/attn/bv"] = ((L, K * hd), BIAS_STD)
    return out


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    root: Dict[str, Any] = {}
    for path, v in flat.items():
        node = root
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return root


def make_params(cfg, seed: int):
    """The base weights, made on the device in one jitted call, in the
    program's weight dtype. Checked against the program's own tree."""
    import jax
    import jax.numpy as jnp
    from repro.models import api
    dtype = cfg.weight_dtype
    spec = shapes(cfg)

    @jax.jit
    def build(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            if std == "gain":
                flat[path] = (GAIN_STD * jax.random.normal(k, shape, dtype))
            else:
                flat[path] = (jax.random.normal(k, shape, dtype)
                              * jnp.asarray(std, dtype))
        return _nest(flat)

    params = build(root_key(seed, 0))
    want = jax.tree.map(lambda a: (a.shape, a.dtype), api.abstract_params(cfg))
    have = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(have) or \
            jax.tree.leaves(want) != jax.tree.leaves(have):
        raise ValueError("benchmark weight tree differs from the program's: "
                         f"{have} vs {want}")
    return params


def adapter_paths(cfg) -> Dict[str, tuple]:
    """{weight path: (layers, d_in)} of the weights GSOFT adapts: the
    seven attention and MLP projections of every layer."""
    L, d, f = cfg.num_layers, cfg.d_model, cfg.d_ff
    hd = cfg.num_heads * cfg.d_head
    return {"layers/attn/wq": (L, d), "layers/attn/wk": (L, d),
            "layers/attn/wv": (L, d), "layers/attn/wo": (L, hd),
            "layers/mlp/wi": (L, d), "layers/mlp/wg": (L, d),
            "layers/mlp/wo": (L, f)}


def gs_block(d: int, block: int) -> int:
    """The GSOFT block size for input width ``d``: ``block`` when it
    divides d (the configuration states it), else an error."""
    if d % block:
        raise ValueError(f"block {block} does not divide {d}")
    return block


@functools.lru_cache(maxsize=None)
def _adapter_fn(layout: tuple):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        out = {}
        for i, (path, L, r, b) in enumerate(layout):
            kl, kr = jax.random.split(jax.random.fold_in(key, i))
            out[path] = {
                "L": ADAPTER_STD * jax.random.normal(kl, (L, r, b, b),
                                                     jnp.float32),
                "R": ADAPTER_STD * jax.random.normal(kr, (L, r, b, b),
                                                     jnp.float32)}
        return out
    return build


def make_adapters(cfg, block: int, seed: int, names: List[str]
                  ) -> Dict[str, Dict[str, Dict[str, np.ndarray]]]:
    """One GSOFT adapter per tenant name, as host arrays {path: {"L", "R"}}
    of generator blocks (L, r, b, b) f32 — the form ``init_peft`` gives."""
    import jax
    layout = tuple((p, L, d // gs_block(d, block), block)
                   for p, (L, d) in sorted(adapter_paths(cfg).items()))
    build = _adapter_fn(layout)
    out = {}
    for i, name in enumerate(names):
        out[name] = jax.device_get(build(root_key(seed, 1000 + i)))
    return out
