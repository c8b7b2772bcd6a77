"""Family-dispatched model API — a thin lookup over the family registry.

Dispatch is keyed on ``ModelConfig.family`` via ``repro.models.registry``
(every family registers a ``FamilyOps`` record; there is no hardcoded
family boolean here). Serving entry points live on
``repro.core.runtime.ModelRuntime``; per-request adapter state travels
only as ``AdapterContext``/``PrefillRequest`` pytrees. The PR-3 era
module-level ``prefill``/``decode_step`` shims (and their loose
``bank``/``adapter_ids``/``bank_cfg`` kwargs) are GONE — CI greps them
out so they cannot return.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from . import encdec, image, transformer  # noqa: F401  (register FamilyOps)
from . import registry
from .layers import no_shard

Array = jnp.ndarray


def family_ops(cfg: ModelConfig) -> registry.FamilyOps:
    """The FamilyOps record for ``cfg.family`` (KeyError on unknown family,
    listing what IS registered)."""
    return registry.get(cfg.family)


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key: jax.Array):
    """Jitted, so each weight's fp32 draw fuses into its cast: the device
    holds the params, never an fp32 copy of a bf16 weight."""
    return family_ops(cfg).init_params(cfg, key)


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))


def forward(cfg: ModelConfig, params, batch, shard=no_shard):
    return family_ops(cfg).forward(cfg, params, batch, shard)


def loss_fn(cfg: ModelConfig, params, batch, shard=no_shard):
    return family_ops(cfg).loss(cfg, params, batch, shard)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      enc_len: int = 0):
    return family_ops(cfg).init_decode_state(cfg, batch, max_len, enc_len)


def abstract_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                          enc_len: int = 0):
    return jax.eval_shape(
        lambda: init_decode_state(cfg, batch, max_len, enc_len))


def param_count(cfg: ModelConfig) -> int:
    return sum(int(math.prod(l.shape))
               for l in jax.tree.leaves(abstract_params(cfg)))


def active_param_count(cfg: ModelConfig) -> int:
    return family_ops(cfg).active_param_count(cfg)
