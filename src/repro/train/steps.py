"""Jitted train / serve step builders (the units the dry-run lowers).

train_step (PEFT mode — the paper's setting):
    inputs : frozen base params (bf16, no grads), adapter params (fp32,
             trainable), optimizer state (adapters only), batch
    body   : scan over microbatches -> mean adapter grads -> AdamW update
    Adapters apply inside the layer that uses each weight
    (core.peft.materialize_tree(lazy=True) -> AdaptedWeight): GSOFT and the
    other input-rotation methods rotate activations, x (Q W) = (x Q) W, so
    no adapted weight is built — zero extra collectives under TP.

serve_step: decode_step over a sharded KV cache / SSM state (cache donated);
``pos`` may be per-slot (continuous batching) and an optional
``AdapterContext`` pytree adds per-request GS rotations.
``build_slot_prefill_step`` is the continuous engine's admission unit:
batch-1 prefill scattered into a decode slot. ``ModelRuntime`` owns the
jitted closures built here — engines and launchers go through it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro import optim
from repro.config import ModelConfig
from repro.core import peft as peft_lib
from repro.kernels import dispatch as kernel_dispatch
from repro.models import api
from repro.models.layers import no_shard
from repro.sharding.specs import ShardingRules

Tree = Any


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    peft: peft_lib.PEFTConfig = peft_lib.PEFTConfig()
    opt: optim.OptimizerConfig = optim.OptimizerConfig()
    num_microbatches: int = 1
    schedule: Optional[Callable] = None


def _split_microbatches(batch: Tree, n: int) -> Tree:
    return jax.tree.map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)


def _resolve_peft(cfg: ModelConfig, tcfg: TrainStepConfig) -> peft_lib.PEFTConfig:
    """Sync the kernel path into the PEFT config and install any launch-
    geometry overrides the model config carries: a model run with
    ``use_pallas=True`` fine-tunes through the differentiable Pallas kernels
    end-to-end (adapter materialization included)."""
    kernel_dispatch.install_tunings(cfg.kernel_tunings)
    peft_cfg = tcfg.peft
    if cfg.use_pallas and peft_cfg.is_peft and not peft_cfg.use_pallas:
        peft_cfg = dataclasses.replace(peft_cfg, use_pallas=True)
    return peft_cfg


def build_train_step(cfg: ModelConfig, tcfg: TrainStepConfig,
                     mesh: Optional[Mesh] = None,
                     batch_divisible: bool = True):
    """Returns train_step(frozen, trainable, opt_state, batch) ->
    (trainable, opt_state, metrics). PEFT: trainable = adapters; full FT:
    trainable = params and frozen is an empty dict."""
    shard = (ShardingRules(cfg, mesh).make_sharder(batch_divisible)
             if mesh is not None else no_shard)
    peft_cfg = _resolve_peft(cfg, tcfg)
    is_peft = peft_cfg.is_peft
    n_micro = tcfg.num_microbatches
    schedule = tcfg.schedule or (lambda s: jnp.asarray(1.0, jnp.float32))

    def loss_fn(trainable, frozen, mb):
        if is_peft:
            params = peft_lib.materialize_tree(peft_cfg, frozen, trainable,
                                               lazy=True)
        else:
            params = trainable
        loss, metrics = api.loss_fn(cfg, params, mb, shard)
        return loss, metrics

    def train_step(frozen: Tree, trainable: Tree, opt_state: Tree,
                   batch: Tree):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        if n_micro > 1:
            mbs = _split_microbatches(batch, n_micro)

            def acc_step(carry, mb):
                gacc, lacc = carry
                (loss, metrics), g = grad_fn(trainable, frozen, mb)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / n_micro, gacc, g)
                return (gacc, lacc + loss / n_micro), metrics

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), trainable)
            (grads, loss), metrics_all = jax.lax.scan(
                acc_step, (zeros, jnp.zeros((), jnp.float32)), mbs)
            metrics = jax.tree.map(lambda m: m[-1], metrics_all)
            metrics["loss"] = loss
        else:
            (loss, metrics), grads = grad_fn(trainable, frozen, batch)

        lr_scale = schedule(opt_state["step"])
        new_trainable, new_opt, om = optim.update(
            tcfg.opt, grads, opt_state, trainable, lr_scale)
        metrics = dict(metrics)
        metrics.update(om)
        return new_trainable, new_opt, metrics

    return train_step


def build_eval_step(cfg: ModelConfig, tcfg: TrainStepConfig,
                    mesh: Optional[Mesh] = None):
    shard = (ShardingRules(cfg, mesh).make_sharder() if mesh is not None
             else no_shard)
    peft_cfg = _resolve_peft(cfg, tcfg)

    def eval_step(frozen, trainable, batch):
        params = (peft_lib.materialize_tree(peft_cfg, frozen, trainable,
                                            lazy=True)
                  if peft_cfg.is_peft else trainable)
        _, metrics = api.loss_fn(cfg, params, batch, shard)
        return metrics
    return eval_step


def build_decode_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                      batch_divisible: bool = True):
    """One decode token for the whole batch. ``pos`` may be a scalar
    (lockstep) or an int32 (B,) array of per-slot write positions
    (continuous batching).

    ``ctx`` is an optional ``AdapterContext`` (None when serving the bare
    model): each row's activations rotate with its own GS adapter, slot 0
    being the identity. Structure of ctx is part of the jit cache key."""
    shard = (ShardingRules(cfg, mesh).make_sharder(batch_divisible)
             if mesh is not None else no_shard)
    fam = api.family_ops(cfg)

    def serve_step(params, ctx, tokens, state, pos):
        logits, new_state = fam.decode_step(cfg, params, tokens, state, pos,
                                            shard, ctx=ctx)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], logits, new_state

    return serve_step


def build_paged_decode_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                            batch_divisible: bool = True):
    """One decode token for the whole batch through per-slot PAGE TABLES
    (ISSUE 7). Same call shape as ``build_decode_step`` — params, ctx,
    tokens (B, 1), state {"pages", "table"}, pos (B,) — so the paged engine
    drops in next to the contiguous one. Parked rows (pos at the sentinel
    position) write into the garbage page; their sampled token is ignored
    by the engine."""
    shard = (ShardingRules(cfg, mesh).make_sharder(batch_divisible)
             if mesh is not None else no_shard)
    fam = api.family_ops(cfg)
    if fam.paged_decode_step is None:
        raise ValueError(f"family {cfg.family!r} has no paged decode path")

    def serve_step(params, ctx, tokens, state, pos):
        logits, new_state = fam.paged_decode_step(cfg, params, tokens, state,
                                                  pos, shard, ctx=ctx)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], logits, new_state

    return serve_step


def build_chunk_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                             batch_divisible: bool = True):
    """Chunked-prefill admission unit: ONE fixed-width prompt chunk for ONE
    slot, written through that slot's page table. Returns
    step(params, req, state, slot, start) -> (first_token scalar,
    last_logits (V,), state); the chunk width is static (one trace per
    width), slot/start are traced scalars, and first_token / last_logits
    (the logits at req.last_idx) are only meaningful on the final chunk,
    where req.last_idx marks the prompt's last valid token."""
    shard = (ShardingRules(cfg, mesh).make_sharder(batch_divisible)
             if mesh is not None else no_shard)
    fam = api.family_ops(cfg)
    if fam.paged_chunk_prefill is None:
        raise ValueError(f"family {cfg.family!r} has no chunked-prefill path")

    def chunk_step(params, req: peft_lib.PrefillRequest, state, slot, start):
        logits, new_state = fam.paged_chunk_prefill(cfg, params, req, state,
                                                    slot, start, shard)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[0]
        return first, logits[0, -1], new_state

    return chunk_step


def build_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                       batch_divisible: bool = True):
    """Full-prompt prefill. The single ``PrefillRequest`` argument carries
    the input batch, the per-row ``last_idx`` (ragged-prompt fix) and the
    optional AdapterContext — there are no mode flags or loose kwargs."""
    shard = (ShardingRules(cfg, mesh).make_sharder(batch_divisible)
             if mesh is not None else no_shard)
    fam = api.family_ops(cfg)

    def prefill_step(params, req: peft_lib.PrefillRequest, state):
        logits, new_state = fam.prefill(cfg, params, req, state, shard)
        return logits, new_state

    return prefill_step


def _decode_state_batch_axes(cfg: ModelConfig, max_len: int, enc_len: int):
    """Per-leaf batch-axis tree for the decode state, found by diffing the
    abstract state shapes at two batch sizes (leaves place the batch dim at
    different axes: kv (L, B, S, K, D) vs encdec enc_out (B, F, D) vs hybrid
    mamba (nsuper, per, B, ...))."""
    s1 = api.abstract_decode_state(cfg, 1, max_len, enc_len)
    s2 = api.abstract_decode_state(cfg, 2, max_len, enc_len)

    def axis(a, b):
        for i, (x, y) in enumerate(zip(a.shape, b.shape)):
            if x != y:
                return i
        raise ValueError(f"no batch axis found in decode-state leaf {a.shape}")

    return jax.tree.map(axis, s1, s2)


def build_slot_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                            *, max_len: int, enc_len: int = 0,
                            batch_divisible: bool = True):
    """Continuous-batching admission: prefill ONE request (batch 1) and
    scatter its fresh decode state into row ``slot`` of the engine's
    persistent slot-array state.

    Returns step(params, req, state, slot) -> (first_token scalar, updated
    state). ``req`` is a batch-1 ``PrefillRequest`` carrying the bucketed
    prompt feed, its ``last_idx`` (the request's last valid position in the
    processed stream — ragged fix) and, when serving a bank, an
    AdapterContext with the (1,) slot id. Donate ``state`` when jitting.
    """
    shard = (ShardingRules(cfg, mesh).make_sharder(batch_divisible)
             if mesh is not None else no_shard)
    fam = api.family_ops(cfg)
    axes = _decode_state_batch_axes(cfg, max_len, enc_len)

    def scatter(dst, src, ax, slot):
        start = [0] * dst.ndim
        start[ax] = slot
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                            tuple(start))

    def slot_prefill(params, req, state, slot):
        sub = fam.init_decode_state(cfg, 1, max_len, enc_len)
        logits, sub = fam.prefill(cfg, params, req, sub, shard)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[0]
        state = jax.tree.map(
            lambda dst, src, ax: scatter(dst, src, ax, slot),
            state, sub, axes)
        return first, state

    return slot_prefill
