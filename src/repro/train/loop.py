"""The training loop: checkpointed, heartbeat-monitored, resumable.

Wiring (per DESIGN §4): deterministic data by (seed, step) — resume replays
exactly; checkpoints carry adapters + optimizer + data cursor; heartbeat +
step-time straggler detection are the signals an external restart
supervisor consumes (runtime/watchdog.py).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.checkpoint import CheckpointManager
from repro.config import ModelConfig
from repro.core import peft as peft_lib
from repro.core.runtime import ModelRuntime
from repro.data import DataConfig, LMDataSource
from repro.runtime import Heartbeat, StepTimer
from repro.train.steps import TrainStepConfig, build_train_step


@dataclasses.dataclass
class LoopConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    heartbeat_path: Optional[str] = None
    async_ckpt: bool = True


def train(cfg: ModelConfig, tcfg: TrainStepConfig, dcfg: DataConfig,
          loop: LoopConfig, mesh=None, resume: bool = True,
          log_fn: Callable[[str], None] = print) -> Dict[str, Any]:
    key = jax.random.PRNGKey(dcfg.seed)
    params = ModelRuntime(cfg, key=key, mesh=mesh).params
    adapters = peft_lib.init_peft(tcfg.peft, params, key)
    trainable, frozen = peft_lib.trainable_and_frozen(tcfg.peft, params,
                                                      adapters)
    if not tcfg.peft.is_peft:
        trainable, frozen = params, {}
    opt_state = optim.init(tcfg.opt, trainable)

    step_fn = build_train_step(cfg, tcfg, mesh)
    if mesh is not None:
        from repro.sharding.specs import ShardingRules, named
        rules = ShardingRules(cfg, mesh)
        p_sh = named(mesh, rules.params_tree(frozen if tcfg.peft.is_peft
                                             else trainable))
        if tcfg.peft.is_peft:
            t_sh = named(mesh, rules.adapters_tree(trainable))
            frozen = jax.device_put(frozen, p_sh)
        else:
            t_sh = p_sh
        o_sh = jax.tree.map(lambda _: named(
            mesh, jax.sharding.PartitionSpec()), opt_state)
        o_sh = {"mu": t_sh, "nu": t_sh,
                "step": named(mesh, jax.sharding.PartitionSpec())} \
            if tcfg.opt.kind == "adamw" else o_sh
        trainable = jax.device_put(trainable, t_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        step_fn = jax.jit(step_fn, out_shardings=(t_sh, o_sh, None))
    else:
        step_fn = jax.jit(step_fn)

    data = LMDataSource(dcfg)
    start_step = 0
    mgr = None
    if loop.ckpt_dir:
        mgr = CheckpointManager(loop.ckpt_dir)
        if resume and mgr.latest_step() is not None:
            state = mgr.restore({"trainable": jax.device_get(trainable),
                                 "opt": jax.device_get(opt_state)})
            trainable = jax.tree.map(jnp.asarray, state["trainable"])
            opt_state = jax.tree.map(jnp.asarray, state["opt"])
            start_step = mgr.extra().get("data_step", mgr.latest_step())
            log_fn(f"resumed from step {start_step}")

    hb = Heartbeat(loop.heartbeat_path) if loop.heartbeat_path else None
    timer = StepTimer()
    history = []
    for step in range(start_step, loop.steps):
        batch = jax.tree.map(jnp.asarray, data.batch_at(step))
        timer.start()
        trainable, opt_state, metrics = step_fn(frozen, trainable,
                                                opt_state, batch)
        jax.block_until_ready(metrics["loss"])
        t = timer.stop()
        if hb:
            hb.beat(step)
        if step % loop.log_every == 0 or step == loop.steps - 1:
            loss = float(metrics["loss"])
            history.append({"step": step, "loss": loss,
                            "accuracy": float(metrics["accuracy"]),
                            "grad_norm": float(metrics["grad_norm"]),
                            "step_time_s": t["step_time_s"],
                            "straggler": t["straggler"]})
            log_fn(f"step {step:5d} loss {loss:.4f} "
                   f"acc {float(metrics['accuracy']):.3f} "
                   f"({t['step_time_s']:.2f}s)")
        if mgr and ((step + 1) % loop.ckpt_every == 0 or
                    step == loop.steps - 1):
            mgr.save(step + 1,
                     {"trainable": trainable, "opt": opt_state},
                     blocking=not loop.async_ckpt,
                     extra={"data_step": step + 1})
    if mgr:
        mgr.wait()
    # serving runtime over the TRAINED weights: adapters merged into the
    # frozen base (PEFT) or the trained tree itself (full FT) — returning
    # the init-time runtime here would silently serve untrained params
    final_params = (peft_lib.materialize_tree(tcfg.peft, frozen, trainable,
                                              merged=True)
                    if tcfg.peft.is_peft else trainable)
    return {"trainable": trainable, "opt_state": opt_state, "frozen": frozen,
            "history": history,
            "runtime": ModelRuntime(cfg, final_params, mesh=mesh)}
