"""The training cell: the program's jitted GSOFT train step
(``repro.train.steps.build_train_step``) over a frozen bf16 base.

Set-up makes the base weights and one tenant's adapter from the seed,
builds and compiles the step once, and drives that same step through its
first ``checked_steps`` steps on rows of their own; their losses, the
optimizer's first moment after step one and the adapter after the last
are what the reference is compared with. The window then runs further
steps on fresh rows (made on the host and put on the chip each step, as
an input pipeline would) for the run's seconds.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from . import traffic as traffic_lib


@dataclasses.dataclass
class TrainWindow:
    seconds: float
    seconds_measured: float
    steps: int
    rows: int
    seq: int
    config: Dict[str, Any]
    trace: Optional[Any] = None
    trace_window: Optional[tuple] = None
    memory_peak_bytes: int = 0
    peaks: Optional[Dict[str, float]] = None
    compiles: int = 0
    chips: int = 1
    kind: str = "train"


def optimizer_config(mix: Dict[str, Any]):
    from repro import optim
    o = mix["optimizer"]
    if o["kind"] != "adamw":
        raise ValueError(f"unknown optimizer {o['kind']!r}")
    return optim.OptimizerConfig(
        kind="adamw", learning_rate=o["learning_rate"], b1=o["b1"],
        b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"],
        grad_clip=o["grad_clip"])


def build(cfg, config: Dict[str, Any], mix: Dict[str, Any], seed: int):
    """(step, frozen params, adapter (host), trainable, optimizer state)."""
    import jax
    from repro import optim
    from repro.core import peft as peft_lib
    from repro.train.steps import TrainStepConfig, build_train_step
    from . import weights

    ad = config["deployment"]["adapter"]
    params = weights.make_params(cfg, seed)
    adapter = weights.make_adapters(cfg, int(ad["block_size"]), seed,
                                    ["trainee"])["trainee"]
    pc = peft_lib.PEFTConfig(method=ad["method"],
                             block_size=int(ad["block_size"]),
                             use_pallas=cfg.use_pallas)
    want = jax.eval_shape(lambda: peft_lib.init_peft(
        pc, params, jax.random.PRNGKey(0)))
    if jax.tree.map(lambda a: a.shape, want) != jax.tree.map(
            lambda a: a.shape, adapter):
        raise ValueError("benchmark adapter differs from the program's")
    tcfg = TrainStepConfig(peft=pc, opt=optimizer_config(mix))
    trainable = jax.device_put(adapter)
    state = optim.init(tcfg.opt, trainable)
    step = jax.jit(build_train_step(cfg, tcfg), donate_argnums=(1, 2))
    return step, params, adapter, trainable, state


def batch(mix, seed: int, stream: int, vocab: int):
    import jax
    rows = traffic_lib.train_rows(mix, seed, int(mix["global_batch"]),
                                  vocab, stream)
    return jax.device_put(rows)
