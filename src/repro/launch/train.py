"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-72b --smoke --peft gsoft --steps 200 \
        --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Multi-device: pass --mesh data,model sized to the visible devices. When
JAX_COORDINATOR is set, jax.distributed.initialize() is called first
(multi-host pods). The persistent compile cache goes where
repro.launch.compile_cache says.
"""
from __future__ import annotations

import argparse
import os

import jax

from repro import optim
from repro.config import get_config, get_smoke_config, parse_overrides
from repro.core import methods as methods_lib
from repro.core import peft as peft_lib
from repro.data import DataConfig
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.optim import schedules
from repro.train.loop import LoopConfig, train
from repro.train.steps import TrainStepConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--peft", default="gsoft",
                    choices=methods_lib.registered() + ["full"])
    ap.add_argument("--block-size", type=int, default=32)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--mesh", default=None, help="e.g. 4,2 for (data, model)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--corpus", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--set", nargs="*", default=[])
    args = ap.parse_args()
    compile_cache.enable()

    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize()  # multi-host pods

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = cfg.with_overrides(**parse_overrides(args.set))

    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh(d, m)

    tcfg = TrainStepConfig(
        peft=peft_lib.PEFTConfig(method=args.peft, block_size=args.block_size,
                                 use_pallas=cfg.use_pallas),
        opt=optim.OptimizerConfig(learning_rate=args.lr),
        num_microbatches=args.microbatches,
        schedule=schedules.warmup_cosine(args.warmup, args.steps),
    )
    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                      seed=args.seed, corpus_path=args.corpus,
                      vocab_size=min(cfg.vocab_size, 256))
    loop = LoopConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir,
                      heartbeat_path=(os.path.join(args.ckpt_dir, "heartbeat")
                                      if args.ckpt_dir else None))
    out = train(cfg, tcfg, dcfg, loop, mesh=mesh, resume=not args.no_resume)
    hist = out["history"]
    if hist:
        print(f"final loss {hist[-1]['loss']:.4f} "
              f"(from {hist[0]['loss']:.4f} @ step {hist[0]['step']})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
