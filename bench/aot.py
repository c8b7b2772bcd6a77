#!/usr/bin/env python3
"""Rehearse each cell's programs for a described v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py [--workload NAME ...]

Compiles, at the cell's real sizes, the chunk-prefill and paged-decode
steps of a serving cell (its batch, chunk, pages and adapter bank) or
the train step of a training cell (its rows and sequence length) for one
chip of a described ``v5e:2x2``, with the Pallas kernels compiled and not
interpreted, and prints each program's ``memory_analysis``: arguments,
outputs, temporaries. Nothing runs, so no time or result comes from here;
what it shows is whether a program compiles and fits the chip's 16 GB
(a train batch is sized by it). Compiling at these sizes takes minutes.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
#: what the v5e compiler lets one program use (its out-of-memory message)
HBM = 15.75 * 2**30


def _shapes(tree, sharding):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def serve_programs(cell, cfg, one):
    """(name, jitted fn, argument shapes) of a serving cell's steps."""
    import jax
    import jax.numpy as jnp
    from repro import quant
    from repro.core import peft as peft_lib
    from repro.core.runtime import ModelRuntime
    from repro.models import api
    from harness import traffic, weights
    dep = cell.config["deployment"]
    mix = cell.traffic
    B, chunk, page = (int(dep["max_batch"]), int(dep["prefill_chunk"]),
                      int(dep["page_size"]))
    max_len = traffic.max_len(mix, page)
    pages = -(-max_len // page)
    params = api.abstract_params(cfg)
    rt = ModelRuntime(cfg, params)
    if dep["base_precision"] == "int8":
        qcfg = quant.QuantConfig(mode="int8", use_pallas=cfg.use_pallas)
        params = jax.eval_shape(lambda p: quant.quantize_params(p, qcfg),
                                params)
    state = jax.eval_shape(lambda: rt.paged_state(
        B, B * pages + 1, page, pages))
    ad = dep["adapter"]
    pc = peft_lib.PEFTConfig(method=ad["method"],
                             block_size=int(ad["block_size"]),
                             use_pallas=cfg.use_pallas)
    slots = int(mix["resident_slots"]) + 1
    bank = {}
    for path, (L, d) in weights.adapter_paths(cfg).items():
        b = int(ad["block_size"])
        f = jax.ShapeDtypeStruct((L, slots, d // b, b, b), jnp.float32)
        peft_lib._nest_insert(bank, path, {ad["method"]: {"L": f, "R": f}})

    def ctx(n):
        return peft_lib.AdapterContext(
            bank=bank, slots={ad["method"]: jax.ShapeDtypeStruct(
                (n,), jnp.int32)}, peft=pc)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    req = peft_lib.PrefillRequest(batch={"tokens": i32(1, chunk)},
                                  last_idx=i32(), ctx=ctx(1))
    decode = (params, ctx(B), i32(B, 1), state, i32(B))
    prefill = (params, req, state, i32(), i32())
    rt = ModelRuntime(cfg, params)
    return [("chunk_prefill", rt.chunk_prefill_fn(), _shapes(prefill, one)),
            ("paged_decode", rt.paged_decode_fn(), _shapes(decode, one))]


def train_programs(cell, cfg, one):
    import jax
    import jax.numpy as jnp
    from repro import optim
    from harness import train as train_lib
    from repro.core import peft as peft_lib
    from repro.models import api
    from repro.train.steps import TrainStepConfig, build_train_step
    mix, ad = cell.traffic, cell.config["deployment"]["adapter"]
    params = api.abstract_params(cfg)
    pc = peft_lib.PEFTConfig(method=ad["method"],
                             block_size=int(ad["block_size"]),
                             use_pallas=cfg.use_pallas)
    adapter = jax.eval_shape(lambda: peft_lib.init_peft(
        pc, params, jax.random.PRNGKey(0)))
    tcfg = TrainStepConfig(peft=pc, opt=train_lib.optimizer_config(mix))
    state = jax.eval_shape(lambda a: optim.init(tcfg.opt, a), adapter)
    rows, seq = int(mix["global_batch"]), int(mix["seq_len"])
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((rows, seq), jnp.int32),
             "mask": jax.ShapeDtypeStruct((rows, seq), jnp.float32)}
    step = jax.jit(build_train_step(cfg, tcfg), donate_argnums=(1, 2))
    return [(f"train_step(rows={rows})", step,
             _shapes((params, adapter, state, batch), one))]


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
    from harness import cells
    bench = cells.load_benchmark()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="cell name (default: every cell)")
    ap.add_argument("--global-batch", type=int, default=None,
                    help="train cells: rehearse this batch instead")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import dispatch, ops
    # compile the kernels for the chip, not the CPU interpreter
    dispatch.interpret_mode = ops.interpret_mode = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    names = args.workload or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = cells.load_cell(name)
        if args.global_batch and cell.traffic["kind"] == "train":
            cell.traffic = dict(cell.traffic, global_batch=args.global_batch)
        cfg = cells.model_config(cell.config)
        progs = (serve_programs if cell.traffic["kind"] == "serve"
                 else train_programs)(cell, cfg, one)
        for pname, fn, shapes in progs:
            t0 = time.perf_counter()
            ma = fn.lower(*shapes).compile().memory_analysis()
            total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
            print(json.dumps({
                "cell": name, "program": pname,
                "compile_s": round(time.perf_counter() - t0, 1),
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "total_bytes": total, "fits": total <= HBM}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
