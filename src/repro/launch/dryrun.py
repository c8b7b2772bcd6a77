import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell
against ShapeDtypeStruct inputs — no allocation — and record
memory_analysis / cost_analysis / collective traffic for the roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch qwen2-72b --shape train_4k --mesh single --peft gsoft

Pallas kernels are disabled here (TPU kernels cannot lower on the CPU
backend); the pure-JAX path is semantically identical (tests prove it).
"""
import argparse
import functools
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import optim
from repro.analysis.hlo import collective_stats
from repro.analysis.roofline import Roofline, advice, model_flops
from repro.config import (SHAPES, get_config, list_archs, parse_overrides,
                          shape_applicable)
from repro.core import peft as peft_lib
from repro.core.runtime import ModelRuntime
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (decode_input_specs, prefill_input_specs,
                                train_input_specs)
from repro.sharding.specs import ShardingRules, dp_size, named
from repro.train.steps import TrainStepConfig, build_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../results/dryrun")


def _mem_dict(ma) -> Dict[str, float]:
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes")
    return {k: float(getattr(ma, k, 0) or 0) for k in keys}


def _microbatches(shape, mesh) -> int:
    local = shape.global_batch // max(dp_size(mesh), 1)
    if shape.global_batch % dp_size(mesh):
        return 1
    # keep per-device microbatch small enough for remat'd activations
    for n in (8, 4, 2, 1):
        if shape.global_batch % n == 0 and (shape.global_batch // n) % dp_size(mesh) == 0:
            return n
    return 1


def run_cell(arch: str, shape_name: str, mesh_kind: str, peft: str = "gsoft",
             overrides: Optional[dict] = None, save_hlo: bool = False,
             microbatches: int = 0) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "peft": peft, "ok": False}
    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        rec.update({"skipped": True, "reason": why, "ok": True})
        return rec
    t0 = time.time()
    try:
        cfg = get_config(arch).with_overrides(use_pallas=False,
                                              **(overrides or {}))
        shape = SHAPES[shape_name]
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        chips = int(len(mesh.devices.ravel()))
        rules = ShardingRules(cfg, mesh)
        rt = ModelRuntime.abstract(cfg, mesh=mesh)
        params_abs = rt.params
        params_sh = named(mesh, rules.params_tree(params_abs))
        bdiv = shape.global_batch % dp_size(mesh) == 0

        if shape.kind == "train":
            peft_cfg = peft_lib.PEFTConfig(method=peft)
            adapters_abs = jax.eval_shape(
                lambda: peft_lib.init_peft(peft_cfg, params_abs,
                                           jax.random.PRNGKey(0)))
            ocfg = optim.OptimizerConfig()
            opt_abs = jax.eval_shape(functools.partial(optim.init, ocfg),
                                     adapters_abs)
            batch_abs = train_input_specs(cfg, shape)
            n_micro = microbatches or _microbatches(shape, mesh)
            tcfg = TrainStepConfig(peft=peft_cfg, opt=ocfg,
                                   num_microbatches=n_micro)
            step = build_train_step(cfg, tcfg, mesh, batch_divisible=bdiv)
            ad_sh = named(mesh, rules.adapters_tree(adapters_abs))
            opt_sh = {"mu": ad_sh, "nu": ad_sh,
                      "step": named(mesh, jax.sharding.PartitionSpec())}
            b_sh = named(mesh, rules.batch_spec(batch_abs, shape.global_batch))
            lowered = jax.jit(
                step, in_shardings=(params_sh, ad_sh, opt_sh, b_sh),
                out_shardings=(ad_sh, opt_sh, None),
            ).lower(params_abs, adapters_abs, opt_abs, batch_abs)
            tokens_per_step = shape.global_batch * shape.seq_len
            rec["microbatches"] = n_micro
        elif shape.kind == "prefill":
            batch_abs, state_abs = prefill_input_specs(cfg, shape)
            step = rt.build_prefill(batch_divisible=bdiv)

            def prefill_cell(params, batch, state):
                return step(params, peft_lib.PrefillRequest(batch=batch),
                            state)
            st_sh = named(mesh, rules.decode_state_spec(state_abs,
                                                        shape.global_batch))
            b_sh = named(mesh, rules.batch_spec(batch_abs, shape.global_batch))
            lowered = jax.jit(prefill_cell,
                              in_shardings=(params_sh, b_sh, st_sh),
                              donate_argnums=(2,)).lower(
                params_abs, batch_abs, state_abs)
            tokens_per_step = shape.global_batch * shape.seq_len
        else:  # decode
            tokens_abs, state_abs, pos_abs = decode_input_specs(cfg, shape)
            step = rt.build_decode(batch_divisible=bdiv)

            def decode_cell(params, tokens, state, pos):
                return step(params, None, tokens, state, pos)
            st_sh = named(mesh, rules.decode_state_spec(state_abs,
                                                        shape.global_batch))
            tok_sh = named(mesh, rules.batch_spec(tokens_abs,
                                                  shape.global_batch))
            pos_sh = named(mesh, jax.sharding.PartitionSpec())
            lowered = jax.jit(decode_cell,
                              in_shardings=(params_sh, tok_sh, st_sh, pos_sh),
                              donate_argnums=(2,)).lower(
                params_abs, tokens_abs, state_abs, pos_abs)
            tokens_per_step = shape.global_batch  # one token per sequence
        t_lower = time.time() - t0

        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        from repro.analysis.hlo_cost import module_cost
        cost = compiled.cost_analysis()
        mem = _mem_dict(compiled.memory_analysis())
        hlo = compiled.as_text()
        # trip-count-aware accounting (XLA's cost_analysis counts while
        # bodies once — see analysis/hlo_cost.py); raw numbers kept alongside
        walk = module_cost(hlo)
        if save_hlo:
            os.makedirs(RESULTS_DIR, exist_ok=True)
            with open(os.path.join(
                    RESULTS_DIR, f"{arch}__{shape_name}__{mesh_kind}.hlo"),
                    "w") as f:
                f.write(hlo)
        del hlo

        n_active = rt.active_param_count()
        mf = model_flops(n_active, tokens_per_step,
                         "train" if shape.is_train else "serve")
        rl = Roofline(
            arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
            flops_per_device=walk.flops,
            bytes_per_device=walk.bytes,
            coll_bytes_per_device=walk.coll_bytes,
            model_flops=mf,
            peak_memory_per_device=mem["argument_size_in_bytes"]
            + mem["temp_size_in_bytes"] + mem["output_size_in_bytes"]
            - mem["alias_size_in_bytes"],
        )
        rec.update({
            "ok": True,
            "chips": chips,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "cost_raw": {k: cost.get(k) for k in ("flops", "bytes accessed")},
            "memory": mem,
            "collectives": {k: dict(v) for k, v in walk.coll.items()},
            "roofline": rl.row(),
            "advice": advice(rl),
            "active_params": n_active,
        })
    except Exception as e:  # record the failure; the sweep continues
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--peft", default="gsoft")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", nargs="*", default=[],
                    help="ModelConfig overrides key=value")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    overrides = parse_overrides(args.set)

    out_dir = args.out or os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "..",
                     "results", "dryrun"))
    os.makedirs(out_dir, exist_ok=True)

    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                name = f"{arch}__{shape}__{mesh_kind}"
                path = os.path.join(out_dir, name + ".json")
                print(f"=== {name} ===", flush=True)
                rec = run_cell(arch, shape, mesh_kind, peft=args.peft,
                               overrides=overrides, save_hlo=args.save_hlo,
                               microbatches=args.microbatches)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = ("SKIP " + rec.get("reason", "") if rec.get("skipped")
                          else "OK" if rec["ok"] else
                          "FAIL " + rec.get("error", ""))
                if rec.get("ok") and not rec.get("skipped"):
                    r = rec["roofline"]
                    print(f"  {status}  dominant={r['dominant']} "
                          f"compute={r['compute_s']:.4f}s "
                          f"mem={r['memory_s']:.4f}s "
                          f"coll={r['collective_s']:.4f}s "
                          f"roofline={r['roofline_frac']:.2%} "
                          f"mem/dev={r['peak_mem_gib']:.2f}GiB "
                          f"(compile {rec['compile_s']}s)", flush=True)
                else:
                    print("  " + status, flush=True)
                results.append(rec)

    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)
    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} cells OK")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
