"""Serve-time tensor-parallel checks (ISSUE 8). Runs under
XLA_FLAGS=--xla_force_host_platform_device_count=8 in a subprocess
(tests/test_distributed.py drives it): a ``ModelRuntime`` built on a tp
mesh must serve TOKEN-IDENTICAL to the single-device runtime through the
real engines — contiguous ServeEngine with a mixed-method eager bank
(tp=2 divides the smoke model's heads, tp=4 exceeds its kv heads so the
KV spec falls back to replicated), the int8-quantized runtime (QuantTensor
trees placed leaf-wise), the paged engine (KV pages head-sharded, page
table replicated), and one-device replicas (launch.serve.replica_runtimes)
with eager and store-paged banks. Prints one JSON line per check."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json
import sys

import jax
import numpy as np

from repro.config import get_smoke_config
from repro.core import peft as peft_lib
from repro.core.runtime import ModelRuntime
from repro.distrib import serve_mesh
from repro.launch.serve import make_demo_adapters, replica_runtimes
from repro.serve.engine import PagedServeEngine, ServeEngine

OUT = []


def check(name, ok, **kw):
    OUT.append({"name": name, "ok": bool(ok), **kw})


def workload(n_req, seed=0, adapters=None):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_req):
        req = {"prompt": rng.integers(1, 200, size=int(
                   rng.integers(4, 13))).tolist(),
               "max_new_tokens": int(rng.integers(2, 11))}
        if adapters:
            req["adapter"] = adapters[i % len(adapters)]
        reqs.append(req)
    return reqs


def run_engine(rt, wl, paged=False):
    if paged:
        eng = PagedServeEngine(rt, max_batch=4, max_len=32, eos_id=-1,
                               page_size=8, prefill_chunk=16)
    else:
        eng = ServeEngine(rt, max_batch=4, max_len=32, eos_id=-1)
    rids = [eng.add_request(**r) for r in wl]
    res = eng.run()
    return [res[r] for r in rids]


def main():
    cfg = get_smoke_config("qwen2-72b")
    key = jax.random.PRNGKey(0)
    rt_solo = ModelRuntime(cfg, key=key)
    bank_peft = {"g0": peft_lib.PEFTConfig(method="gsoft", block_size=8),
                 "b0": peft_lib.PEFTConfig(method="boft", block_size=8)}
    adapters = make_demo_adapters(list(bank_peft), rt_solo.params, bank_peft)
    wl = workload(8, adapters=[None, "g0", "b0"])

    ref = run_engine(rt_solo.attach(adapters, bank_peft), wl)

    for tp in (2, 4):
        rt_tp = ModelRuntime(cfg, key=key, mesh=serve_mesh(tp))
        got = run_engine(rt_tp.attach(adapters, bank_peft), wl)
        check(f"serve/tp{tp}/tokens_equal", got == ref)
        # the mesh runtime must actually shard — a silently replicated wq
        # would make every equality above vacuous
        paths = jax.tree_util.tree_flatten_with_path(rt_tp.params)[0]
        wq = next(l for p, l in paths
                  if "wq" in jax.tree_util.keystr(p))
        check(f"serve/tp{tp}/params_sharded",
              len(wq.sharding.device_set) == tp)

    # int8: QuantTensor q/scale leaves placed per-leaf on the mesh
    ref_q = run_engine(rt_solo.attach(adapters, bank_peft).quantized(), wl)
    rt_tp = ModelRuntime(cfg, key=key, mesh=serve_mesh(2))
    got_q = run_engine(rt_tp.attach(adapters, bank_peft).quantized(), wl)
    check("serve/tp2/int8_tokens_equal", got_q == ref_q)

    # paged engine: KV pages sharded over the head axis, table replicated
    wl_pg = workload(8, seed=1)
    ref_pg = run_engine(rt_solo, wl_pg, paged=True)
    got_pg = run_engine(ModelRuntime(cfg, key=key, mesh=serve_mesh(2)),
                        wl_pg, paged=True)
    check("serve/tp2/paged_tokens_equal", got_pg == ref_pg)

    # one replica per device: params, KV pages and bank stacks (eager, and
    # store-paged so that page-ins run) stay on the replica's own device
    wl_rep = workload(6, seed=2, adapters=[None, "g0", "b0"])
    ref_rep = run_engine(rt_solo.attach(adapters, bank_peft), wl_rep,
                         paged=True)
    for label, budget in (("eager", None), ("paged", 2)):
        reps = replica_runtimes(
            rt_solo.attach(adapters, bank_peft, hbm_budget=budget), 4,
            hbm_budget=budget)
        got = [run_engine(r, wl_rep, paged=True) for r in reps]
        homes = [sorted({d.id for leaf in jax.tree.leaves(
                     (r.params, r.bank.tree)) for d in leaf.sharding.device_set})
                 for r in reps]
        check(f"serve/replicas/{label}/one_device_each",
              homes == [[i] for i in range(4)], homes=homes)
        check(f"serve/replicas/{label}/tokens_equal",
              all(g == ref_rep for g in got))

    for rec in OUT:
        print("CHECK " + json.dumps(rec))
    bad = [r for r in OUT if not r["ok"]]
    print(f"RESULT {len(OUT) - len(bad)}/{len(OUT)} ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
