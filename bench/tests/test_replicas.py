"""A serving cell that asks for several chips builds one replica per chip
behind the cluster, each with its own bank, from the cell's ``chips``
alone: a four-chip cell is a new entry, not an edit. Run on two virtual
CPU devices in a process of its own (the device count is fixed before
JAX starts)."""
import json
import os
import subprocess
import sys

from harness import cells

SCRIPT = r"""
import dataclasses, json, sys, time
sys.path[:0] = sys.argv[1:4]
import jax
from harness import runner, serve
from small import DEVICE, PEAKS, model_config, small_cell

runner.device_lib.peaks = lambda kind: PEAKS
runner.limits = lambda _: {"limits": {"mean_gap": {"limit": 1e9},
                                      "bad_outputs": {"limit": 0},
                                      "unchecked": {"limit": 0}}}
cell = dataclasses.replace(small_cell("qwen2-72b", "chat"), chips=2)
cfg = model_config(cell)
cluster, rts, *_ = serve.build(cfg, cell.config, cell.traffic, 1, 2)
devices = [str(jax.tree.leaves(rt.bank.tree)[0].devices()) for rt in rts]
res = runner.run_serve(cell, 5, 2.0, False, time.perf_counter(),
                       dict(DEVICE, count=2), cfg=cfg)
print(json.dumps({"engines": len(cluster.engines), "devices": devices,
                  "banks": len({id(rt.bank) for rt in rts}),
                  "correct": res["correct"], "failed": res["failed"]}))
"""


def test_one_replica_per_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    tests = str(cells.BENCH / "tests")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(cells.BENCH), tests,
                        str(cells.CHECKOUT / "src")],
                       cwd=tests, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["engines"] == 2 and out["banks"] == 2
    assert len(set(out["devices"])) == 2, out["devices"]
    assert out["correct"] and out["failed"] == 0, out
