"""Distributed serving (ISSUE 8): in-process ``EngineCluster`` behavior
(routing, affinity, rebalance, aggregated stats, token parity with a
single engine) plus the serve-TP subprocess runner
(tests/serve_distributed_runner.py — it needs its own XLA_FLAGS device
count before jax initializes, so it cannot run in this process)."""
import json
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")
np = pytest.importorskip("numpy")

from repro.config import get_smoke_config               # noqa: E402
from repro.core import peft as peft_lib                 # noqa: E402
from repro.core.runtime import ModelRuntime             # noqa: E402
from repro.distrib import EngineCluster, format_cluster_report  # noqa: E402
from repro.launch.serve import make_demo_adapters       # noqa: E402
from repro.serve.engine import ServeEngine              # noqa: E402
from repro.serve.kv import merge_pool_stats             # noqa: E402
from repro.store import AdapterStore                    # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def rt():
    return ModelRuntime(get_smoke_config("qwen2-72b"),
                        key=jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tenant_store(rt):
    """4 tenants, methods split so an affinity partition of the tenants
    (which alternates replicas) mixes methods within each replica."""
    bank_peft = {f"t{i}": peft_lib.PEFTConfig(
        method="gsoft" if i < 2 else "boft", block_size=8)
        for i in range(4)}
    adapters = make_demo_adapters(list(bank_peft), rt.params, bank_peft)
    return AdapterStore.from_adapters(adapters, bank_peft), bank_peft


def _cluster(rt, store, n, budget=2, max_batch=2, **kw):
    return EngineCluster(
        [ServeEngine(rt.attach(store, hbm_budget=budget),
                     max_batch=max_batch, max_len=32, eos_id=-1)
         for _ in range(n)], **kw)


def _workload(names, n_req, seed=0):
    rng = np.random.default_rng(seed)
    return [{"prompt": rng.integers(1, 200, size=int(
                 rng.integers(4, 11))).tolist(),
             "max_new_tokens": int(rng.integers(2, 7)),
             "adapter": names[i % len(names)]}
            for i in range(n_req)]


def test_cluster_affinity_keeps_tenants_warm(rt, tenant_store):
    """Repeat traffic lands on the home replica whose bank already holds
    the tenant — zero page-ins after the first round."""
    store, bank_peft = tenant_store
    cl = _cluster(rt, store, 2)
    wl = _workload(list(bank_peft), 8)
    for r in wl:
        cl.add_request(**r)
    cl.run()
    homes = dict(cl._affinity)
    assert sorted(homes.values()) == [0, 0, 1, 1]   # tenants partitioned
    page_ins = [e.rt.bank.counters["misses"] for e in cl.engines]
    for r in wl:
        cl.add_request(**r)
    cl.run()
    assert dict(cl._affinity) == homes
    assert [e.rt.bank.counters["misses"] for e in cl.engines] == page_ins
    assert cl.affinity_hit_rate() == 1.0
    assert cl.routing["fresh"] == 4
    assert cl.routing["affinity_hits"] == 12


def test_cluster_tokens_match_single_engine(rt, tenant_store):
    """Routing is a scheduling decision, not a math one: per-request greedy
    tokens agree exactly with one engine serving the same arrivals."""
    store, bank_peft = tenant_store
    wl = _workload(list(bank_peft), 10, seed=1)
    solo = ServeEngine(rt.attach(store, hbm_budget=4), max_batch=2,
                       max_len=32, eos_id=-1)
    rids = [solo.add_request(**r) for r in wl]
    ref = solo.run()
    cl = _cluster(rt, store, 2)
    crids = [cl.add_request(**r) for r in wl]
    out = cl.run()
    assert [out[c] for c in crids] == [ref[r] for r in rids]


def test_cluster_spill_and_rebalance(rt, tenant_store):
    """A flooded home spills to the least-loaded sibling (home stays
    sticky), and explicit rebalance moves only queued backlog."""
    store, _ = tenant_store
    cl = _cluster(rt, store, 2, auto_rebalance=False)
    crids = [cl.add_request([3, 4, 5], max_new_tokens=3, adapter="t0")
             for _ in range(10)]
    assert cl.routing["affinity_spills"] > 0
    assert cl._affinity["t0"] == 0                   # sticky through spills
    assert cl.engines[1].load > 0                    # spills actually landed
    moved = cl.rebalance()
    assert moved >= 0
    out = cl.run()
    assert sorted(out) == sorted(crids)
    assert cl.stats["requests"] == 10


def test_cluster_stats_and_report(rt, tenant_store):
    store, bank_peft = tenant_store
    cl = _cluster(rt, store, 2)
    for r in _workload(list(bank_peft), 6, seed=2):
        cl.add_request(**r)
    cl.run()
    cs = cl.cluster_stats()
    assert cs["replicas"] == 2
    assert cs["aggregate"]["requests"] == 6
    assert cs["aggregate"]["tokens_generated"] == cl.stats["tokens_generated"]
    assert len(cs["per_replica"]) == 2
    assert sum(row["requests"] for row in cs["per_replica"]) == 6
    assert cs["routing"]["affinity_hit_rate"] == 1.0
    rep = format_cluster_report(cs)
    assert "2 replica(s)" in rep and "replica[0]" in rep and "bank:" in rep


def test_cluster_n1_is_the_degenerate_case(rt, tenant_store):
    """The launcher wraps a single engine in the same cluster surface —
    stats and report must work without siblings."""
    store, bank_peft = tenant_store
    cl = _cluster(rt, store, 1, budget=4)
    for r in _workload(list(bank_peft), 4, seed=3):
        cl.add_request(**r)
    cl.run()
    cs = cl.cluster_stats()
    assert cs["replicas"] == 1
    assert cs["aggregate"]["requests"] == 4
    assert cl.affinity_hit_rate() == 1.0             # nothing to spill to
    format_cluster_report(cs)


def test_merge_pool_stats_contract():
    a = {"page_size": 8, "alloc": 3, "in_use": 2}
    b = {"page_size": 8, "alloc": 5, "in_use": 1}
    m = merge_pool_stats([a, b])
    assert m == {"page_size": 8, "alloc": 8, "in_use": 3}
    with pytest.raises(ValueError):
        merge_pool_stats([])
    with pytest.raises(ValueError):
        merge_pool_stats([a, {"page_size": 16, "alloc": 1, "in_use": 0}])


def _drive_runner(name, min_checks):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", name)],
        env=env, capture_output=True, text=True, timeout=1500)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, f"{name} failed"
    checks = [json.loads(l[6:]) for l in proc.stdout.splitlines()
              if l.startswith("CHECK ")]
    assert len(checks) >= min_checks
    assert all(c["ok"] for c in checks), [c for c in checks if not c["ok"]]


@pytest.mark.distributed
def test_serve_tp_stack():
    """Sharded serving == single-device serving, token for token (bf16
    eager mixed-method bank, int8 quantized, paged KV), and one replica per
    device (eager and store-paged banks) on its own device, on 8 fake CPU
    devices."""
    _drive_runner("serve_distributed_runner.py", min_checks=10)
