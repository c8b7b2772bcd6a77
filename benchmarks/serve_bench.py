"""Serving engine benchmark: static vs continuous batching on a
mixed-length workload (ragged prompts, ragged output budgets — the traffic
shape continuous batching exists for).

Reports throughput (tok/s), p50/p95 per-request latency, and scheduler
utilization = generated tokens / (decode_steps * max_batch), the
deterministic measure of how much decode work the scheduler wastes on
finished-or-empty rows (lockstep static batching burns steps on the
max(max_new) barrier; slot-based continuous batching refills them).

The ``cluster`` lane (ISSUE 8) is the 1->N replica scaling curve: Poisson
mixed-length multi-tenant traffic whose adapter working set thrashes ONE
replica's HBM budget but partitions cleanly across two under the
``EngineCluster`` affinity router. Tokens are asserted identical across
replica counts, and the 2-replica speedup / affinity hit rate ride the
summary so the scale-out trajectory is tracked PR-over-PR.

``REPRO_BENCH_TINY=1`` shrinks the workload for the CI smoke lane and
writes a ``BENCH_serve.json`` summary at the repo root (uploaded as a CI
artifact so the serving-perf trajectory is tracked PR-over-PR).
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from repro.config import get_smoke_config
from repro.core.runtime import ModelRuntime
from repro.serve.engine import ServeEngine, StaticServeEngine

from .common import emit, mixed_workload, run_engine_timed, write_summary

TINY = bool(os.environ.get("REPRO_BENCH_TINY"))


def run():
    cfg = get_smoke_config("qwen2-72b")
    n_req = 16 if TINY else 48
    max_batch = 4
    prompt_hi = 12 if TINY else 32
    # wide output-budget spread: the lockstep max(max_new) barrier is what
    # static batching pays for and slot refill is what continuous wins on
    max_new_hi = 32 if TINY else 48
    max_len = prompt_hi + max_new_hi + 8
    rt = ModelRuntime(cfg, key=jax.random.PRNGKey(0))
    workload = mixed_workload(n_req, prompt_hi, max_new_hi, seed=0)
    # warmup = the same workload, so every shape both schedulers will see
    # (static: per-batch pad shapes; continuous: prefill buckets) is
    # compiled before the timed pass — the comparison measures scheduling,
    # not retracing
    warmup = workload

    res = {}
    for name, make in (
        ("static", lambda: StaticServeEngine(
            rt, max_batch=max_batch, max_len=max_len, eos_id=-1)),
        ("continuous", lambda: ServeEngine(
            rt, max_batch=max_batch, max_len=max_len, eos_id=-1)),
    ):
        r = res[name] = run_engine_timed(make, warmup, workload)
        emit(f"serve/{name}_mixed",
             1e6 * r["dt"] / max(r["tokens"], 1),
             f"tok/s={r['tok_s']:.1f};util={r['util']:.2f};"
             f"p50_ms={r['p50_ms']:.0f};p95_ms={r['p95_ms']:.0f};"
             f"decode_steps={r['decode_steps']}")

    speedup = res["continuous"]["tok_s"] / max(res["static"]["tok_s"], 1e-9)
    emit("serve/continuous_speedup", 0.0,
         f"x{speedup:.2f};util {res['static']['util']:.2f}->"
         f"{res['continuous']['util']:.2f}")

    # ---- store-paged lane: same traffic shape, per-request adapters paged
    # under an HBM budget smaller than the tenant count (residency counters
    # ride the CSV so eviction/hit-rate/page-in trends are tracked here too)
    from repro.store import AdapterStore
    from repro.core import peft as peft_lib
    from repro.launch.serve import make_demo_adapters
    n_ad = 6 if TINY else 12
    meths = ("gsoft", "boft", "householder")
    bank_peft = {f"a{i}": peft_lib.PEFTConfig(method=meths[i % 3],
                                              block_size=8)
                 for i in range(n_ad)}
    adapters = make_demo_adapters(list(bank_peft), rt.params, bank_peft)
    store = AdapterStore.from_adapters(adapters, bank_peft)
    rt_store = rt.attach(store, hbm_budget=max(n_ad // 2, 3))
    wl_store = mixed_workload(n_req, prompt_hi, max_new_hi, seed=0,
                              adapters=list(bank_peft))
    r = res["store_paged"] = run_engine_timed(
        lambda: ServeEngine(rt_store, max_batch=max_batch, max_len=max_len,
                            eos_id=-1), wl_store, wl_store)
    st = rt_store.bank.stats()
    emit("serve/store_paged_mixed",
         1e6 * r["dt"] / max(r["tokens"], 1),
         f"tok/s={r['tok_s']:.1f};hit_rate={st['hit_rate']:.2f};"
         f"evictions={st['evictions']};"
         f"page_in_p95_ms={st['page_in_ms_p95']:.1f};"
         f"max_resident={st['max_resident']}/{st['capacity']};"
         f"compaction={st['compaction_ratio']:.2f}x")

    # ---- store-resident lane: same adapter traffic with a budget that fits
    # every tenant. Once resident, the paged bank must serve within ~10% of
    # an eagerly-attached bank — i.e. the decode hot loop does no per-step
    # host work (adapter contexts are cached on the bank version).
    rt_eager = rt.attach(adapters, bank_peft)
    r = res["store_eager"] = run_engine_timed(
        lambda: ServeEngine(rt_eager, max_batch=max_batch, max_len=max_len,
                            eos_id=-1), wl_store, wl_store)
    emit("serve/store_eager_mixed", 1e6 * r["dt"] / max(r["tokens"], 1),
         f"tok/s={r['tok_s']:.1f}")
    rt_res = rt.attach(store, hbm_budget=n_ad)
    r = res["store_resident"] = run_engine_timed(
        lambda: ServeEngine(rt_res, max_batch=max_batch, max_len=max_len,
                            eos_id=-1), wl_store, wl_store)
    resident_ratio = r["tok_s"] / max(res["store_eager"]["tok_s"], 1e-9)
    st = rt_res.bank.stats()
    emit("serve/store_resident_mixed", 1e6 * r["dt"] / max(r["tokens"], 1),
         f"tok/s={r['tok_s']:.1f};vs_eager=x{resident_ratio:.2f};"
         f"evictions={st['evictions']};hit_rate={st['hit_rate']:.2f}")

    cluster = _lane_cluster(rt)

    if TINY:
        summary = {"backend": jax.default_backend(), "arch": cfg.name,
                   "continuous_speedup": speedup,
                   "store_resident_vs_eager": resident_ratio}
        for name, r in res.items():
            for key, val in r.items():
                summary[f"{name}_{key}"] = val
        summary.update(cluster)
        write_summary("serve", summary)


def _poisson_arrivals(n: int, rate: float, seed: int) -> np.ndarray:
    """Cumulative Poisson arrival times in DECODE-TICK units (exponential
    gaps at ``rate`` requests/tick) — deterministic, no wall-clock sleeps,
    so the timed pass measures serving, not the arrival process."""
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def _drive_poisson(eng, workload, arrivals):
    """Feed ``workload`` as it arrives on the tick clock, stepping the
    engine/cluster between waves; returns (wall_s, {rid: tokens})."""
    nxt, tick, busy = 0, 0, True
    out = {}
    t0 = time.perf_counter()
    while nxt < len(workload) or busy:
        while nxt < len(workload) and arrivals[nxt] <= tick:
            out[eng.add_request(**workload[nxt])] = None
            nxt += 1
        busy = eng.step()
        tick += 1
    dt = time.perf_counter() - t0
    for r in eng.drain_finished():
        out[r.rid] = r.output
    return dt, out


def _lane_cluster(rt):
    """1 -> N replica scaling (ISSUE 8): the tenant working set (``n_ad``
    adapters) is twice ONE replica's paged-bank budget, so a single engine
    pages factors on nearly every admission while the 2-replica cluster's
    affinity router partitions tenants into two working sets that each
    fit — page-ins happen once per tenant per home. Same per-replica
    resources on both sides; greedy tokens must agree exactly."""
    from repro.core import peft as peft_lib
    from repro.distrib import EngineCluster
    from repro.launch.serve import make_demo_adapters
    from repro.store import AdapterStore

    n_ad, max_batch = 8, 4
    n_req = 32 if TINY else 64
    budget = n_ad // 2                     # one replica holds half the tenants
    # method split uncorrelated with tenant index parity: the affinity
    # router alternates first sightings across replicas, so an i%2 method
    # assignment would hand each replica ONE method's tenants and starve
    # the per-method capacity split
    bank_peft = {f"t{i}": peft_lib.PEFTConfig(
        method="gsoft" if i < n_ad // 2 else "boft", block_size=8)
        for i in range(n_ad)}
    adapters = make_demo_adapters(list(bank_peft), rt.params, bank_peft)
    store = AdapterStore.from_adapters(adapters, bank_peft)
    wl = mixed_workload(n_req, 12, 16, seed=3, adapters=list(bank_peft))
    arrivals = _poisson_arrivals(n_req, rate=2.0, seed=3)

    rows, outputs = [], {}
    for n in (1, 2):
        cl = EngineCluster([ServeEngine(rt.attach(store, hbm_budget=budget),
                                        max_batch=max_batch, max_len=40,
                                        eos_id=-1) for _ in range(n)])
        _drive_poisson(cl, wl, arrivals)   # warmup: compile + page + homes
        toks0 = cl.stats["tokens_generated"]
        dt, out = _drive_poisson(cl, wl, arrivals)
        toks = cl.stats["tokens_generated"] - toks0
        tok_s = toks / max(dt, 1e-9)
        ahr = cl.affinity_hit_rate()
        outputs[n] = [out[k] for k in sorted(out)]
        rows.append({"replicas": n, "tok_s": tok_s, "tokens": toks,
                     "affinity_hit_rate": ahr})
        emit(f"serve/cluster_{n}replica", 1e6 * dt / max(toks, 1),
             f"tok/s={tok_s:.1f};affinity_hit_rate={ahr:.2f};"
             f"rebalanced={cl.routing['rebalanced']}")
    assert outputs[1] == outputs[2], \
        "cluster tokens diverged from single-replica tokens"
    speedup = rows[1]["tok_s"] / max(rows[0]["tok_s"], 1e-9)
    ahr = rows[1]["affinity_hit_rate"]
    assert speedup >= 1.5, f"2-replica speedup x{speedup:.2f} < x1.5"
    assert ahr >= 0.9, f"affinity hit rate {ahr:.2f} < 0.9"
    emit("serve/cluster_scaling_speedup", 0.0,
         f"x{speedup:.2f};affinity_hit_rate={ahr:.2f};tokens_equal=1")
    return {"cluster_scaling": rows, "cluster_speedup": speedup,
            "cluster_affinity_hit_rate": ahr}


if __name__ == "__main__":
    run()
