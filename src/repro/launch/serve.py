"""Serving launcher: continuous-batching (default) or static engine over a
(smoke or full) config, with streaming Poisson arrivals and a per-request
adapter bank.

    # continuous batching, mixed-length synthetic traffic
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
        --requests 16 --prompt-len 12 --max-new 8 --mixed-lengths

    # streaming arrivals at 4 req/s
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
        --requests 16 --arrival-rate 4

    # multi-adapter serving from saved checkpoints (ModelRuntime.attach);
    # requests round-robin over the loaded adapters
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
        --adapters alice=/ckpts/alice bob=/ckpts/bob

    # fabricate a demo bank, save it, and round-trip through the loader
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
        --demo-adapters 3 --save-adapters /tmp/bank

    # thousand-tenant mode: serve a whole adapter checkpoint as a DISK-
    # backed store, paged into HBM under a fixed budget (LRU eviction)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
        --store-dir /ckpts/tenants --hbm-adapter-budget 64

    # image lane: batched stateless serving of the 1-Lipschitz convnet
    # with per-request conv adapters (ImageServeEngine; same bank/store/
    # quantize/replica flags as the token lanes)
    PYTHONPATH=src python -m repro.launch.serve --arch lipconvnet-15 \
        --smoke --family image --requests 16 --demo-adapters 3

    # observability: per-request trace spans (TTFT/TPOT/stall attribution)
    # exported for chrome://tracing, periodic SLO report, JSON tick log
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
        --requests 16 --arrival-rate 8 --trace --trace-out /tmp/trace.json \
        --report-interval 1 --log-json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.config import get_config, get_smoke_config, parse_overrides
from repro.core import peft as peft_lib
from repro.core.runtime import ModelRuntime
from repro.distrib import EngineCluster, format_cluster_report, serve_mesh
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.obs import SLOMonitor, TraceRecorder, compile_counts
from repro.serve.engine import (PagedServeEngine, ServeEngine,
                                StaticServeEngine, latency_percentiles)
from repro.serve.image import ImageServeEngine


def make_demo_adapters(names, params, peft_cfg, seed=1, scale=0.1):
    """Random (non-identity) adapters, one per name; an int n means names
    a0..a{n-1}. ``peft_cfg`` is a single PEFTConfig or a {name: PEFTConfig}
    mapping (mixed-method demo banks). Stands in for real fine-tunes in
    demos/benchmarks."""
    if isinstance(names, int):
        names = [f"a{i}" for i in range(names)]
    out = {}
    for i, name in enumerate(names):
        cfg = peft_cfg[name] if isinstance(peft_cfg, dict) else peft_cfg
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        ad = peft_lib.init_peft(cfg, params, key)
        out[name] = jax.tree.map(
            lambda a, k=key: a + scale * jax.random.normal(
                jax.random.fold_in(k, 7), a.shape), ad)
    return out


def _replica(rt: ModelRuntime, mesh, hbm_budget) -> ModelRuntime:
    """A copy of ``rt`` committed to ``mesh``: same params (re-placed),
    quantization and adapters, with its own bank."""
    from repro.store import PagedAdapterBank
    r = ModelRuntime(rt.cfg, rt.params, mesh=mesh)
    r.quant_cfg = rt.quant_cfg
    if isinstance(rt.bank, PagedAdapterBank):
        return r.attach(rt.bank.store, hbm_budget=hbm_budget)
    if rt.bank is not None:         # attach re-places a shallow bank copy
        return r.attach(dataclasses.replace(rt.bank))
    return r


def replica_runtimes(rt: ModelRuntime, n: int, *, hbm_budget=None):
    """Runtimes for N engine replicas of ``rt``; replica 0 is ``rt``.

    Without a serve mesh, replica i is committed to device i mod the
    visible count through a 1x1 serve mesh, so its params, KV pages and
    bank stacks (eager or store-paged) live on its own chip. Replicas that land on one
    device (or share a serve mesh) share one runtime: engines keep their
    own KV state, and weights and compiled closures exist once. A store-
    paged bank forces a fresh runtime per replica: paging state
    (residency, pins, LRU order) must be per replica for the cluster's
    adapter-affinity routing to mean anything."""
    from repro.store import PagedAdapterBank
    paged = isinstance(rt.bank, PagedAdapterBank)
    devices = jax.devices()
    out, on_device = [rt], {devices[0]: rt}
    for i in range(1, n):
        if rt.mesh is not None:
            out.append(_replica(rt, rt.mesh, hbm_budget) if paged else rt)
            continue
        dev = devices[i % len(devices)]
        if paged or dev not in on_device:
            on_device[dev] = _replica(rt, serve_mesh(1, devices=[dev]),
                                      hbm_budget)
        out.append(on_device[dev])
    return out


def drive_streaming(eng, requests, arrivals, tick_hook=None):
    """Admit requests as they 'arrive' (Poisson sim) while stepping the
    continuous scheduler; returns results once traffic drains. ``eng`` is
    anything engine-shaped — a single engine or an ``EngineCluster``.
    ``tick_hook`` (optional) runs after every scheduler tick — the
    launcher's periodic SLO report / --log-json emitter. An SLO-breached
    cluster (``eng.accepting`` False) HOLDS arrivals until the monitor
    clears — admission backpressure, not drops. Each request carries its
    arrival time, so TTFT and latency count any hold or late admission."""
    t0 = time.perf_counter()
    i = 0
    while i < len(requests) or not eng.idle:
        now = time.perf_counter() - t0
        while (i < len(requests) and arrivals[i] <= now
               and getattr(eng, "accepting", True)):
            eng.add_request(**requests[i], t_arrival=t0 + arrivals[i])
            i += 1
        if eng.idle:                     # nothing in flight: wait for traffic
            time.sleep(min(0.005, max(arrivals[i] - now, 0.0)))
            continue
        eng.step()
        if tick_hook is not None:
            tick_hook()
    eng.add_wall(time.perf_counter() - t0)
    return {r.rid: r.output for r in eng.finished}


def make_tick_observer(eng, slo, interval, log_json):
    """Per-tick callback: every ``interval`` seconds (every tick when 0)
    emit either the human SLO report or one ``--log-json`` record — the
    machine-readable mirror of the same numbers."""
    state = {"t0": time.perf_counter(), "last": time.perf_counter()}

    def observe():
        now = time.perf_counter()
        if interval > 0 and now - state["last"] < interval:
            return
        state["last"] = now
        if log_json:
            rec = {"event": "tick", "t_s": round(now - state["t0"], 6),
                   "queue_depth": eng.queue_depth,
                   "active": eng.num_active,
                   "requests": eng.stats["requests"],
                   "tokens_generated": eng.stats["tokens_generated"],
                   "decode_steps": eng.stats["decode_steps"],
                   "prefills": eng.stats["prefills"],
                   "admission_stalls": eng.stats["admission_stalls"]}
            if slo is not None:
                rec["slo"] = slo.report()
            print(json.dumps(rec))
        elif slo is not None:
            print(SLOMonitor.format_report(slo.report()))

    return observe


def describe(eng, results, engine_name, dt):
    toks = eng.stats["tokens_generated"]
    lat = latency_percentiles(eng.finished)
    print(f"[{engine_name}] served {len(results)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks / max(dt, 1e-9):.1f} tok/s, "
          f"{eng.stats['decode_steps']} decode steps, "
          f"{eng.stats['prefills']} prefills)")
    print(f"latency p50={lat[50] * 1e3:.0f}ms p95={lat[95] * 1e3:.0f}ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--family", default=None,
                    help="assert the arch's registered family (lane "
                         "selector in scripts: --family image routes "
                         "through the batched stateless ImageServeEngine)")
    ap.add_argument("--engine", choices=("continuous", "static", "paged"),
                    default="continuous",
                    help="'paged': fixed-size KV pages + per-slot page "
                         "tables, chunked prefill, shared-prefix caching")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--mixed-lengths", action="store_true",
                    help="prompt lens U[4, prompt_len], budgets U[2, max_new]"
                         " — the ragged workload continuous batching wins on")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrivals (req/s); 0 = all queued up front")
    ap.add_argument("--mesh", default=None,
                    help="'data,model' mesh shape for tensor-parallel "
                         "serving (params/KV/bank commit per "
                         "sharding.specs)")
    ap.add_argument("--tp", type=int, default=0,
                    help="shorthand for --mesh 1,N: split the model over N "
                         "devices at serve time")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run N engine replicas behind an EngineCluster "
                         "with adapter-affinity routing (continuous/paged "
                         "engines)")
    ap.add_argument("--adapters", nargs="*", default=[],
                    metavar="NAME=CKPT_DIR",
                    help="load named adapters into a per-request bank "
                         "(continuous engine only)")
    ap.add_argument("--store-dir", default=None,
                    help="serve an adapter checkpoint dir as a DISK-backed "
                         "AdapterStore: only the index loads up front; "
                         "adapters page into HBM on admission")
    ap.add_argument("--hbm-adapter-budget", type=int, default=0,
                    help="max adapters resident in HBM at once (slot-"
                         "compacted, LRU-paged); 0 = everything resident")
    ap.add_argument("--demo-adapters", type=int, default=0,
                    help="fabricate N random adapters as a demo bank")
    ap.add_argument("--demo-methods", default="gsoft",
                    help="comma-list of registered methods assigned round-"
                         "robin to the demo adapters (mixed-method bank), "
                         "e.g. gsoft,boft,householder")
    ap.add_argument("--save-adapters", default=None,
                    help="save the (demo) bank to this checkpoint dir and "
                         "reload it through the round-trip path")
    ap.add_argument("--peft-demo", action="store_true",
                    help="attach + merge one GSOFT adapter into the weights "
                         "before serving (paper §6.1: zero overhead)")
    ap.add_argument("--quantize", choices=("none", "int8", "fp8"),
                    default="none",
                    help="serve with quantized base weights (per-channel "
                         "int8 / fp8 stub); GS adapter rotations stay bf16")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page size in tokens (paged engine)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens fed per scheduler tick (paged "
                         "engine): decode latency is bounded by one chunk, "
                         "not one prompt")
    ap.add_argument("--hbm-kv-budget", type=int, default=0,
                    help="KV pool HBM budget in BYTES (paged engine); the "
                         "page count is static — exhaustion stalls "
                         "admission. 0 = stall-free worst-case pool")
    ap.add_argument("--trace", action="store_true",
                    help="record per-request lifecycle spans (submit/"
                         "stall/prefill/tokens/finish) with TTFT/TPOT; "
                         "all lanes including --family image")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export finished traces: .jsonl = one event per "
                         "line, anything else = Chrome trace_event JSON "
                         "(chrome://tracing / Perfetto); implies --trace")
    ap.add_argument("--report-interval", type=float, default=0.0,
                    help="print the sliding-window SLO report (p50/p95/p99 "
                         "TTFT+TPOT, tok/s, stall rates) every N seconds "
                         "while serving; implies --trace")
    ap.add_argument("--log-json", action="store_true",
                    help="emit structured per-tick JSON records to stdout "
                         "— the machine-readable mirror of the human "
                         "report (throttled by --report-interval)")
    ap.add_argument("--set", nargs="*", default=[])
    args = ap.parse_args()
    compile_cache.enable()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    cfg = cfg.with_overrides(**parse_overrides(args.set))
    if args.family and not registry.is_family(cfg, args.family):
        raise SystemExit(f"--family {args.family} but arch {args.arch!r} "
                         f"registers family {cfg.family!r}")
    stateless = registry.get(cfg.family).stateless
    if stateless and args.engine != "continuous":
        raise SystemExit(f"family {cfg.family!r} is stateless (no KV) — "
                         "it serves through the batched image engine "
                         "(--engine continuous, the default)")
    mesh = None
    if args.tp:
        if args.mesh:
            raise SystemExit("--tp is shorthand for --mesh 1,N — pass one "
                             "or the other")
        mesh = serve_mesh(args.tp)
    elif args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh(d, m)
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    if args.replicas > 1 and args.engine == "static":
        raise SystemExit("--replicas needs a steppable engine "
                         "(continuous/paged) — the static engine drains "
                         "one batch at a time")

    base_rt = ModelRuntime(cfg, key=jax.random.PRNGKey(0), mesh=mesh)
    rt = base_rt
    max_len = cfg.frontend_tokens + args.prompt_len + args.max_new + 8

    # ---- adapter bank / store ----------------------------------------------
    from repro.store import AdapterStore, load_adapter_checkpoints
    budget = args.hbm_adapter_budget or None
    adapter_names = []
    if sum(map(bool, (args.adapters, args.demo_adapters,
                      args.store_dir))) > 1:
        raise SystemExit("--adapters / --demo-adapters / --store-dir are "
                         "exclusive: load a saved bank, fabricate one, OR "
                         "serve a checkpoint dir as a paged store")
    if args.save_adapters and not (args.adapters or args.demo_adapters):
        raise SystemExit("--save-adapters needs a bank to save: pass "
                         "--demo-adapters N or --adapters name=dir")
    if args.peft_demo and (args.adapters or args.demo_adapters or
                           args.store_dir):
        raise SystemExit("--peft-demo merges an adapter INTO the weights; "
                         "combining it with a per-request bank would rotate "
                         "already-rotated activations — pick one")
    if args.store_dir:
        store = AdapterStore.open(args.store_dir)
        rt = rt.attach(store, hbm_budget=budget)
        adapter_names = list(store.names)
        print(f"adapter store: {len(store)} adapters on disk/host, "
              f"HBM capacity {rt.bank.capacity} "
              f"(per-method {rt.bank.caps})")
    elif args.adapters or args.demo_adapters:
        if args.demo_adapters:
            # mixed-method demo bank: methods round-robin over the names
            meths = [m.strip() for m in args.demo_methods.split(",")
                     if m.strip()]
            if not meths:
                raise SystemExit("--demo-methods needs at least one "
                                 "registered method (e.g. "
                                 "gsoft,boft,householder)")
            names = [f"a{i}" for i in range(args.demo_adapters)]
            bank_peft = {name: peft_lib.PEFTConfig(
                             method=meths[i % len(meths)], block_size=8,
                             use_pallas=cfg.use_pallas)
                         for i, name in enumerate(names)}
            adapters_by_name = make_demo_adapters(names, rt.params,
                                                  bank_peft)
        else:
            adapters_by_name, bank_peft = load_adapter_checkpoints(
                args.adapters)
        if args.save_adapters:
            AdapterStore.from_adapters(adapters_by_name,
                                       bank_peft).save(args.save_adapters)
            adapters_by_name, bank_peft = load_adapter_checkpoints(
                [args.save_adapters])
            print(f"round-tripped {list(adapters_by_name)} through "
                  f"{args.save_adapters}")
        rt = rt.attach(adapters_by_name, bank_peft, hbm_budget=budget)
        adapter_names = list(adapters_by_name)
        print(f"adapter bank: {rt.bank.num_slots} slots "
              f"{list(rt.bank.names)}, methods {list(rt.bank.bank_methods)}")

    # ---- merged single-adapter demo (static story) -------------------------
    if args.peft_demo:
        peft_cfg = peft_lib.PEFTConfig(method="gsoft", block_size=8)
        adapters = peft_lib.init_peft(peft_cfg, rt.params,
                                      jax.random.PRNGKey(1))
        rt = ModelRuntime(cfg, rt.params, mesh=mesh, adapters=adapters,
                          peft_cfg=peft_cfg)

    # ---- weight quantization (after any merge/bank: rotations stay bf16) ---
    if args.quantize != "none":
        from repro.quant import tree_bytes
        before = tree_bytes(rt.params)
        rt = rt.quantized(args.quantize)
        after = tree_bytes(rt.params)
        print(f"quantized base weights ({args.quantize}): params "
              f"{before / 1e6:.2f} MB -> {after / 1e6:.2f} MB "
              f"({before / max(after, 1):.2f}x smaller)")

    # ---- observability: one tracer + SLO monitor across every lane ---------
    want_trace = (args.trace or args.trace_out is not None
                  or args.report_interval > 0)
    slo = SLOMonitor(window=256) if want_trace else None
    tracer = TraceRecorder(slo=slo) if want_trace else None

    if args.engine == "static":
        if rt.banked:
            raise SystemExit("--adapters needs --engine continuous "
                             "(static serving merges ONE adapter offline)")
        eng = StaticServeEngine(rt, max_batch=args.max_batch,
                                max_len=max_len, tracer=tracer)
    elif stateless:
        engines = [ImageServeEngine(r, max_batch=args.max_batch,
                                    tracer=tracer)
                   for r in replica_runtimes(rt, args.replicas,
                                             hbm_budget=budget)]
        eng = EngineCluster(engines, slo=slo)
    else:
        rts = replica_runtimes(rt, args.replicas, hbm_budget=budget)
        if args.engine == "paged":
            engines = [PagedServeEngine(r, max_batch=args.max_batch,
                                        max_len=max_len,
                                        page_size=args.page_size,
                                        prefill_chunk=args.prefill_chunk,
                                        hbm_kv_budget=args.hbm_kv_budget
                                        or None, tracer=tracer)
                       for r in rts]
        else:
            engines = [ServeEngine(r, max_batch=args.max_batch,
                                   max_len=max_len, tracer=tracer)
                       for r in rts]
        # N=1 rides the same cluster path: the launcher report below IS
        # cluster_stats(), single-replica being its degenerate case
        eng = EngineCluster(engines, slo=slo)

    # ---- synthetic traffic -------------------------------------------------
    rng = np.random.default_rng(0)
    names = adapter_names if rt.banked and adapter_names else [None]
    requests = []
    for i in range(args.requests):
        if stateless:       # one image in, one class out — the prompt IS
            req = {"prompt": rng.normal(size=(       # the (H, W, C) array
                       cfg.image_size, cfg.image_size,
                       cfg.in_channels)).astype(np.float32),
                   "max_new_tokens": 1}
        else:
            plen = (int(rng.integers(4, args.prompt_len + 1))
                    if args.mixed_lengths else args.prompt_len)
            mnew = (int(rng.integers(2, args.max_new + 1))
                    if args.mixed_lengths else args.max_new)
            req = {"prompt": rng.integers(1, min(cfg.vocab_size, 255),
                                          size=plen).tolist(),
                   "max_new_tokens": mnew}
        if rt.banked:
            req["adapter"] = names[i % len(names)]
        requests.append(req)

    tick_hook = None
    if args.log_json or (args.report_interval > 0 and slo is not None):
        tick_hook = make_tick_observer(eng, slo, args.report_interval,
                                       args.log_json)

    t0 = time.perf_counter()
    if args.arrival_rate > 0 and args.engine == "continuous":
        arrivals = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                             size=args.requests))
        results = drive_streaming(eng, requests, arrivals, tick_hook)
    else:
        if args.arrival_rate > 0:
            print("note: static engine ignores arrival times "
                  "(drain-queue batching)")
        for req in requests:
            eng.add_request(**req)
        if tick_hook is not None and hasattr(eng, "step"):
            t0r = time.perf_counter()
            while eng.step():
                tick_hook()
            eng.add_wall(time.perf_counter() - t0r)
            results = {r.rid: r.output for r in eng.finished}
        else:
            results = eng.run()
    dt = time.perf_counter() - t0

    describe(eng, results, args.engine, dt)
    compiles = compile_counts()
    print("compiles by span: " + " ".join(
        f"{k}={v}" for k, v in sorted(compiles.items())))
    if isinstance(eng, EngineCluster):
        # the ONE residency/routing report — replica rows carry the bank
        # and KV-pool residency that used to be printed ad hoc here
        # (and the SLO block when tracing is on)
        print(format_cluster_report(eng.cluster_stats()))
    elif slo is not None:
        print(SLOMonitor.format_report(slo.report()))
    if args.log_json:
        print(json.dumps({
            "event": "summary", "engine": args.engine,
            "replicas": args.replicas, "requests": len(results),
            "tokens_generated": eng.stats["tokens_generated"],
            "decode_steps": eng.stats["decode_steps"],
            "prefills": eng.stats["prefills"],
            "admission_stalls": eng.stats["admission_stalls"],
            "wall_s": round(dt, 6), "compiles": compiles,
            "slo": slo.report() if slo is not None else None}))
    if tracer is not None and args.trace_out:
        if args.trace_out.endswith(".jsonl"):
            n = tracer.export_jsonl(args.trace_out)
        else:
            n = tracer.export_chrome(args.trace_out)
        print(f"trace: {len(tracer.finished)} requests, {n} events "
              f"-> {args.trace_out}")
    sample = results[min(results)]
    print("sample output tokens:", sample[:16])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
