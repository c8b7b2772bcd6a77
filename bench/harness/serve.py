"""The serving cell: an open loop of timed requests into the program's
``EngineCluster`` of ``PagedServeEngine`` replicas.

Set-up makes the weights and the tenants' adapters from the seed, builds
the runtime the configuration states (bf16 or int8 base, a store-paged
GSOFT bank with the mix's resident slots), pages every tenant through the
bank once (least popular first, so the most popular are resident when
the window opens, the tail pages in during it, and every tenant's
factors sit in the host page cache, as in a server that has been up), and
sends two warm-up asks that run every program the window runs.

The window then admits each request as soon as it is due, between
scheduler ticks, and times it from its due time. When the window closes
no new request is sent; the loop runs on until every request that was due
in the window has finished (at most ``DRAIN_S``), so a late answer is
late, not missing. Then the program's state is freed and the reference
recomputes a sample of the finished requests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

from . import traffic as traffic_lib
from .probe import Probe

DRAIN_S = 60.0
SAMPLE = 6                 # requests the reference recomputes
TRACE_S = 4.0              # traced sub-window of a --trace 1 run


@dataclasses.dataclass
class Window:
    """What the run measured, for the result line and the readers."""
    seconds: float
    t0: float
    t_end: float
    t_drained: float
    requests: List[traffic_lib.Request]
    probe: Probe
    sent: Dict[int, float]              # request idx -> perf_counter sent
    counters: Dict[str, float]          # program counters, window deltas
    config: Dict[str, Any]
    max_batch: int
    trace: Optional[Any] = None         # harness.trace.Summary
    trace_window: Optional[tuple] = None
    memory_peak_bytes: int = 0
    peaks: Optional[Dict[str, float]] = None
    compiles: int = 0
    chips: int = 1
    kind: str = "serve"


def _counters(cluster) -> Dict[str, float]:
    s = dict(cluster.stats)
    kv = cluster.kv_stats() or {}
    ad = cluster.adapter_stats() or {}
    return {"tokens_generated": s["tokens_generated"],
            "decode_steps": s["decode_steps"], "prefills": s["prefills"],
            "admission_stalls": s["admission_stalls"],
            "prefix_hit_pages": kv.get("prefix_hits", 0),
            "page_size": kv.get("page_size", 0),
            "bank_hits": ad.get("hits", 0), "bank_misses": ad.get("misses", 0),
            "affinity_hits": cluster.routing["affinity_hits"],
            "affinity_spills": cluster.routing["affinity_spills"]}


def _delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: (b[k] - a[k] if k != "page_size" else b[k]) for k in b}


def build(cfg, config: Dict[str, Any], mix: Dict[str, Any], seed: int,
          chips: int):
    """(cluster, runtime, adapters, params or None, probe inputs): the
    program as the configuration states it, weights from the seed."""
    import jax
    from repro.core import peft as peft_lib
    from repro.core.runtime import ModelRuntime
    from repro.distrib import EngineCluster
    from repro.launch.serve import replica_runtimes
    from repro.serve.engine import PagedServeEngine
    from repro.store import AdapterStore
    from . import weights

    dep = config["deployment"]
    ad_cfg = dep["adapter"]
    names = traffic_lib.tenant_names(mix)
    params = weights.make_params(cfg, seed)
    adapters = weights.make_adapters(cfg, int(ad_cfg["block_size"]), seed,
                                     names)
    pc = peft_lib.PEFTConfig(method=ad_cfg["method"],
                             block_size=int(ad_cfg["block_size"]),
                             use_pallas=cfg.use_pallas)
    want = jax.eval_shape(lambda: peft_lib.init_peft(
        pc, params, jax.random.PRNGKey(0)))
    for path, entry in want.items():
        for k, v in entry.items():
            if adapters[names[0]][path][k].shape != v.shape:
                raise ValueError(f"adapter {path}/{k}: benchmark shape "
                                 f"{adapters[names[0]][path][k].shape}, "
                                 f"program {v.shape}")
    store = AdapterStore.from_adapters(adapters, pc)
    base = ModelRuntime(cfg, params)
    if dep["base_precision"] == "int8":
        base = base.quantized("int8")
        del params                      # the reference remakes it later
        params = None
    elif dep["base_precision"] != "bf16":
        raise ValueError(f"unknown base precision {dep['base_precision']!r}")
    # one replica per chip of the cell, each with its own bank
    slots = int(mix["resident_slots"])
    rts = replica_runtimes(base.attach(store, hbm_budget=slots), chips,
                           hbm_budget=slots)
    page = int(dep["page_size"])
    probe = Probe()
    for rt in rts:
        probe.watch_bank(rt.bank)
    engines = [PagedServeEngine(
        rt, max_batch=int(dep["max_batch"]),
        max_len=traffic_lib.max_len(mix, page), eos_id=-1, page_size=page,
        prefill_chunk=int(dep["prefill_chunk"]), tracer=probe)
        for rt in rts]
    return EngineCluster(engines), rts, adapters, params, probe, names


def _send(target, probe: Probe, r: traffic_lib.Request) -> int:
    rid = target.add_request(r.prompt.tolist(), r.max_new, adapter=r.tenant)
    probe.bind(r.idx, len(r.prompt))
    return rid


def warm(cluster, rts, probe: Probe, mix, seed: int, vocab: int,
         chunk: int, names: List[str]) -> None:
    """Page every tenant through each bank (least popular first), then run
    the warm-up asks to completion."""
    for rt in rts:
        for name in reversed(names):
            if rt.acquire_adapter(name) is None:
                raise RuntimeError(f"bank stalled paging {name} at set-up")
            rt.release_adapter(name)
    for eng in cluster.engines:
        for r in traffic_lib.warmup_requests(mix, seed, vocab, chunk):
            _send(eng, probe, r)
            while eng.step():
                pass
        eng.drain_finished()


def measure(cluster, probe: Probe, requests: List[traffic_lib.Request],
            seconds: float, tracer=None) -> Dict[str, float]:
    """The open loop. ``tracer`` (start, stop) callables are called at the
    middle of the window when a traced sub-window is asked for."""
    import jax
    sent: Dict[int, float] = {}
    crid_to_idx: Dict[int, int] = {}
    t0 = time.perf_counter()
    t_end = t0 + seconds
    nxt = 0
    n = len(requests)
    traced = None
    if tracer is not None:
        mid = t0 + seconds / 2
        span = min(TRACE_S, seconds / 3)
        traced = [mid - span / 2, mid + span / 2, False, False]
    busy = True
    while True:
        now = time.perf_counter()
        if traced is not None:
            if not traced[2] and now >= traced[0]:
                tracer[0]()
                traced[2] = True
                traced[0] = time.perf_counter()
            elif traced[2] and not traced[3] and now >= traced[1]:
                tracer[1]()
                traced[3] = True
                traced[1] = time.perf_counter()
        while nxt < n and t0 + requests[nxt].due <= now:
            with _span(tracer, "add_request"):
                crid = _send(cluster, probe, requests[nxt])
            crid_to_idx[crid] = requests[nxt].idx
            sent[requests[nxt].idx] = time.perf_counter()
            nxt += 1
        if now >= t_end and nxt >= n:
            break
        if busy or not cluster.idle:
            with _span(tracer, "cluster_step"):
                busy = cluster.step()
        else:
            wake = t0 + requests[nxt].due if nxt < n else t_end
            with _span(tracer, "wait_for_arrival"):
                time.sleep(max(0.0, min(wake, t_end) - time.perf_counter()))
            busy = not cluster.idle
    t_close = time.perf_counter()
    if traced is not None and traced[2] and not traced[3]:
        tracer[1]()
        traced[1] = time.perf_counter()
    deadline = t_close + DRAIN_S
    due_idx = {r.idx for r in requests}
    while time.perf_counter() < deadline and not due_idx <= set(probe.t_done):
        if not cluster.step() and cluster.idle:
            break
    jax.effects_barrier()
    return {"t0": t0, "t_end": t_end, "t_close": t_close,
            "t_drained": time.perf_counter(), "sent": sent,
            "crid_to_idx": crid_to_idx,
            "trace_window": tuple(traced[:2]) if traced else None}


class _span:
    """A TraceAnnotation named ``name`` in a traced run (``tracer`` set)."""

    def __init__(self, tracer, name):
        self.on = tracer is not None
        self.name = name

    def __enter__(self):
        if self.on:
            import jax.profiler
            self.cm = jax.profiler.TraceAnnotation(self.name)
            self.cm.__enter__()

    def __exit__(self, *exc):
        if self.on:
            self.cm.__exit__(*exc)


def pick_sample(requests: List[traffic_lib.Request], probe: Probe,
                outputs: Dict[int, List[int]], seed: int, t0: float
                ) -> (List[traffic_lib.Request], bool):
    """The requests the reference recomputes, and whether they cover a
    slot paged in during the window where one was: the longest finished
    request, the first finished one admitted after its tenant's adapter
    was paged into the bank in the window, the last-due one whose prefix
    an earlier request had already sent, and the rest drawn from the
    seed."""
    done = [r for r in requests if r.idx in outputs]
    if not done:
        return [], False
    pick: List[traffic_lib.Request] = []

    def add(r):
        if r is not None and all(r.idx != p.idx for p in pick):
            pick.append(r)
    add(max(done, key=lambda r: (len(r.prompt) + r.max_new, -r.idx)))
    paged = {}
    for name, t in probe.page_ins:
        if t >= t0:
            paged.setdefault(name, t)
    after_page_in = [r for r in done if r.tenant in paged
                     and probe.t_chunk0.get(r.idx, -1.0) >= paged[r.tenant]]
    add(min(after_page_in, key=lambda r: r.due, default=None))
    seen, shared = set(), None
    for r in sorted(requests, key=lambda r: r.due):
        if r.prefix_key in seen and r.idx in outputs:
            shared = r
        seen.add(r.prefix_key)
    add(shared)
    rest = [r for r in done if all(r.idx != p.idx for p in pick)]
    order = traffic_lib.rng_for(seed, 3).permutation(len(rest))
    for i in order[:max(0, SAMPLE - len(pick))]:
        add(rest[int(i)])
    return pick, bool(after_page_in) or not paged
