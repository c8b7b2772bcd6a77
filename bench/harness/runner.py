"""One run of one cell: set-up, the measured window, the readers, and the
comparison with the reference that decides ``correct``. Returns the
result line's object; ``run.py`` prints it."""
from __future__ import annotations

import gc
import json
import sys
import time
from typing import Any, Callable, Dict, Optional, Sequence

from . import cells as cells_lib
from . import device as device_lib
from . import metrics as m_lib
from . import serve as serve_lib
from . import traffic as traffic_lib

LIMITS_DIR = cells_lib.BENCH / "limits"
#: the base weights' quantization as the configuration states it
QUANT = {"bf16": None, "int8": "int8"}


def limits(cell_name: str) -> Dict[str, Any]:
    """The cell's comparison (``limits/<cell>.json``): under ``limits``,
    the numbers compared with the limit of each."""
    with open(LIMITS_DIR / f"{cell_name}.json") as f:
        return json.load(f)


def judge(values: Dict[str, float], lim: Dict[str, Any]
          ) -> (bool, Dict[str, Dict[str, float]]):
    """Each number the limits file names, beside its limit; correct when
    every one is at or under it. A number with no limit, or one that is
    not finite, fails."""
    checks, ok = {}, True
    for k, spec in lim["limits"].items():
        v, limit = values.get(k, float("inf")), spec["limit"]
        checks[k] = {"value": v, "limit": limit}
        if limit is None or not m_lib.finite(v) or v > limit:
            ok = False
    return ok, checks


def read_per_layer(cell: cells_lib.Cell, w) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        value = cells_lib.load_reader(m["name"])(w)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _trace_hooks(logdir: str):
    import jax

    def start():
        jax.profiler.start_trace(logdir)

    def stop():
        jax.profiler.stop_trace()
    return start, stop


def run_serve(cell: cells_lib.Cell, seed: int, seconds: float, trace: bool,
              t_start: float, dev: Dict[str, Any], cfg=None,
              log: Callable[[str], None] = None,
              controls: Sequence[str] = (),
              check: bool = True,
              precision: Optional[str] = None) -> Dict[str, Any]:
    """One serving run. ``controls`` (``control.py`` only) name
    quantizations below the configuration's precision; the run then also
    judges each control, the reference at that precision put in the
    program's place, by the same limits. ``precision`` (``control.py``
    only) runs the program's own base path at another precision than the
    configuration states, judged against the reference at the stated one.
    ``check=False`` (``sweep.py`` only) skips the reference and reports
    the backlog."""
    import jax
    from reference import qwen2 as ref
    from . import trace as trace_lib
    from . import weights

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    cfg = cfg or cells_lib.model_config(cell.config)
    mix, dep = cell.traffic, cell.config["deployment"]
    clock = device_lib.CompileClock()
    built = cell.config if precision is None else dict(
        cell.config, deployment=dict(dep, base_precision=precision))
    cluster, rts, adapters, params, probe, names = serve_lib.build(
        cfg, built, mix, seed, cell.chips)
    serve_lib.warm(cluster, rts, probe, mix, seed, cfg.vocab_size,
                   int(dep["prefill_chunk"]), names)
    requests = traffic_lib.serve_requests(mix, seed, seconds, cfg.vocab_size)
    probe.jax_annotations = trace
    logdir = str(cells_lib.CHECKOUT / ".bench_trace" / f"{cell.name}-{seed}")
    c0 = serve_lib._counters(cluster)
    compiles0 = clock.count
    jax.effects_barrier()
    setup_s = time.perf_counter() - t_start
    t = serve_lib.measure(cluster, probe, requests, seconds,
                          _trace_hooks(logdir) if trace else None)
    outputs = {}
    crid_to_idx = t.pop("crid_to_idx")
    for r in cluster.drain_finished():
        outputs[crid_to_idx[r.rid]] = list(r.output)
    w = serve_lib.Window(
        seconds=seconds, t0=t["t0"], t_end=t["t_end"],
        t_drained=t["t_drained"], requests=requests, probe=probe,
        sent=t["sent"], counters=serve_lib._delta(
            c0, serve_lib._counters(cluster)),
        config=cell.config, max_batch=int(dep["max_batch"]) * len(cluster.engines),
        compiles=clock.count - compiles0, chips=cell.chips,
        memory_peak_bytes=device_lib.memory_peak_bytes(cell.chips),
        peaks=device_lib.peaks(dev["kind"]), trace_window=t["trace_window"])
    page_ins = sum(1 for _, t_ in probe.page_ins if t_ >= t["t0"])
    log(f"[window] compiles={w.compiles} attempted={len(requests)} "
        f"failed={m_lib.failed(w)} page_ins={page_ins} "
        f"{json.dumps(m_lib.lateness(w))}")
    e2e = m_lib.serve_end_to_end(w)
    e2e["setup_s"] = setup_s
    out_metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    device = dict(dev, memory_peak_bytes=w.memory_peak_bytes)
    result: Dict[str, Any] = {}
    if trace:
        w.trace = trace_lib.summarize(logdir, w.peaks, cell.chips)
        out_metrics = read_per_layer(cell, w)
        device["busy_s"] = w.trace.busy_s
        device["window_s"] = w.trace.window_s
        result["breakdown"] = w.trace.breakdown()
        trace_lib.remove(logdir)

    if not check:
        result.update(attempted=len(requests), failed=m_lib.failed(w),
                      metrics=out_metrics, device=device,
                      load=m_lib.load_report(w))
        return result

    # the program's state goes before the reference runs
    sample, covered = serve_lib.pick_sample(requests, probe, outputs, seed,
                                            t["t0"])
    served = [(r.prompt, outputs[r.idx], r.tenant) for r in sample]
    bad = sum(1 for r in sample
              if len(outputs[r.idx]) != r.max_new
              or not all(0 <= x < cfg.vocab_size for x in outputs[r.idx]))
    del cluster, rts, probe
    gc.collect()
    if params is None:
        params = weights.make_params(cfg, seed)
    arch = ref.Arch.from_config(cell.config)
    t_ref = time.perf_counter()
    gaps = {}
    if served:
        gaps = ref.served_gaps(arch, params, adapters, served,
                               ref.pad_to(traffic_lib.max_len(
                                   mix, int(dep["page_size"]))),
                               QUANT[dep["base_precision"]], controls)
    log(f"[reference] {len(served)} requests, "
        f"{len(gaps.get('program', ()))} served tokens, "
        f"{time.perf_counter() - t_ref:.1f}s")

    def compared(g):
        inf = float("inf")
        return {"mean_gap": float(g.mean()) if g is not None else inf,
                "bad_outputs": float(bad),
                "unchecked": 0.0 if served and covered else 1.0}
    lim = limits(cell.name)
    values = compared(gaps.get("program"))
    ok, checks = judge(values, lim)
    if controls:
        result["control"] = {}
        for c in controls:
            c_values = compared(gaps.get(c))
            c_ok, c_checks = judge(c_values, lim)
            result["control"][c] = {"correct": c_ok, "checks": c_checks,
                                    "readings": c_values}
    result["readings"] = values
    for k in sorted(set(values) - set(checks)):
        log(f"[reading] {k} = {values[k]!r} (not compared)")
    result.update({"correct": ok, "attempted": len(requests),
                   "failed": m_lib.failed(w), "metrics": out_metrics,
                   "device": device, "checks": checks})
    for k, v in checks.items():
        log(f"[check] {k} = {v['value']!r} (limit {v['limit']!r})")
    return result


def _leaf_norms(tree) -> Dict[str, float]:
    import numpy as np
    return {f"{p}/{k}": float(np.linalg.norm(np.asarray(v, np.float64)))
            for p, e in tree.items() for k, v in e.items()}


def norm_gap(prog, ref, ref_grad) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out."""
    import numpy as np
    pn, rn, gn = _leaf_norms(prog), _leaf_norms(ref), _leaf_norms(ref_grad)
    med = float(np.median(list(rn.values())))
    gmed = float(np.median(list(gn.values())))
    gaps = [abs(pn[k] - rn[k]) / max(rn[k], med) for k in rn
            if gn[k] >= 1e-3 * gmed]
    return max(gaps) if gaps else float("inf")


def run_train(cell: cells_lib.Cell, seed: int, seconds: float, trace: bool,
              t_start: float, dev: Dict[str, Any], cfg=None,
              log: Callable[[str], None] = None,
              controls: Sequence[str] = ()) -> Dict[str, Any]:
    """One training run. ``controls`` (``control.py`` only) name
    quantizations of the base weights below the configuration's; the run
    then also judges each, the reference at that precision in the
    program's place, by the same limits."""
    import jax
    import numpy as np
    from reference import qwen2 as ref
    from . import trace as trace_lib
    from . import train as train_lib

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    cfg = cfg or cells_lib.model_config(cell.config)
    mix = cell.traffic
    clock = device_lib.CompileClock()
    step, params, adapter, trainable, state = train_lib.build(
        cfg, cell.config, mix, seed)
    rows, seq = int(mix["global_batch"]), int(mix["seq_len"])
    n_check = int(mix["checked_steps"])
    losses, mu1 = [], None
    for k in range(n_check):
        trainable, state, m = step(params, trainable, state,
                                   train_lib.batch(mix, seed, k,
                                                   cfg.vocab_size))
        losses.append(float(m["loss"]))
        if k == 0:
            mu1 = jax.device_get(state["mu"])
    theta_prog = jax.device_get(trainable)
    compiles0 = clock.count
    logdir = str(cells_lib.CHECKOUT / ".bench_trace" / f"{cell.name}-{seed}")
    setup_s = time.perf_counter() - t_start

    t0 = time.perf_counter()
    traced = None
    if trace:
        mid, span = t0 + seconds / 2, min(serve_lib.TRACE_S, seconds / 3)
        traced = [mid - span / 2, mid + span / 2, False, False]
    stream, steps = n_check, 0
    nxt = train_lib.batch(mix, seed, stream, cfg.vocab_size)
    while True:
        now = time.perf_counter()
        if traced is not None and not traced[2] and now >= traced[0]:
            jax.profiler.start_trace(logdir)
            traced[2], traced[0] = True, time.perf_counter()
        elif traced is not None and traced[2] and not traced[3] \
                and now >= traced[1]:
            jax.profiler.stop_trace()
            traced[3], traced[1] = True, time.perf_counter()
        with serve_lib._span(traced, "train_step"):
            trainable, state, m = step(params, trainable, state, nxt)
        stream += 1
        with serve_lib._span(traced, "feed"):
            nxt = train_lib.batch(mix, seed, stream, cfg.vocab_size)
        jax.block_until_ready(m["loss"])
        steps += 1
        if time.perf_counter() - t0 >= seconds:
            break
    measured = time.perf_counter() - t0
    if traced is not None and traced[2] and not traced[3]:
        jax.profiler.stop_trace()
        traced[1] = time.perf_counter()
    w = train_lib.TrainWindow(
        seconds=seconds, seconds_measured=measured, steps=steps, rows=rows,
        seq=seq, config=cell.config,
        memory_peak_bytes=device_lib.memory_peak_bytes(cell.chips),
        peaks=device_lib.peaks(dev["kind"]),
        compiles=clock.count - compiles0, chips=cell.chips,
        trace_window=tuple(traced[:2]) if traced else None)
    log(f"[window] compiles={w.compiles} steps={steps} "
        f"seconds={measured:.3f} losses={losses}")
    e2e = {"train_tok_s": steps * rows * seq / measured, "setup_s": setup_s}
    out_metrics = {m_["name"]: {"value": e2e[m_["name"]], "unit": m_["unit"]}
                   for m_ in cell.end_to_end if m_["name"] in e2e}
    device = dict(dev, memory_peak_bytes=w.memory_peak_bytes)
    result: Dict[str, Any] = {}
    if trace:
        w.trace = trace_lib.summarize(logdir, w.peaks, cell.chips)
        out_metrics = read_per_layer(cell, w)
        device["busy_s"] = w.trace.busy_s
        device["window_s"] = w.trace.window_s
        result["breakdown"] = w.trace.breakdown()
        trace_lib.remove(logdir)

    # the program's state goes before the reference runs
    del step, trainable, state, m, nxt
    gc.collect()
    arch = ref.Arch.from_config(cell.config)
    batches = [(b["tokens"], b["labels"]) for b in (
        traffic_lib.train_rows(mix, seed, rows, cfg.vocab_size, k)
        for k in range(n_check))]
    t_ref = time.perf_counter()
    ref_losses, ref_g1, ref_theta = ref.train_steps(
        arch, params, adapter, batches, mix["optimizer"])
    log(f"[reference] {n_check} steps, losses {ref_losses}, "
        f"{time.perf_counter() - t_ref:.1f}s")
    b1 = float(mix["optimizer"]["b1"])
    g1_prog = jax.tree.map(lambda x: np.asarray(x) / (1.0 - b1), mu1)
    delta = lambda th: jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                                    th, adapter)
    ref_g1 = jax.device_get(ref_g1)
    ref_delta = delta(jax.device_get(ref_theta))

    def compare(losses_, g1_, theta_):
        return {"loss_gap": max(abs(a - b)
                                for a, b in zip(losses_, ref_losses)),
                "grad_norm_gap": norm_gap(g1_, ref_g1, ref_g1),
                "update_norm_gap": norm_gap(delta(theta_), ref_delta,
                                            ref_g1)}
    values = compare(losses, g1_prog, theta_prog)
    lim = limits(cell.name)
    if controls:
        result["control"] = {}
        for c in controls:
            c_losses, c_g1, c_theta = ref.train_steps(
                arch, params, adapter, batches, mix["optimizer"], quant=c)
            c_ok, c_checks = judge(compare(c_losses, jax.device_get(c_g1),
                                           jax.device_get(c_theta)), lim)
            result["control"][c] = {"correct": c_ok, "checks": c_checks}
    result["readings"] = values
    ok, checks = judge(values, lim)
    result.update({"correct": ok, "attempted": steps, "failed": 0,
                   "metrics": out_metrics, "device": device,
                   "checks": checks})
    for k, v in checks.items():
        log(f"[check] {k} = {v['value']!r} (limit {v['limit']!r})")
    return result
