"""Paged-KV serving benchmark (ISSUE 7): residency + scheduling lanes.

Three lanes, all over the same smoke model:

  * ``bytes``  — mixed-length multi-tenant traffic with one shared system
    prompt: paged vs contiguous KV bytes per request (the contiguous engine
    pays ``max_len`` rows per slot; the paged engine pays the pages it
    touches, and shared-prefix pages are paid ONCE across tenants). Greedy
    tokens are asserted identical to the contiguous engine on the way.
  * ``slots``  — a burst (the high-variance limit of Poisson arrivals) into
    a FIXED KV budget worth two contiguous worst-case slots: the paged
    engine fits >= 2x more concurrent requests in the same HBM (asserted;
    the schedule is deterministic, no timing involved).
  * ``hol``    — chunked vs whole-prompt admission on long prompts: wall
    p99 of the gap between consecutive decode steps (what a decoding slot
    actually waits through) plus the deterministic worst-case prefill
    tokens a single tick can interpose.
  * ``tp``     — the tensor-parallel curve: the paged engine re-run in
    THIS process at tp = 1 and, when two or more devices are visible,
    tp = 2 over the real devices (a child process could not reach a chip
    this process already holds). Greedy tokens are asserted identical
    across geometries; tok/s per tp rides the summary, named with the
    device it ran on.

``REPRO_BENCH_TINY=1`` shrinks the workload and writes ``BENCH_kv.json``
at the repo root (uploaded as a CI artifact).
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from repro.config import get_smoke_config
from repro.core.runtime import ModelRuntime
from repro.distrib import serve_mesh
from repro.serve.engine import PagedServeEngine, ServeEngine
from repro.serve.kv import kv_page_bytes

from .common import emit, mixed_workload, run_engine_timed, write_summary

TINY = bool(os.environ.get("REPRO_BENCH_TINY"))

PAGE = 8
CHUNK = 16


def _tenant_workload(n_req, sys_len, priv_hi, new_hi, seed=0):
    """Every tenant shares one ``sys_len``-token system prompt and appends
    a private U[4, priv_hi] suffix; budgets U[2, new_hi]."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(1, 200, size=sys_len).tolist()
    reqs = []
    for _ in range(n_req):
        suffix = rng.integers(
            1, 200, size=int(rng.integers(4, priv_hi + 1))).tolist()
        reqs.append({"prompt": sys_prompt + suffix,
                     "max_new_tokens": int(rng.integers(2, new_hi + 1))})
    return reqs


def _run_all(eng, workload):
    rids = [eng.add_request(**r) for r in workload]
    res = eng.run()
    return {i: res[rid] for i, rid in enumerate(rids)}


def _lane_bytes(rt, cfg, summary):
    n_req = 12 if TINY else 32
    sys_len, priv_hi, new_hi = 64, 32, 16
    max_len = sys_len + priv_hi + new_hi + 8
    wl = _tenant_workload(n_req, sys_len, priv_hi, new_hi)

    # max_batch=2 keeps the first admission wave small: a prefix is only
    # claimable once a finished prefill has published it.
    ref = ServeEngine(rt, max_batch=2, max_len=max_len, eos_id=-1)
    out_ref = _run_all(ref, wl)
    pg = PagedServeEngine(rt, max_batch=2, max_len=max_len, eos_id=-1,
                          page_size=PAGE, prefill_chunk=CHUNK)
    out_pg = _run_all(pg, wl)
    assert out_pg == out_ref, "paged engine diverged from contiguous tokens"

    ptb = kv_page_bytes(cfg, 1)                      # bytes per KV token
    st = pg.kv_stats()
    contig_req = max_len * ptb
    paged_req = st["alloc"] * PAGE * ptb / n_req     # fresh pages only
    ratio = contig_req / max(paged_req, 1e-9)
    assert ratio >= 2.0, f"kv bytes/request ratio {ratio:.2f} < 2x"
    emit("kv/bytes_per_request", 0.0,
         f"contig_kb={contig_req / 1e3:.1f};paged_kb={paged_req / 1e3:.1f};"
         f"ratio=x{ratio:.2f};prefix_hits={st['prefix_hits']};"
         f"tokens_equal=1")
    summary.update(kv_bytes_per_request_contiguous=contig_req,
                   kv_bytes_per_request_paged=paged_req,
                   kv_bytes_per_request_ratio=ratio,
                   prefix_hits=st["prefix_hits"], tokens_equal=True)


def _lane_slots(rt, cfg, summary):
    """Burst admission into a pool worth TWO contiguous worst-case slots."""
    n_req = 12 if TINY else 24
    prompt_hi, new_hi = 24, 12
    max_len = prompt_hi + new_hi + 8
    max_pages = -(-max_len // PAGE)
    budget_pages = 2 * max_pages                 # == 2 contiguous slots
    contig_slots = budget_pages // max_pages     # what contiguous affords
    rng = np.random.default_rng(1)
    wl = [{"prompt": rng.integers(
               1, 200, size=int(rng.integers(4, prompt_hi + 1))).tolist(),
           "max_new_tokens": int(rng.integers(2, new_hi + 1))}
          for _ in range(n_req)]

    eng = PagedServeEngine(rt, max_batch=8, max_len=max_len, eos_id=-1,
                           page_size=PAGE, prefill_chunk=CHUNK,
                           num_pages=budget_pages + 1)   # +1 garbage page
    for r in wl:
        eng.add_request(**r)
    max_conc = 0
    while eng.step():
        max_conc = max(max_conc, eng.num_active)
    max_conc = max(max_conc, eng.num_active)
    budget_bytes = budget_pages * kv_page_bytes(cfg, PAGE)
    st = eng.kv_stats()
    assert max_conc >= 2 * contig_slots, \
        f"paged fits {max_conc} concurrent slots, contiguous {contig_slots}"
    emit("kv/slots_at_fixed_budget", 0.0,
         f"budget_kb={budget_bytes / 1e3:.1f};contig_slots={contig_slots};"
         f"paged_max_concurrent={max_conc};kv_stalls={st['kv_stalls']}")
    summary.update(kv_budget_bytes=budget_bytes,
                   contiguous_slots_at_budget=contig_slots,
                   paged_max_concurrent_slots=max_conc,
                   kv_stalls=st["kv_stalls"])


def _decode_gaps(eng, workload):
    """Wall-clock gaps between consecutive decode steps (ms); the gap a
    decoding slot sits through, including any interleaved prefill work."""
    for r in workload:
        eng.add_request(**r)
    gaps, t_last = [], None
    more = True
    while more:
        before = eng.stats["decode_steps"]
        more = eng.step()
        if eng.stats["decode_steps"] > before:
            now = time.perf_counter()
            if t_last is not None:
                gaps.append((now - t_last) * 1e3)
            t_last = now
    return gaps


def _lane_hol(rt, summary):
    """Head-of-line: long prompts admitted whole vs in chunks."""
    n_req = 8 if TINY else 16
    plo, phi, new_hi = 64, 96, 12
    max_len = phi + new_hi + 8
    rng = np.random.default_rng(2)
    wl = [{"prompt": rng.integers(
               1, 200, size=int(rng.integers(plo, phi + 1))).tolist(),
           "max_new_tokens": int(rng.integers(4, new_hi + 1))}
          for _ in range(n_req)]

    res = {}
    for name, chunk in (("whole", max_len), ("chunked", CHUNK)):
        mk = lambda: PagedServeEngine(rt, max_batch=4, max_len=max_len,
                                      eos_id=-1, page_size=PAGE,
                                      prefill_chunk=chunk)
        _decode_gaps(mk(), wl)                       # warmup (compile)
        gaps = _decode_gaps(mk(), wl)
        p99 = float(np.percentile(gaps, 99)) if gaps else 0.0
        p50 = float(np.percentile(gaps, 50)) if gaps else 0.0
        res[name] = {"p99_ms": p99, "p50_ms": p50,
                     "hol_tokens": min(chunk, phi)}
        emit(f"kv/decode_gap_{name}", 1e3 * p99,
             f"p50_ms={p50:.2f};p99_ms={p99:.2f};"
             f"max_prefill_tokens_per_tick={min(chunk, phi)}")
    ratio = res["whole"]["p99_ms"] / max(res["chunked"]["p99_ms"], 1e-9)
    emit("kv/chunked_prefill_p99_speedup", 0.0, f"x{ratio:.2f}")
    summary.update(
        decode_gap_p99_ms_whole=res["whole"]["p99_ms"],
        decode_gap_p99_ms_chunked=res["chunked"]["p99_ms"],
        chunked_prefill_p99_speedup=ratio,
        hol_tokens_whole=res["whole"]["hol_tokens"],
        hol_tokens_chunked=res["chunked"]["hol_tokens"])


def _lane_tp(cfg, summary):
    """Paged decode under serve-time TP, in this process, over the visible
    devices (tp = 2 only where two are visible)."""
    n_req = 8 if TINY else 16
    prompt_hi, new_hi = 24, 12
    max_len = prompt_hi + new_hi + 8
    wl = mixed_workload(n_req, prompt_hi, new_hi, seed=7)
    rows = []
    for tp in sorted({1, min(2, jax.device_count())}):
        rt = ModelRuntime(cfg, key=jax.random.PRNGKey(0),
                          mesh=serve_mesh(tp) if tp > 1 else None)
        make = lambda: PagedServeEngine(rt, max_batch=4, max_len=max_len,
                                        eos_id=-1, page_size=PAGE,
                                        prefill_chunk=CHUNK)
        r = run_engine_timed(make, wl, wl)
        probe = make()
        rids = [probe.add_request(**req) for req in wl]
        res = probe.run()
        rows.append(dict(r, tp=tp, devices=tp,
                         outputs=[res[rid] for rid in rids]))
    for r in rows:
        assert r["outputs"] == rows[0]["outputs"], \
            f"tp={r['tp']} tokens diverged from tp=1"
        emit(f"kv/paged_tp{r['tp']}", 0.0,
             f"tok/s={r['tok_s']:.1f};devices={r['devices']};"
             f"decode_steps={r['decode_steps']};tokens_equal=1")
    summary["tp_scaling"] = [{k: r[k] for k in
                              ("tp", "devices", "tok_s", "tokens")}
                             for r in rows]


def run():
    cfg = get_smoke_config("qwen2-72b")
    rt = ModelRuntime(cfg, key=jax.random.PRNGKey(0))
    dev = jax.devices()[0]
    summary = {"backend": dev.platform, "device_kind": dev.device_kind,
               "device_count": jax.device_count(), "arch": cfg.name,
               "page_size": PAGE, "prefill_chunk": CHUNK}
    _lane_bytes(rt, cfg, summary)
    _lane_slots(rt, cfg, summary)
    _lane_hol(rt, summary)
    _lane_tp(cfg, summary)
    if TINY:
        write_summary("kv", summary)


if __name__ == "__main__":
    run()
