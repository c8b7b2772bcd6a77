"""Observability plane: typed-instrument registry semantics, per-request
span lifecycle on real engine traffic, stall attribution, export formats,
SLO thresholds/backpressure wiring, the bounded ``page_in_ms`` histogram
(regression for the unbounded-list leak), the serving tick's spans and
the per-span compile counter, TTFT from arrival, and the bench-regression
gate's comparison logic."""
import contextlib
import io
import json
import time

import pytest

jax = pytest.importorskip("jax")
np = pytest.importorskip("numpy")

from repro.config import get_smoke_config               # noqa: E402
from repro.core import peft as peft_lib                 # noqa: E402
from repro.core.runtime import ModelRuntime             # noqa: E402
from repro.distrib import EngineCluster                 # noqa: E402
from repro.launch.serve import (                        # noqa: E402
    drive_streaming, make_demo_adapters)
from repro.obs import (                                 # noqa: E402
    SPANS, Counter, Gauge, Histogram, MetricsRegistry, RequestTrace,
    SLOMonitor, TraceRecorder, compile_counts, span)
from repro.serve.engine import (                        # noqa: E402
    PagedServeEngine, ServeEngine, latency_percentiles)
from repro.store import AdapterStore                    # noqa: E402

from benchmarks import check_regress                    # noqa: E402
from benchmarks.common import mixed_workload, run_engine_timed  # noqa: E402


@pytest.fixture(scope="module")
def rt():
    return ModelRuntime(get_smoke_config("qwen2-72b"),
                        key=jax.random.PRNGKey(0))


def _tenant_store(rt, n_ad, method="gsoft"):
    bank_peft = {f"a{i}": peft_lib.PEFTConfig(method=method, block_size=8)
                 for i in range(n_ad)}
    adapters = make_demo_adapters(list(bank_peft), rt.params, bank_peft)
    return AdapterStore.from_adapters(adapters, bank_peft), bank_peft


# -- registry / instrument semantics ------------------------------------------

def test_instrument_semantics():
    c = Counter("c")
    c.inc()
    c.inc(3)
    assert c.value == 4
    g = Gauge("g")
    g.set(7)
    g.set_max(3)            # lower: ignored
    g.set_max(11)
    assert g.value == 11
    h = Histogram("h", cap=8)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100 and len(h) == 8      # bounded reservoir
    assert h.sum == sum(range(100))
    # percentiles come from the RECENT window (last 8 samples: 92..99)
    assert h.percentile(0) >= 92.0
    assert h.percentiles()["p50"] >= 92.0


def test_registry_idempotent_and_kind_collision():
    r = MetricsRegistry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")
    with pytest.raises(TypeError):
        r.histogram("x")


def test_scope_uniquify_isolates_replicas():
    r = MetricsRegistry()
    s0, s1 = r.scope("kvpool"), r.scope("kvpool")
    assert s0.prefix == "kvpool" and s1.prefix == "kvpool:1"
    c0 = s0.counters("alloc", "freed")
    c1 = s1.counters("alloc", "freed")
    c0["alloc"].inc(5)
    assert c1["alloc"].value == 0              # replicas never share
    assert r.get("kvpool/alloc").value == 5
    assert r.get("kvpool:1/alloc").value == 0


def test_snapshot_expands_histograms():
    r = MetricsRegistry()
    s = r.scope("bank")
    s.counter("hits").inc(2)
    h = s.histogram("page_in_ms", cap=16)
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    snap = r.snapshot()
    assert snap["bank/hits"] == 2
    assert snap["bank/page_in_ms.count"] == 3
    assert snap["bank/page_in_ms.mean"] == pytest.approx(2.0)
    assert "bank/page_in_ms.p95" in snap
    assert r.snapshot(prefix="nope") == {}
    r.reset()
    assert r.names() == []


# -- bounded page_in_ms (the leak regression) ---------------------------------

def test_page_in_histogram_bounded_under_thrash(rt, monkeypatch):
    """Regression: ``page_in_ms`` used to be an append-forever list; under
    LRU thrash past the cap the reservoir must stop growing while the
    streaming count keeps the true total."""
    from repro.store import paging
    monkeypatch.setattr(paging, "PAGE_IN_HIST_CAP", 4)
    store, _ = _tenant_store(rt, n_ad=6)
    bank = rt.attach(store, hbm_budget=3).bank
    for i in range(12):                        # cyclic over 6 tenants, cap 3
        name = f"a{i % 6}"
        assert bank.acquire(name) is not None
        bank.release(name)
    hist = bank._page_in_ms
    assert hist.count > 4, "expected >cap page-ins from LRU thrash"
    assert len(hist) <= 4, "page_in_ms reservoir exceeded its cap"
    st = bank.stats()
    assert st["page_in_ms_p95"] >= st["page_in_ms_p50"] >= 0.0


# -- span lifecycle on real traffic -------------------------------------------

@pytest.fixture(scope="module")
def traced_run(rt):
    """One continuous-batching run over ragged traffic with a recorder +
    SLO monitor attached; shared by the lifecycle/export/SLO tests."""
    slo = SLOMonitor(window=64)
    reg = MetricsRegistry()
    tracer = TraceRecorder(slo=slo, registry=reg)
    eng = ServeEngine(rt, max_batch=2, max_len=32, eos_id=-1, tracer=tracer)
    rng = np.random.default_rng(0)
    n_req = 8
    for _ in range(n_req):
        prompt = [int(t) for t in rng.integers(0, 100,
                                               size=int(rng.integers(4, 12)))]
        eng.add_request(prompt, max_new_tokens=int(rng.integers(2, 8)))
    out = eng.run()
    return tracer, slo, reg, out, n_req


def test_span_lifecycle_complete_on_ragged_traffic(traced_run):
    tracer, slo, reg, out, n_req = traced_run
    assert len(tracer.finished) == n_req
    assert tracer.pending_count == 0
    for tr in tracer.finished:
        assert tr.complete, f"rid {tr.rid} missing lifecycle events"
        # prefill happens after submit, first token after prefill: TTFT
        # must cover at least the prefill span(s)
        assert tr.ttft_s >= tr.prefill_s > 0.0
        assert tr.t_submit <= tr.t_first <= tr.t_finish
        assert tr.n_tokens == len(out[tr.rid])
        assert all(g >= 0.0 for g in tr.tpot_s)
    snap = reg.snapshot(prefix="trace/")
    assert snap["trace/submitted"] == snap["trace/finished"] == n_req
    assert snap["trace/tokens"] == sum(len(v) for v in out.values())


def test_slo_report_from_real_run(traced_run):
    _, slo, _, out, n_req = traced_run
    rep = slo.report()
    assert rep["window_requests"] == rep["total_requests"] == n_req
    assert rep["ttft_ms"]["p95"] >= rep["ttft_ms"]["p50"] > 0.0
    assert rep["tpot_ms"]["p50"] > 0.0
    assert rep["tok_s"] > 0.0
    text = SLOMonitor.format_report(rep)
    assert "ttft_ms" in text and "tok/s" in text


def test_export_formats(traced_run):
    tracer, _, _, out, n_req = traced_run
    buf = io.StringIO()
    n_lines = tracer.export_jsonl(buf)
    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    assert len(events) == n_lines > 0
    by_kind = {}
    for ev in events:
        by_kind.setdefault(ev["event"], set()).add(ev["rid"])
    rids = {tr.rid for tr in tracer.finished}
    for kind in ("submit", "prefill", "first_token", "finish"):
        assert by_kind[kind] == rids, f"{kind} events missing for some rids"

    buf = io.StringIO()
    n_ev = tracer.export_chrome(buf)
    doc = json.loads(buf.getvalue())
    assert len(doc["traceEvents"]) == n_ev
    phases = {ev["ph"] for ev in doc["traceEvents"]}
    assert phases <= {"M", "X", "i"}
    for ev in doc["traceEvents"]:
        if "ts" in ev:
            assert ev["ts"] >= 0.0                  # relative to first submit
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0


def test_span_completeness_warm_and_timed_passes(rt):
    """One traced engine through the serving bench's protocol (a warm-up
    pass, then the timed pass of the same mixed workload): every request
    of both passes finishes with a complete span set, and the SLO report
    carries TTFT and TPOT percentiles."""
    tracer = TraceRecorder(slo=SLOMonitor(window=512),
                           registry=MetricsRegistry())
    workload = mixed_workload(8, 12, 8, seed=0)
    run_engine_timed(lambda: ServeEngine(rt, max_batch=4, max_len=28,
                                         eos_id=-1, tracer=tracer),
                     workload, workload)
    done = tracer.finished
    assert len(done) == 2 * len(workload)
    incomplete = [t.rid for t in done if not t.complete]
    assert not incomplete, f"incomplete spans for rids {incomplete}"
    rep = tracer.slo.report()
    assert rep["ttft_ms"]["p95"] > 0 and rep["tpot_ms"]["p50"] > 0


# -- TTFT from arrival ---------------------------------------------------------

def test_ttft_counts_the_wait_before_admission(rt):
    """A request the caller held for a while before ``add_request``
    carries its arrival time: its TTFT and latency include the hold."""
    tracer = TraceRecorder(registry=MetricsRegistry())
    eng = ServeEngine(rt, max_batch=2, max_len=32, eos_id=-1, tracer=tracer)
    held = 0.25
    t_arrival = time.perf_counter() - held
    eng.add_request([1, 2, 3, 4], max_new_tokens=3, t_arrival=t_arrival)
    eng.run()
    (tr,), (req,) = tracer.finished, eng.finished
    assert tr.t_submit == req.t_submit == t_arrival
    assert tr.ttft_s >= held + tr.prefill_s
    assert latency_percentiles([req])[50] >= held


def test_drive_streaming_submits_at_arrival_times(rt):
    """``drive_streaming`` stamps each request with ``t0 + arrival``, not
    with when a tick let it in: submit times are spaced as the arrivals
    are, though the first tick (which compiles) holds the later two."""
    tracer = TraceRecorder(registry=MetricsRegistry())
    eng = ServeEngine(rt, max_batch=1, max_len=30, eos_id=-1, tracer=tracer)
    arrivals = np.array([0.0, 0.001, 0.003])
    drive_streaming(eng, [{"prompt": [5, 6, 7, 8], "max_new_tokens": 3}
                          for _ in arrivals], arrivals)
    trs = sorted(tracer.finished, key=lambda t: t.rid)
    np.testing.assert_allclose(np.diff([t.t_submit for t in trs]),
                               np.diff(arrivals), atol=1e-6)
    # one slot: the second request waits for the first to finish
    assert trs[1].ttft_s > trs[0].t_finish - trs[0].t_submit - 0.001


# -- the serving tick's spans and the compile counter -------------------------

def test_span_times_annotates_and_names_compiles():
    """``span`` observes its block's milliseconds into the histogram, goes
    through the given annotate hook (else a profiler annotation), and
    counts an XLA compile under the innermost open span."""
    outer, inner = Histogram("outer"), Histogram("inner")
    names = []

    def annotate(name):
        names.append(name)
        return contextlib.nullcontext()
    before = compile_counts().get("inner", 0)
    with span("outer", outer, annotate):
        with span("inner", inner):
            jax.jit(lambda x: x * 3.0 + 1.0)(np.ones(5, np.float32) * 7)
            time.sleep(0.002)
    assert names == ["outer"]
    assert outer.count == inner.count == 1
    assert outer.sum >= inner.sum >= 2.0
    assert compile_counts()["inner"] > before


def _drive(eng, requests):
    """Submit ``requests`` and step to idle; returns the ticks run."""
    for r in requests:
        eng.add_request(**r)
    ticks = 1
    while eng.step():
        ticks += 1
    return ticks


def test_tick_spans_on_paged_engine_with_paging_bank(rt):
    """A paged engine over a store-paged bank smaller than its tenants:
    every span of ``SPANS`` is recorded, one ``launch`` and one ``commit``
    per tick, one ``sync`` per decode commit, final prompt chunk and
    page-in, one ``page_in`` per bank miss that paged, and the host's
    waits fit inside the tick's two halves."""
    store, _ = _tenant_store(rt, n_ad=4)
    eng = PagedServeEngine(rt.attach(store, hbm_budget=2), max_batch=2,
                           max_len=40, eos_id=-1, page_size=4,
                           prefill_chunk=8)
    ticks = _drive(eng, [{"prompt": list(range(1, 12 + i)),
                          "max_new_tokens": 3, "adapter": f"a{i % 4}"}
                         for i in range(6)])
    st = eng.span_stats()
    assert set(SPANS) <= {n for n, s in st.items() if s["count"] > 0}
    assert st["launch"]["count"] == st["commit"]["count"] == ticks
    bank = eng.rt.bank.counters
    page_ins = bank["misses"] - bank["stalls"]
    assert page_ins > 0
    assert st["page_in"]["count"] == page_ins
    assert st["sync"]["count"] == (eng.stats["decode_steps"]
                                   + eng.stats["prefills"] + page_ins)
    assert st["sync"]["ms"] <= st["launch"]["ms"] + st["commit"]["ms"]
    ad = eng.adapter_stats()
    assert ad["page_in_count"] == page_ins
    assert ad["page_in_ms_sum"] == pytest.approx(st["page_in"]["ms"])


def test_cluster_span_stats_sum_replicas(rt):
    store, _ = _tenant_store(rt, n_ad=2)
    engines = [PagedServeEngine(rt.attach(store, hbm_budget=2), max_batch=2,
                                max_len=40, eos_id=-1, page_size=4,
                                prefill_chunk=8) for _ in range(2)]
    cl = EngineCluster(engines)
    _drive(cl, [{"prompt": [3, 4, 5, 6, 7], "max_new_tokens": 3,
                 "adapter": f"a{i % 2}"} for i in range(4)])
    per = [e.span_stats() for e in engines]
    assert all(p["launch"]["count"] > 0 for p in per)
    total = cl.span_stats()
    for name in SPANS:
        assert total[name]["count"] == sum(p[name]["count"] for p in per)
        assert total[name]["ms"] == pytest.approx(
            sum(p[name]["ms"] for p in per))
    assert cl.adapter_stats()["page_in_count"] == total["page_in"]["count"]


def test_first_decode_compiles_under_decode_and_repeats_do_not(rt):
    """The compile counter names the step that compiled: the first decode
    dispatch compiles under ``decode``; a tick identical to the one
    before it compiles nothing anywhere."""
    eng = PagedServeEngine(rt, max_batch=3, max_len=24, eos_id=-1,
                           page_size=4, prefill_chunk=8)
    eng.add_request([1, 2, 3, 4, 5], max_new_tokens=8)
    before = compile_counts().get("decode", 0)
    while eng.stats["decode_steps"] == 0:
        eng.step()
    assert compile_counts()["decode"] > before
    eng.step()
    settled = compile_counts()
    eng.step()
    assert compile_counts() == settled


# -- stall attribution --------------------------------------------------------

def test_adapter_stall_attribution(rt):
    """More concurrent tenants than the paged bank admits: the engine must
    record ``adapter`` stalls on the queue head (and nothing spurious)."""
    store, bank_peft = _tenant_store(rt, n_ad=4)
    reg = MetricsRegistry()
    tracer = TraceRecorder(registry=reg)
    eng = ServeEngine(rt.attach(store, hbm_budget=3), max_batch=4,
                      max_len=32, eos_id=-1, tracer=tracer)
    for i in range(8):
        eng.add_request([1, 2, 3, 4], max_new_tokens=4,
                        adapter=f"a{i % 4}")
    eng.run()
    assert eng.stats["admission_stalls"] > 0, "workload failed to stall"
    snap = reg.snapshot(prefix="trace/")
    assert snap["trace/stalls_adapter"] == eng.stats["admission_stalls"]
    stalled = [tr for tr in tracer.finished if tr.stalls.get("adapter")]
    assert stalled, "no finished trace carries the adapter stall"
    assert all(set(tr.stalls) <= {"adapter", "queue", "kv"}
               for tr in tracer.finished)


# -- SLO thresholds + backpressure --------------------------------------------

def _fake_trace(rid, ttft_s, t0=0.0):
    return RequestTrace(engine="e0", rid=rid, t_submit=t0,
                        t_first=t0 + ttft_s, t_finish=t0 + ttft_s + 0.01,
                        prefill_spans=[(t0, t0 + ttft_s / 2)],
                        token_times=[t0 + ttft_s, t0 + ttft_s + 0.01])


def test_slo_threshold_transitions_fire_once():
    slo = SLOMonitor(window=4, thresholds={"ttft_ms.p95": 50.0})
    fired = {"breach": 0, "clear": 0}
    slo.on_breach(lambda m, v, lim: fired.__setitem__(
        "breach", fired["breach"] + 1))
    slo.on_clear(lambda m, v, lim: fired.__setitem__(
        "clear", fired["clear"] + 1))
    rid = 0
    for _ in range(3):                          # healthy: 10ms TTFT
        slo.observe(_fake_trace(rid, 0.010)); rid += 1
    assert fired == {"breach": 0, "clear": 0} and not slo.any_breached
    for _ in range(4):                          # saturate window with 100ms
        slo.observe(_fake_trace(rid, 0.100)); rid += 1
    assert slo.any_breached and slo.report()["breached"] == ["ttft_ms.p95"]
    assert fired["breach"] == 1, "breach callback must fire on transition only"
    for _ in range(4):                          # recover: flush the window
        slo.observe(_fake_trace(rid, 0.010)); rid += 1
    assert not slo.any_breached
    assert fired == {"breach": 1, "clear": 1}


def test_cluster_backpressure_wiring(rt):
    from repro.distrib import EngineCluster
    slo = SLOMonitor(window=4, thresholds={"ttft_ms.p95": 50.0})
    cl = EngineCluster([ServeEngine(rt, max_batch=2, max_len=32, eos_id=-1)],
                       slo=slo)
    assert cl.accepting
    for rid in range(4):
        slo.observe(_fake_trace(rid, 0.100))
    assert not cl.accepting, "SLO breach must stop admission"
    for rid in range(4, 8):
        slo.observe(_fake_trace(rid, 0.010))
    assert cl.accepting, "clearing the breach must re-admit"
    assert cl.cluster_stats()["slo"]["total_requests"] == 8


# -- bench-regression gate ----------------------------------------------------

def _write_suite(root, suite, latest, prior):
    """BENCH file shaped like common.write_summary: history = prior runs
    plus a ts-stamped mirror of latest."""
    history = [dict(e, ts=f"2026-01-0{i + 1}T00:00:00+00:00")
               for i, e in enumerate(prior)]
    history.append(dict(latest, ts="2026-02-01T00:00:00+00:00"))
    (root / f"BENCH_{suite}.json").write_text(
        json.dumps({"latest": latest, "history": history}))


def test_check_regress_passes_and_fails(tmp_path, capsys):
    base = {"a_tok_s": 100.0, "b_tok_s": 200.0, "x_speedup": 2.0,
            "tokens_equal": True}
    _write_suite(tmp_path, "ok", dict(base), [dict(base)] * 3)
    assert check_regress.main(["--root", str(tmp_path)]) == 0

    # one absolute key collapses while its sibling holds -> fail
    bad = dict(base, a_tok_s=40.0)
    _write_suite(tmp_path, "ok", bad, [dict(base)] * 3)
    assert check_regress.main(["--root", str(tmp_path)]) == 1
    assert "a_tok_s" in capsys.readouterr().out


def test_check_regress_normalizes_machine_speed(tmp_path):
    base = {"a_tok_s": 100.0, "b_tok_s": 200.0, "x_speedup": 2.0}
    # uniformly half as fast (slower CI box): normalized gate passes,
    # absolute comparison fails
    slow = {"a_tok_s": 50.0, "b_tok_s": 100.0, "x_speedup": 2.0}
    _write_suite(tmp_path, "m", slow, [dict(base)] * 3)
    assert check_regress.main(["--root", str(tmp_path)]) == 0
    assert check_regress.main(
        ["--root", str(tmp_path), "--no-normalize"]) == 1


def test_check_regress_ratio_keys_compared_raw(tmp_path):
    # machine got 2x faster but the speedup RATIO collapsed: the
    # dimensionless key must not be excused by the machine factor
    base = {"a_tok_s": 100.0, "b_tok_s": 200.0, "x_speedup": 2.0}
    fast_but_flat = {"a_tok_s": 200.0, "b_tok_s": 400.0, "x_speedup": 1.0}
    _write_suite(tmp_path, "r", fast_but_flat, [dict(base)] * 3)
    assert check_regress.main(["--root", str(tmp_path)]) == 1


def test_check_regress_equality_drift_fails(tmp_path):
    base = {"a_tok_s": 100.0, "tokens_equal": True}
    drifted = {"a_tok_s": 100.0, "tokens_equal": False}
    _write_suite(tmp_path, "eq", drifted, [dict(base)] * 3)
    assert check_regress.main(["--root", str(tmp_path)]) == 1


def test_check_regress_no_history_is_vacuous(tmp_path):
    latest = {"a_tok_s": 1.0}
    (tmp_path / "BENCH_new.json").write_text(json.dumps(
        {"latest": latest, "history": [dict(latest, ts="2026-01-01")]}))
    assert check_regress.main(["--root", str(tmp_path)]) == 0
