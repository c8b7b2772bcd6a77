"""The readers of the program's tick spans, on hand-built windows, and
the trace reduction given the program's span names: a trace recorded
before the program had them reads the same, and an idle gap goes to the
innermost program span open at its middle."""
import gzip
import json
import pathlib
import shutil
import types

import pytest

from harness import cells, serve, trace
from repro.obs.trace import SPANS

DATA = pathlib.Path(__file__).parent / "data" / "serve_step.xplane.pb.gz"


def window(counters, kind="serve"):
    return serve.Window(seconds=51.0, t0=0.0, t_end=51.0, t_drained=60.0,
                        requests=[], probe=None, sent={}, counters=counters,
                        config={}, max_batch=32, kind=kind)


def reader(name):
    return cells.load_reader(name)


def test_tick_host_ms_is_host_time_per_tick():
    w = window({"launch_ms": 900.0, "launch_count": 100,
                "commit_ms": 3400.0, "commit_count": 100,
                "sync_ms": 3300.0, "sync_count": 230})
    assert reader("tick_host_ms")(w) == pytest.approx(10.0)


def test_adapter_page_in_ms_is_mean_page_in():
    w = window({"page_in_ms_sum": 42.0, "page_in_count": 3})
    assert reader("adapter_page_in_ms")(w) == pytest.approx(14.0)


@pytest.mark.parametrize("counters", [
    {},                                              # no span counters
    {"launch_ms": 0.0, "launch_count": 0, "commit_ms": 0.0,
     "sync_ms": 0.0, "page_in_ms_sum": 0.0, "page_in_count": 0},
], ids=["absent", "no-ticks-no-page-ins"])
def test_readers_give_none_without_samples(counters):
    for name in ("tick_host_ms", "adapter_page_in_ms"):
        assert reader(name)(window(counters)) is None
        assert reader(name)(window(counters, kind="train")) is None


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_program_spans_leave_the_recorded_trace_unchanged(trace_file,
                                                          monkeypatch):
    with open(cells.BENCH / "peaks.json") as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    base = trace.summarize_file(trace_file, peaks)
    monkeypatch.setattr(trace, "HOST_SPANS", trace.HOST_SPANS + tuple(
        s for s in SPANS if s not in trace.HOST_SPANS))
    wide = trace.summarize_file(trace_file, peaks)
    assert wide == base
    assert wide.breakdown() == base.breakdown()


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start)


def test_idle_gap_goes_to_innermost_program_span(monkeypatch):
    """cluster_step > commit > sync on the host; the device idles during
    the sync and, later, outside every program span."""
    host = [_event("cluster_step", 0, 100), _event("commit", 40, 90),
            _event("sync", 50, 80)]
    pd = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(events=host)])])
    busy = [(0, 55), (75, 95)]
    monkeypatch.setattr(trace, "HOST_SPANS", trace.HOST_SPANS + tuple(
        s for s in SPANS if s not in trace.HOST_SPANS))
    gaps = trace._blame(pd, busy, (0, 100))
    assert gaps == pytest.approx({"sync": 20e-9, "cluster_step": 5e-9})
