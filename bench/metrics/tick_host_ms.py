"""Scheduler: the host's own time per engine tick, in ms: the tick's
``launch`` and ``commit`` halves less the time they spent waiting on the
device (``sync``), over the window and its drain. The chip can fill this
time only with work queued before it. Reads the program's tick-span
counters (``repro.obs.trace.SPANS`` through ``EngineCluster.span_stats``)
from the run's counters; None where the run carries none."""


def read(w):
    if w.kind != "serve":
        return None
    c = w.counters
    ticks = c.get("launch_count")
    if not ticks:
        return None
    return (c["launch_ms"] + c["commit_ms"] - c["sync_ms"]) / ticks
