"""repro.obs — the stack's one observability plane.

Three small pieces, one rule: every serving-layer statistic lives in the
process-wide :data:`REGISTRY`, and the pre-existing ``stats()`` surfaces
are thin views over it.

* :mod:`repro.obs.metrics` — typed instruments (Counter / Gauge /
  bounded Histogram) in a :class:`MetricsRegistry`.
* :mod:`repro.obs.trace` — per-request lifecycle spans with TTFT/TPOT
  and stall attribution; JSONL + Chrome ``trace_event`` export; the
  always-on serving-tick ``span`` (histogram + profiler annotation) and
  the per-span XLA compile counter.
* :mod:`repro.obs.slo` — sliding-window percentile monitor with
  threshold callbacks for admission backpressure.

The bench-regression gate lives with the benches it gates:
``benchmarks/check_regress.py``.
"""
from .metrics import (
    DEFAULT_HIST_CAP,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    MetricsScope,
)
from .slo import SLO_PERCENTILES, SLOMonitor
from .trace import (SPANS, STALL_REASONS, RequestTrace, TraceRecorder,
                    compile_counts, span)

__all__ = [
    "DEFAULT_HIST_CAP",
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsScope",
    "SLO_PERCENTILES",
    "SLOMonitor",
    "SPANS",
    "STALL_REASONS",
    "RequestTrace",
    "TraceRecorder",
    "compile_counts",
    "span",
]
