"""End-to-end metrics of a serving window, taken by the benchmark from its
own clock: every request due in the window counts, timed from its due
time."""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np


def percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def serve_latencies(w) -> Dict[str, List[float]]:
    """Per request: time to first token from due time (a request with no
    first token counts with the time it had waited when the run gave up),
    and time per output token after the first."""
    ttft, tpot = [], []
    p = w.probe
    for r in w.requests:
        due = w.t0 + r.due
        first = p.t_first.get(r.idx)
        ttft.append((first if first is not None else w.t_drained) - due)
        done = p.t_done.get(r.idx)
        n = len(p.token_times.get(r.idx, ()))
        if first is not None and done is not None and n >= 2:
            tpot.append((done - first) / (n - 1))
    return {"ttft": ttft, "tpot": tpot}


def failed(w) -> int:
    """Requests due in the window that never finished."""
    return sum(1 for r in w.requests if r.idx not in w.probe.t_done)


def output_tokens_in_window(w) -> int:
    n = 0
    for r in w.requests:
        for t in w.probe.token_times.get(r.idx, ()):
            if w.t0 <= t <= w.t_end:
                n += 1
    return n


def serve_end_to_end(w) -> Dict[str, float]:
    lat = serve_latencies(w)
    out = {"output_tok_s": output_tokens_in_window(w) / w.seconds}
    tpot = percentile(lat["tpot"], 95)
    if tpot is not None:
        out["tpot_p95_ms"] = tpot * 1e3
    return out


def lateness(w) -> Dict[str, float]:
    """How late the generator sent each request after its due time."""
    late = [w.sent[r.idx] - (w.t0 + r.due) for r in w.requests
            if r.idx in w.sent]
    if not late:
        return {}
    return {"late_p50_ms": percentile(late, 50) * 1e3,
            "late_p95_ms": percentile(late, 95) * 1e3,
            "late_max_ms": max(late) * 1e3}


def finite(x: float) -> bool:
    return x is not None and math.isfinite(x)


def load_report(w) -> Dict[str, float]:
    """Whether the system kept up with the offered load: the tokens asked
    for and served per second, the requests still without a first token
    when the window closed, how long the drain took, and the median time
    to first token of the requests due in each half of the window (past
    the knee the second half waits longer than the first)."""
    offered = sum(r.max_new for r in w.requests) / w.seconds
    backlog = sum(1 for r in w.requests
                  if w.probe.t_first.get(r.idx, float("inf")) > w.t_end)
    ttft = serve_latencies(w)["ttft"]
    half = [r.due < w.seconds / 2 for r in w.requests]
    return {"offered_tok_s": offered,
            "served_tok_s": output_tokens_in_window(w) / w.seconds,
            "backlog_at_close": backlog, "requests": len(w.requests),
            "drain_s": w.t_drained - w.t_end,
            "ttft_p50_first_half_ms": percentile(
                [x for x, h in zip(ttft, half) if h], 50) * 1e3,
            "ttft_p50_second_half_ms": percentile(
                [x for x, h in zip(ttft, half) if not h], 50) * 1e3}
