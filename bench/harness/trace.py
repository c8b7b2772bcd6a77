"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
kernel time with each call's operations and bytes, and a breakdown.

Each TPU op event is named by its HLO instruction, result and operand
shapes included, so a Pallas kernel (``custom_call_target=
"tpu_custom_call"``) is recognised by its operands and its least time is
worked out from those shapes (``flops.kernel_cost``). The window is the
stretch between the end of ``start_trace`` and the start of ``stop_trace``
on the host's Python line, where both exist; host spans and device ops
share the trace's clock (nanoseconds from the profile's start).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

from . import flops

SHAPE = re.compile(r"\b(pred|s8|u8|s32|u32|s64|bf16|f16|f32|f8e4m3fn)"
                   r"\[([\d,]*)\]")
#: host spans a gap can be blamed on: the harness's and the program's
HOST_SPANS = ("decode", "prefill_chunk", "prefill", "cluster_step",
              "add_request", "wait_for_arrival", "train_step", "feed")


#: ops whose event spans the ops of their body (counted once, in the body)
CONTAINERS = ("while", "conditional", "call")


def parse_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in SHAPE.finditer(text)]


def split_custom_call(name: str):
    """(results, operands) of a ``custom-call`` op's HLO text."""
    body = name.split(" = ", 1)[1]
    head, rest = body.split(" custom-call(", 1)
    operands = rest.split("), custom_call_target", 1)[0]
    return parse_shapes(head), parse_shapes(operands)


def op_label(name: str) -> str:
    """A short, stable label of one device op: a kernel's class, or the
    HLO instruction's opcode with its result shape."""
    if "tpu_custom_call" in name:
        res, ops = split_custom_call(name)
        return flops.classify(res, ops)
    try:
        lhs, body = name.split(" = ", 1)
    except ValueError:
        return name[:60]
    inst = re.sub(r"\.\d+$", "", lhs.strip().lstrip("%"))
    shapes = parse_shapes(body.split("(", 1)[0])
    shape = "" if not shapes else f"{shapes[0][0]}{list(shapes[0][1])}"
    return f"{inst} {shape}".strip()


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


@dataclasses.dataclass
class KernelTotals:
    calls: int = 0
    seconds: float = 0.0          # summed device time
    least_seconds: float = 0.0    # summed roofline time, where shapes say
    flops: float = 0.0
    bytes: float = 0.0


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over the chips traced
    chips: int
    kernels: Dict[str, KernelTotals]
    modules: Dict[str, int]       # executions of each jitted program
    ops: Dict[str, float]         # device seconds by op label
    gaps: Dict[str, float]        # idle seconds by the host span open then

    def breakdown(self) -> Dict[str, List[List]]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _window(pd) -> Optional[Tuple[float, float]]:
    start = stop = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if line.name != "python":
                continue
            for e in line.events:
                if e.name.endswith("start_trace"):
                    start = e.start_ns + e.duration_ns
                elif e.name.endswith("stop_trace"):
                    stop = e.start_ns
    if start is None or stop is None or stop <= start:
        return None
    return start, stop


def summarize(logdir: str, peaks: Dict[str, float], chips: int = 1
              ) -> Summary:
    """Reduce the newest trace under ``logdir``."""
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return summarize_file(paths[-1], peaks, chips)


def summarize_file(path: str, peaks: Dict[str, float], chips: int = 1
                   ) -> Summary:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    win = _window(pd)
    kernels: Dict[str, KernelTotals] = {}
    modules: Dict[str, int] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    busy_all: List[List[Tuple[float, float]]] = []
    lo = hi = None
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        if int(plane.name.rsplit(":", 1)[1]) >= chips:
            continue
        ivs = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    if win and not (win[0] <= e.start_ns < win[1]):
                        continue
                    key = e.name.split("(", 1)[0]
                    modules[key] = modules.get(key, 0) + 1
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                a0, b0 = e.start_ns, e.start_ns + e.duration_ns
                a, b = a0, b0
                if win:
                    a, b = max(a, win[0]), min(b, win[1])
                    if b <= a:
                        continue
                ivs.append((a, b))
                lo = a if lo is None else min(lo, a)
                hi = b if hi is None else max(hi, b)
                label = op_label(e.name)
                dur = (b - a) * 1e-9
                if label.split(" ")[0] not in CONTAINERS:
                    ops[label] = ops.get(label, 0.0) + dur
                # a kernel cut by the window's edge would count its whole
                # least time against part of its run: leave it out
                if "tpu_custom_call" in e.name and (a, b) == (a0, b0):
                    res, opnds = split_custom_call(e.name)
                    kind = flops.classify(res, opnds)
                    k = kernels.setdefault(kind, KernelTotals())
                    k.calls += 1
                    k.seconds += dur
                    cost = flops.kernel_cost(kind, res, opnds)
                    if cost is not None:
                        k.flops += cost[0]
                        k.bytes += cost[1]
                        k.least_seconds += flops.least_time(cost, peaks)
        merged = _union(ivs)
        busy_all.append(merged)
        busy_total += sum(b - a for a, b in merged)
    if win is None:
        if lo is None:
            raise ValueError(f"no device ops in {path}")
        win = (lo, hi)
    window_s = (win[1] - win[0]) * 1e-9
    n = max(1, len(busy_all))
    gaps = _blame(pd, busy_all[0] if busy_all else [], win)
    return Summary(window_s=window_s, busy_s=busy_total * 1e-9 / n,
                   chips=n, kernels=kernels, modules=modules, ops=ops,
                   gaps=gaps)


def _blame(pd, busy: List[Tuple[float, float]], win) -> Dict[str, float]:
    """Idle seconds of the first chip, each gap given to the innermost
    host span open at its middle (``idle`` where none is). The spans
    nest, so the innermost one holding a point is the latest to start."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    spans.sort()
    starts = [s[0] for s in spans]
    edges = [win[0]] + [x for iv in busy for x in iv] + [win[1]]
    out: Dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        name = "idle"
        i = bisect.bisect_right(starts, mid) - 1
        for j in range(i, max(-1, i - 64), -1):
            if spans[j][1] > mid:
                name = spans[j][2]
                break
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def remove(logdir: str) -> None:
    shutil.rmtree(logdir, ignore_errors=True)


def roofline_share(summary: Optional[Summary], *kinds: str
                   ) -> Optional[float]:
    """Least time over device time of the named kernel classes in the
    traced window, in percent; None where none of them ran."""
    if summary is None:
        return None
    secs = least = 0.0
    for k in kinds:
        t = summary.kernels.get(k)
        if t is not None:
            secs += t.seconds
            least += t.least_seconds
    if secs <= 0:
        return None
    return 100.0 * least / secs
