#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload qwen2-72b.chat --seed 7 --seconds 30 \
        --trace 0

One process is one run: set-up (weights and adapters from the seed,
programs from the compile cache, warm-up), then ``--seconds`` of measured
traffic, then the comparison with the plain reference that decides
``correct``. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each number compared beside its limit. The same checks are the last lines
on standard error.

The run exits non-zero and prints no result when JAX finds no TPU, or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
    from harness import cells, device
    device.use_checkout_cache()
    cell = cells.load_cell(args.workload)
    try:
        dev = device.require_chips(cell.chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    device.enable_cache()
    from harness import runner
    kind = cell.traffic["kind"]
    if kind == "serve":
        result = runner.run_serve(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START, dev)
    elif kind == "train":
        result = runner.run_train(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START, dev)
    else:
        raise ValueError(f"traffic {cell.traffic_name!r} has unknown kind "
                         f"{kind!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
