#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate the system keeps
up with, one process per rate (a second engine built in the same process
does not fit beside what the first left on the chip).

    for r in 0.6 0.8 1.0; do python3 bench/sweep.py \
        --workload qwen2-72b.chat --rate $r --seconds 100 --seed 5; done

The rate replaces the mix's ``rate_per_s`` for one run of the cell
(same seed, same set-up, no reference check) and prints one JSON line:
the end-to-end metrics, the tokens per second offered and served, the
requests still waiting for a first token when the window closed, the
drain's seconds, and the median time to first token of the requests due
in each half of the window. Below the knee the served rate follows the
offered one, the backlog stays near zero and the two halves wait alike;
past it the queue grows all through the window, and the second half
waits longer. Use a window of several requests' lifetimes. A cell's fixed rate is set once from such a sweep, at about four
fifths of the knee, and recorded in PERF.md; runs never search.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True, help="req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
    from harness import cells, device
    device.use_checkout_cache()
    cell = cells.load_cell(args.workload)
    try:
        dev = device.require_chips(cell.chips)
    except device.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    device.enable_cache()
    from harness import runner
    c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                               rate_per_s=args.rate))
    res = runner.run_serve(c, args.seed, args.seconds, False, T_START, dev,
                           check=False)
    print(json.dumps({"rate_per_s": args.rate, **res["load"],
                      "failed": res["failed"],
                      "metrics": {k: v["value"]
                                  for k, v in res["metrics"].items()}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
