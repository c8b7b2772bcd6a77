#!/usr/bin/env python3
"""Readings for a cell's correctness limits, a few seeds in one process
(each seed builds the engine anew, and the chip's peak climbs with each).

    python3 bench/control.py --workload qwen2-72b.chat --seeds 1,2,3 \
        --seconds 20 --control fp8-wa

For each seed it makes one run as ``run.py`` does (short window at the
cell's own load) and prints one JSON line: the numbers the run compares,
and beside them each control's, judged by the same limits: the reference
computed one precision below the configuration's and put in the
program's place (``--control``: ``fp8-wa``, weights and activations in
fp8, below bf16; ``int4-wa`` below int8; without ``-wa`` only the
weights are rounded). The program's readings over a dozen seeds or more give
each limit's lower end, the controls' its upper end. The benchmark's own
runs never run a control.
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
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="fp8-wa",
                    help="comma-separated precisions of the controls "
                         "(reference.qwen2.QMAX, '-wa' to round the "
                         "activations too); 'none' reads the program only")
    ap.add_argument("--precision", default=None,
                    help="run the program's own base path at this "
                         "precision (int8 below bf16) as the control, "
                         "judged against the reference at the "
                         "configuration's precision")
    ap.add_argument("--fault", default=None,
                    help="plant a fault under the timed path "
                         "(harness.faults.FAULTS)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
    from harness import cells, device
    device.use_checkout_cache()
    cell = cells.load_cell(args.workload)
    try:
        dev = device.require_chips(cell.chips)
    except device.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    device.enable_cache()
    from harness import faults, runner
    if args.fault:
        faults.FAULTS[args.fault]()
    run = (runner.run_serve if cell.traffic["kind"] == "serve"
           else runner.run_train)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = run(cell, seed, args.seconds, False, t0, dev,
                  controls=() if args.control == "none"
                  else tuple(args.control.split(",")),
                  **({"precision": args.precision} if args.precision
                     else {}))
        print(json.dumps({
            "seed": seed, "fault": args.fault, "precision": args.precision,
            "correct": res["correct"],
            "program": res["readings"],
            "control": res.get("control"),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
