#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 15

For each seed, in one process: serve the cell as a benchmark run does (a
shorter window), then judge the same sample of completed requests twice
through ``check.judge`` against the configuration's limit: once with the
program's served tokens, and once with the float8-weight control in the
program's place (the reference with its weights rounded to float8; at
each served position, the gap of the token the control puts first).  One
JSON line per seed on standard output, each verdict with its ``correct``.
The benchmark's own runs never run the control; the limit in the
configuration file lies between the largest program reading and the
smallest control reading (PERF.md).
"""
import argparse
import dataclasses
import gc
import json
import time

import run as bench_run  # puts the checkout on sys.path

from bench import check, harness, spec  # noqa: E402


def readings(cell, seed: int, seconds: float, devices) -> dict:
    t0 = time.perf_counter()
    run, served = harness.serve_cell(
        cell, seed, seconds, peaks=spec.peaks(devices[0].device_kind),
        t_process=t0)
    limit = cell.config["check"]["max_logit_gap"]
    ref = check.reference(cell, seed)
    program = check.judge(ref.served_gaps, served, seed, limit)
    control = check.judge(lambda p, t: ref.control_gaps(p, t)[1], served,
                          seed, limit)
    del ref
    gc.collect()
    return {"seed": seed, "program": dataclasses.asdict(program),
            "control": dataclasses.asdict(control),
            "window_requests_done": len(run.window.done),
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    devices = bench_run.accelerator(cell.chips)
    bench_run.use_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, devices)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
