#!/usr/bin/env python3
"""Device time by named program, and idle time by program span, read
from a profiler trace: two reductions that ``bench/xplane.py`` does not
make.

* A program is an event of the ``XLA Modules`` line of a device plane,
  named after its jitted function and the hash of one compiled variant:
  ``jit_engine_decode_step(<hash>)``.  :func:`program_name` strips both, so
  the window's executions of every variant of one program sum under one
  name (``engine_decode_step``).
* Each idle gap of the device in the window (``xplane``'s busy union, per
  device) goes to the innermost program span covering its midpoint: an
  ``engine.*`` span of ``runtime/engine.py``, a ``bench.*`` span of the
  harness, or ``python.gc``; else to ``(outside program spans)``.  Every
  gap is labelled, the shortest too.

Run as a script, it serves one cell with the profiler on, as ``bench/
run.py --trace 1`` does but with no reference check, and prints one JSON
line: the cell's per-layer metrics, the window's device programs, idle by
span, the engine's span counts and seconds, and the phases of its decode
steps (``Engine.step_phases``), with the window's stalls::

    python3 bench/programs.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

if __name__ == "__main__":
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import xplane  # noqa: E402

MODULES_LINE = "XLA Modules"
OUTSIDE = "(outside program spans)"
_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module_event: str) -> str:
    """``jit_engine_decode_step(123)`` gives ``engine_decode_step``."""
    return _PROGRAM.match(module_event).group(1)


def is_program_span(name: str) -> bool:
    return name.startswith(("engine.", "bench.")) or name == "python.gc"


@dataclasses.dataclass
class ProgramSummary:
    window_s: float
    busy_s: float                      # as xplane.summarize reads it
    module_seconds: Dict[str, float]   # device seconds by program
    module_counts: Dict[str, int]      # executions by program
    idle_by_span: List[Tuple[str, float]]   # idle seconds, largest first

    def programs(self) -> List[List]:
        return [[k, v] for k, v in sorted(self.module_seconds.items(),
                                          key=lambda kv: -kv[1])]


def _device_gaps(plane, w0, w1) -> Tuple[float, list]:
    """Busy nanoseconds of one device plane in the window and its idle
    gaps, the way ``xplane.summarize`` counts them."""
    intervals = []
    for line in plane.lines:
        if line.name != xplane.OPS_LINE:
            continue
        for ev in line.events:
            lo, hi = xplane._clip(ev.start_ns, ev.end_ns, w0, w1)
            if hi > lo and xplane.hlo_op(ev.name)[1] not in \
                    xplane.CONTROL_FLOW:
                intervals.append((lo, hi))
    merged = xplane._merge(intervals)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return sum(hi - lo for lo, hi in merged), gaps


def idle_by_span(gaps, spans, top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds by the innermost program span covering each gap's
    midpoint.  ``gaps``: ``(lo, hi)`` nanoseconds; ``spans``: host spans
    as ``xplane._host_spans`` gives them."""
    import numpy as np

    if not gaps:
        return []
    g = np.asarray(sorted(gaps), np.float64)
    mid, length = 0.5 * (g[:, 0] + g[:, 1]), (g[:, 1] - g[:, 0]) / 1e9
    best = np.full(len(g), np.inf)
    owner = np.full(len(g), -1, np.int64)
    lo, hi, idx, names = spans
    for a, b, i in zip(lo, hi, idx):
        if not is_program_span(names[i]):
            continue
        first, last = np.searchsorted(mid, [a, b], side="left")
        if first == last:
            continue
        shorter = (b - a) < best[first:last]
        best[first:last][shorter] = b - a
        owner[first:last][shorter] = i
    totals: Dict[str, float] = {}
    for i in np.unique(owner):
        name = OUTSIDE if i < 0 else names[i]
        totals[name] = totals.get(name, 0.0) + float(length[owner == i].sum())
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:top]]


def summarize(path, top: int = 10) -> ProgramSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    w0, w1 = xplane._window(pd)
    seconds: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    busy_ns, gaps, devices = 0.0, [], 0
    for plane in pd.planes:
        if not xplane.DEVICE_PLANE.match(plane.name) or not any(
                line.name == xplane.OPS_LINE for line in plane.lines):
            continue
        devices += 1
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                lo, hi = xplane._clip(ev.start_ns, ev.end_ns, w0, w1)
                if hi <= lo:
                    continue
                name = program_name(ev.name)
                seconds[name] = seconds.get(name, 0.0) + (hi - lo) / 1e9
                counts[name] = counts.get(name, 0) + 1
        busy, plane_gaps = _device_gaps(plane, w0, w1)
        busy_ns += busy
        gaps += plane_gaps
    if not devices:
        raise ValueError("no device operations in the trace")
    spans = xplane._host_spans(pd, w0, w1)
    return ProgramSummary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / devices / 1e9,
        module_seconds={k: v / devices for k, v in seconds.items()},
        module_counts={k: v // devices for k, v in counts.items()},
        idle_by_span=idle_by_span(gaps, spans, top))


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import time

    import bench.run as bench_run
    from bench import harness, spec

    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    devices = bench_run.accelerator(cell.chips)
    bench_run.use_cache()
    phases: list = []
    trace_dir = bench_run.TRACE_DIR
    run, _ = harness.serve_cell(
        cell, args.seed, args.seconds,
        peaks=spec.peaks(devices[0].device_kind), t_process=t_process,
        trace_dir=trace_dir,
        engine_hook=lambda engine: phases.append(engine.step_phases))
    path = xplane.find_xplane(trace_dir)
    run.trace = xplane.summarize(path)
    programs = summarize(path)
    shutil.rmtree(trace_dir, ignore_errors=True)

    window = phases[0][-len(run.step_s):] if run.step_s else []
    median = sorted(run.step_s)[len(run.step_s) // 2] if run.step_s else 0
    stalls = [[1e3 * t, p.largest()] for t, p in zip(run.step_s, window)
              if t > harness.STALL_FACTOR * median]
    engine_spans = {k: [run.trace.span_counts[k], v]
                    for k, v in sorted(run.trace.span_seconds.items())
                    if is_program_span(k) and not k.startswith("bench.")}
    result = {
        "metrics": bench_run.per_layer(run, cell.per_layer),
        "device": {"kind": devices[0].device_kind,
                   "busy_s": run.trace.busy_s,
                   "window_s": run.trace.window_s},
        "device_programs": programs.programs(),
        "program_executions": programs.module_counts,
        "idle_by_span": programs.idle_by_span,
        "idle_gaps": run.trace.idle_gaps,
        "engine_spans": engine_spans,
        "step_phases_ms": {
            p: 1e3 * sum(getattr(r, p) for r in window) / max(1, len(window))
            for p in ("admit", "dispatch", "wait", "emit", "gc")},
        "stall_phases": stalls[:harness.STALLS_SHOWN],
        "window": {"seconds": run.window.seconds, "tokens": run.window.tokens,
                   "steps": len(run.step_s),
                   "prefills": len(run.prefill_s)},
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
