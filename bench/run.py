#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic and metrics are found by name under ``bench/``.
The run builds the program's serving stack from the seed, warms every
shape the traffic uses, ramps the closed loop until as many requests have
completed as there are clients (all of that is ``setup_s``), measures
``--seconds``, then checks the window's served tokens against the plain
reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``, each compared number beside its
limit (also the last lines of standard error).  Without an accelerator,
or with fewer chips than the cell asks for, it exits 2 and prints no
result.  JAX's compilation cache is kept in ``.jax_cache`` of the
checkout, the TPU runtime's logs in ``.bench_logs``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import spec  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
LOG_DIR = ROOT / ".bench_logs"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def accelerator(chips: int):
    """The device list, or exit 2 when JAX finds no accelerator or too
    few chips.  The TPU runtime, started here, logs into the checkout."""
    os.environ.setdefault("TPU_LOG_DIR", str(LOG_DIR))
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        log("no accelerator: JAX found only CPU devices")
        raise SystemExit(2)
    if len(devices) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devices)}")
        raise SystemExit(2)
    return devices


def use_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def end_to_end(run) -> dict:
    from bench.window import percentile

    w = run.window
    itl = percentile(w.itl_s, 95)
    values = {
        "out_tok_s": w.out_tok_s,
        "itl_p95_ms": None if itl is None else 1e3 * itl,
        "hbm_in_use_gb": run.hbm_in_use_bytes / 1e9,
        "setup_s": run.setup_s,
    }
    return values


def per_layer(run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        value = spec.metric_module(m["name"]).compute(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            t_process: float = T_PROCESS, engine_hook=None) -> dict:
    """Serve the cell, check it, and build the result line."""
    from bench import check, harness, xplane

    dev = devices[0]
    peaks = spec.peaks(dev.device_kind)
    run, served = harness.serve_cell(
        cell, seed, seconds, peaks=peaks, t_process=t_process,
        trace_dir=TRACE_DIR if trace else None, engine_hook=engine_hook)
    w = run.window
    log(f"window {w.seconds:.3f}s: {w.tokens} tokens, {len(w.ttft_s)} "
        f"first tokens, {len(w.itl_s)} token gaps, {w.attempted} ended, "
        f"{w.failed} failed, {run.compiles_in_window} programs lowered")
    if trace:
        run.trace = xplane.summarize_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    t0 = time.perf_counter()
    verdict = check.judge(check.reference(cell, seed).served_gaps, served,
                          seed, cell.config["check"]["max_logit_gap"])
    log(f"reference check {time.perf_counter() - t0:.1f}s over "
        f"{verdict.requests} requests, {verdict.tokens} tokens")

    if trace:
        metrics = per_layer(run, cell.per_layer)
    else:
        values = end_to_end(run)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": verdict.correct, "attempted": w.attempted,
              "failed": w.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops(),
                               "idle_gaps": run.trace.idle_gaps}
    result["window"] = {"seconds": w.seconds, "tokens": w.tokens,
                        "requests_done": len(w.done),
                        "programs_lowered": run.compiles_in_window,
                        "stalls_ms": run.stalls_ms}
    result["checks"] = verdict.checks()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    devices = accelerator(cell.chips)
    use_cache()
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     devices)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
