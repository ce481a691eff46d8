"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows AND persists one machine-
readable ``BENCH_<suite>.json`` per suite at the repo root (timestamp,
backend/config, per-benchmark numbers) so the perf trajectory is tracked
across PRs instead of vanishing into stdout.

    python -m benchmarks.run                       # every suite
    python -m benchmarks.run pipeline ckpt         # a subset (by suite name)
    python -m benchmarks.run --smoke pipeline      # smallest configs only

Modules:
  bench_ratio       Table II   (compression ratio, 10 datasets, baselines)
  bench_throughput  Fig. 9     (CPU measured + TPU roofline projection)
  bench_blocksize   Fig. 11/12 + Table VI (block/input size sweeps)
  bench_ablation    Fig. 13    (V0 -> V3)
  bench_params      Table IV   (searched params + Eq. 4 formula check)
  bench_transfer    Table V    (parameter transferability)
  bench_pipeline    ISSUE 1/4  (whole-tree compress AND decompress:
                                per-layer vs stacked)
  bench_e2e         Fig. 10    (TTFT/TPOT dense vs ENEC-streamed + derived)
  bench_serve       ISSUE 2    (TTFT/TPOT/tok-s across weight-execution modes)
  bench_overlap     ISSUE 7    (decode-prefetch pipeline: decode_ms vs
                                matmul_ms, overlapped vs serial TPOT)
  bench_ckpt        ISSUE 3/4  (enec-v2 save/load + restore wall clock +
                                decode dispatch accounting)
  bench_faults      ISSUE 6    (restore latency under injected fault rates:
                                transient I/O, decode failure, corruption)
  bench_mesh        ISSUE 8    (sharded vs replicated restore, per-link
                                ledger: collective traffic = compressed
                                bytes only; needs a multi-device mesh)
  bench_traffic     ISSUE 9    (Poisson load against the continuous-batching
                                engine: served tok/s vs offered load,
                                p50/p99 TTFT/TPOT, shed/evicted/rejected
                                accounting, one-shot logit parity)
  bench_moe         ISSUE 10   (expert streaming: tok/s + p50/p99 TPOT vs
                                expert-cache budget 0/25/100%, hit-rate
                                curves skewed vs uniform routing, logit
                                parity + dispatch-bound gates)
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import traceback
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

SUITE_ORDER = ["ratio", "throughput", "blocksize", "ablation", "params",
               "transfer", "pipeline", "e2e", "serve", "overlap", "ckpt",
               "faults", "mesh", "traffic", "moe"]


def _env_flag(name: str) -> bool:
    """A truthy env flag: unset, "", "0", "false", "no", "off" are all
    False.  (``bool(os.environ.get(...))`` counted ``BENCH_SMOKE=0`` as
    smoke, so full-config runs got recorded as smoke artifacts.)"""
    return os.environ.get(name, "").strip().lower() \
        not in ("", "0", "false", "no", "off")


def _suite_name(mod_name: str) -> str:
    return mod_name.rsplit(".", 1)[-1].removeprefix("bench_")


def write_suite_json(suite: str, rows, error: str = None,
                     out_dir: Path = REPO_ROOT) -> Path:
    """Persist one suite's rows as ``BENCH_<suite>.json`` (the artifact CI
    uploads and the perf-trajectory record across PRs)."""
    import jax

    doc = {
        "suite": suite,
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "config": {
            "jax_backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "python": sys.version.split()[0],
            "smoke": _env_flag("BENCH_SMOKE"),
        },
        "results": [{"name": name, "us_per_call": round(us, 1),
                     "derived": derived} for name, us, derived in rows],
    }
    if error is not None:
        doc["error"] = error
    path = Path(out_dir) / f"BENCH_{suite}.json"
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("suites", nargs="*",
                    help="suite names to run (default: all); accepts "
                         "'pipeline' or 'bench_pipeline'")
    ap.add_argument("--smoke", action="store_true",
                    help="smallest configs only (sets BENCH_SMOKE=1; the "
                         "CI bench-smoke job uses this)")
    ap.add_argument("--out-dir", default=str(REPO_ROOT),
                    help="where BENCH_<suite>.json files land "
                         "(default: repo root)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
        # every suite is expected to have a committed baseline at the repo
        # root for cross-PR comparison; flag the ones that don't so a new
        # suite can't silently ship without one
        missing = [s for s in SUITE_ORDER
                   if not (REPO_ROOT / f"BENCH_{s}.json").exists()]
        if missing:
            print(f"[benchmarks.run] suites missing a committed baseline "
                  f"at {REPO_ROOT}: {' '.join(missing)}", file=sys.stderr)

    from . import (bench_ablation, bench_blocksize, bench_ckpt, bench_e2e,
                   bench_faults, bench_mesh, bench_moe, bench_overlap,
                   bench_params, bench_pipeline, bench_ratio, bench_serve,
                   bench_throughput, bench_traffic, bench_transfer)
    by_suite = {_suite_name(m.__name__): m for m in
                [bench_ratio, bench_throughput, bench_blocksize,
                 bench_ablation, bench_params, bench_transfer,
                 bench_pipeline, bench_e2e, bench_serve, bench_overlap,
                 bench_ckpt, bench_faults, bench_mesh, bench_traffic,
                 bench_moe]}
    wanted = [s.removeprefix("bench_") for s in args.suites] or SUITE_ORDER
    unknown = [s for s in wanted if s not in by_suite]
    if unknown:
        raise SystemExit(f"unknown suites {unknown}; "
                         f"expected a subset of {SUITE_ORDER}")

    print("name,us_per_call,derived")
    failed = 0
    for suite in wanted:
        mod = by_suite[suite]
        rows = []   # accumulated incrementally so a mid-suite failure still
        try:        # records every completed benchmark, not an empty file
            for row in mod.run():
                rows.append(row)
                name, us, derived = row
                print(f"{name},{us:.1f},{derived}")
            write_suite_json(suite, rows, out_dir=Path(args.out_dir))
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{mod.__name__},ERROR,{type(e).__name__}: {e}",
                  file=sys.stderr)
            traceback.print_exc()
            write_suite_json(suite, rows, error=f"{type(e).__name__}: {e}",
                             out_dir=Path(args.out_dir))
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
