"""Find a cell's pieces by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A name in ``BENCHMARK.json`` that has no file, or a malformed file."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """Everything one workload entry names, loaded."""
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file's contents
    traffic_name: str
    traffic: dict         # the traffic file's contents
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else benchmark(root)
    try:
        w = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SpecError(f"no workload named {name!r} in BENCHMARK.json")
    try:
        c = next(c for c in bench["configs"] if c["name"] == w["config"])
    except StopIteration:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=c["name"], config=load_json(root / c["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(root / "bench" / "traffic"
                          / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_module(name: str, root: Path = ROOT):
    """``bench/metrics/<name>.py``; it defines ``compute(run)``."""
    mod = _load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench.metrics._" + name.replace(".", "_")
                       .replace("-", "_"))
    if not callable(getattr(mod, "compute", None)):
        raise SpecError(f"bench/metrics/{name}.py defines no compute()")
    return mod


def reference_module(name: str, root: Path = ROOT):
    """``bench/references/<name>.py``, the plain reference a config names."""
    return _load_module(root / "bench" / "references" / f"{name}.py",
                        "bench.references._" + name.replace(".", "_")
                        .replace("-", "_"))


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
