"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time, device time by operation, and the longest idle
gaps labelled by what the host was doing.

Device operations are the events of the ``XLA Ops`` line of each
``/device:<platform>:<n>`` plane, less the control-flow ops (``while``,
``conditional``, ``call``) whose events enclose the ops of their bodies.
The window is the host span named ``bench.window`` that the harness wraps
around its measured loop; the device and host planes share the
profiler's clock.  An event is named by its HLO instruction
(``%tiled_matmul.3 = f32[32,2560] custom-call(...)``); a Pallas kernel's
instruction keeps the ``name`` it was given, so :func:`kernel_seconds`
finds it by that name with the instruction's numeric suffix stripped.

A kernel's time includes the ops that stage its operands: inside a layer
loop XLA slices each layer's weight (or its ENEC streams) out of the
stacked array into fast memory with a ``...slice...`` op, and the kernel
then reads that copy.  Such a producer, named in the kernel's operand
list within the same program execution, is counted with the kernel, so
the kernel's time holds the weight's read from HBM.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
CONTROL_FLOW = ("while", "conditional", "call")
_SUFFIX = re.compile(r"\.\d+$")
_OPCODE = re.compile(r"([A-Za-z][\w-]*)\(")
_OPERAND = re.compile(r"%([\w.-]+)")


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _operands(event_name: str) -> List[str]:
    """Instruction names in the operand list of an op event."""
    head = event_name.split(" = ", 1)[-1]
    m = _OPCODE.search(head if not head.startswith("(") else
                       head[head.find(") ") + 1:])
    if not m:
        return []
    return _OPERAND.findall(head[m.end():].split("), ", 1)[0])


def hlo_op(event_name: str) -> Tuple[str, str]:
    """``(base name, opcode)`` of a device op event:
    ``%tiled_matmul.3 = f32[32,2560]{1,0} custom-call(...)`` gives
    ``("tiled_matmul", "custom-call")``.  A bare name (``fusion.12``) has
    no opcode."""
    if not event_name.startswith("%") or " = " not in event_name:
        return _SUFFIX.sub("", event_name), ""
    name, rest = event_name[1:].split(" = ", 1)
    if rest.startswith("("):            # a tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    m = _OPCODE.search(rest)
    return _SUFFIX.sub("", name), m.group(1) if m else ""


@dataclasses.dataclass
class TraceSummary:
    window_s: float              # length of the bench.window span
    busy_s: float                # union of device op intervals, per device
    op_seconds: Dict[str, float]  # device time by base op name, per device
    staged_seconds: Dict[str, float]  # operand staging, by kernel base name
    span_seconds: Dict[str, float]   # host events by name, inside the window
    span_counts: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]   # idle seconds by host activity
    devices: int

    def kernel_seconds(self, kernel: str) -> float:
        """Device time of a kernel's calls with their operand staging."""
        return (self.op_seconds.get(kernel, 0.0)
                + self.staged_seconds.get(kernel, 0.0))

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ops[:top]]


def _merge(intervals):
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return out


def _clip(lo, hi, w0, w1):
    return max(lo, w0), min(hi, w1)


def _window(pd) -> Tuple[float, float]:
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    return ev.start_ns, ev.end_ns
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def _host_spans(pd, w0, w1):
    """Host events overlapping the window as numpy arrays (start, end,
    name index) plus the names: the harness's spans and the runtime's own
    host events."""
    import numpy as np

    lo, hi, idx, names, index = [], [], [], [], {}
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns <= 0 or ev.end_ns <= w0 \
                        or ev.start_ns >= w1 or ev.name == WINDOW_SPAN:
                    continue
                if ev.name not in index:
                    index[ev.name] = len(names)
                    names.append(ev.name)
                lo.append(ev.start_ns)
                hi.append(ev.end_ns)
                idx.append(index[ev.name])
    return (np.asarray(lo, np.float64), np.asarray(hi, np.float64),
            np.asarray(idx, np.int64), names)


def _label(gap_lo, gap_hi, spans) -> str:
    """The shortest host span that covers the gap's midpoint: the most
    specific thing the host was doing while the device waited."""
    import numpy as np

    lo, hi, idx, names = spans
    mid = 0.5 * (gap_lo + gap_hi)
    cover = (lo <= mid) & (hi >= mid)
    if not cover.any():
        return "(no host span)"
    length = np.where(cover, hi - lo, np.inf)
    return names[idx[int(np.argmin(length))]]


def _idle_by_activity(gaps, spans, top, labelled=2000):
    """Idle seconds summed by host activity over the longest ``labelled``
    gaps, largest first; the rest of the idle time as one entry."""
    totals: Dict[str, float] = {}
    for lo, hi in gaps[:labelled]:
        name = _label(lo, hi, spans)
        totals[name] = totals.get(name, 0.0) + (hi - lo) / 1e9
    rest = sum(hi - lo for lo, hi in gaps[labelled:]) / 1e9
    if rest:
        totals["(shorter gaps)"] = rest
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:top]]


def _staging(events, modules) -> Dict[str, float]:
    """Nanoseconds of ``...slice...`` producers named as operands of
    custom calls, by the calling kernel's base name.  ``events`` are
    ``(lo, hi, name, opcode, text)`` of one device; ``modules`` the sorted
    start times of its program executions.  Each producer event counts
    once."""
    import bisect

    by_exec: Dict[Tuple[int, str], List[float]] = {}
    for lo, hi, name, _op, text in events:
        full = text[1:].split(" = ", 1)[0] if text.startswith("%") else text
        key = (bisect.bisect_right(modules, lo), full)
        by_exec.setdefault(key, []).append(hi - lo)
    staged: Dict[str, float] = {}
    used = set()
    for lo, _hi, name, opcode, text in events:
        if opcode != "custom-call":
            continue
        ex = bisect.bisect_right(modules, lo)
        for operand in _operands(text):
            key = (ex, operand)
            if "slice" not in operand or key in used or key not in by_exec:
                continue
            used.add(key)
            staged[name] = staged.get(name, 0.0) + sum(by_exec[key])
    return staged


def summarize(path, top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    w0, w1 = _window(pd)
    op_ns: Dict[str, float] = {}
    staged_ns: Dict[str, float] = {}
    busy_ns = 0.0
    devices = 0
    gaps = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = [line for line in plane.lines if line.name == OPS_LINE]
        if not ops:
            continue
        devices += 1
        modules = sorted(ev.start_ns for line in plane.lines
                         if line.name == "XLA Modules" for ev in line.events)
        events = []
        for line in ops:
            for ev in line.events:
                lo, hi = _clip(ev.start_ns, ev.end_ns, w0, w1)
                if hi <= lo:
                    continue
                name, opcode = hlo_op(ev.name)
                if opcode in CONTROL_FLOW:
                    continue
                events.append((lo, hi, name, opcode, ev.name))
                op_ns[name] = op_ns.get(name, 0.0) + (hi - lo)
        for k, v in _staging(events, modules).items():
            staged_ns[k] = staged_ns.get(k, 0.0) + v
        merged = _merge([(lo, hi) for lo, hi, *_ in events])
        busy_ns += sum(hi - lo for lo, hi in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    if not devices:
        raise ValueError("no device operations in the trace")
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = _host_spans(pd, w0, w1)
    idle = _idle_by_activity(gaps, spans, top) if gaps else []
    span_ns: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    lo, hi, idx, names = spans
    for a, b, i in zip(lo.clip(w0, w1), hi.clip(w0, w1), idx):
        span_ns[names[i]] = span_ns.get(names[i], 0.0) + float(b - a)
        span_n[names[i]] = span_n.get(names[i], 0) + 1
    return TraceSummary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / devices / 1e9,
        op_seconds={k: v / devices / 1e9 for k, v in op_ns.items()},
        staged_seconds={k: v / devices / 1e9 for k, v in staged_ns.items()},
        span_seconds={k: v / 1e9 for k, v in span_ns.items()},
        span_counts=span_n, idle_gaps=idle, devices=devices)


def summarize_dir(trace_dir: Path, top: int = 10) -> Optional[TraceSummary]:
    return summarize(find_xplane(trace_dir), top=top)
