"""Reduction of a JAX profiler trace (`.xplane.pb`, read through
jax.profiler.ProfileData) to what the per-layer metrics and the
`breakdown` read:

- device busy: the union of the intervals in which an operation ran
  on the device, averaged over the devices traced;
- the idle share: 1 - busy / window;
- the summed device time of named programs (XLA modules);
- the longest idle gaps, each labelled by the benchmark's host span
  (a jax.profiler.TraceAnnotation named `<layer>.<call>`) that
  covers most of it.

The pure interval functions take lists of (start_ns, end_ns) so that
benchmark/tests/test_trace.py checks them on hand-computed values.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] not covered by `busy`."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The name of the host span that overlaps the gap most."""
    best, best_overlap = "no host span", 0.0
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


@dataclass
class Reduced:
    window_ns: float
    busy_ns: float  # mean over devices
    n_devices: int
    module_ns: Dict[str, float] = field(default_factory=dict)
    module_count: Dict[str, int] = field(default_factory=dict)
    op_ns: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def program_ns(self, fragment: str) -> Tuple[float, int]:
        """Summed device time and event count of the modules whose
        name contains `fragment`."""
        ns = sum(v for k, v in self.module_ns.items() if fragment in k)
        n = sum(v for k, v in self.module_count.items() if fragment in k)
        return ns, n


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    ))
    return paths[-1] if paths else None


def reduce(
    path: str,
    window_span: str,
    device_prefix: str = DEVICE_PREFIX,
    ops_line: str = OPS_LINE,
    modules_line: str = MODULES_LINE,
    top: int = 10,
) -> Reduced:
    """Reduce the trace at `path` over the host span named
    `window_span` (the benchmark marks its window with one): device
    planes are those whose name starts with `device_prefix`; the
    events of their lines whose names start with `ops_line` are the
    operations, those of `modules_line` the programs.  Host spans are
    read from every plane: those of the window's loop, named like the
    window span up to its last dot (`replay.` for `replay.window`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == window_span:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise ValueError(f"no span {window_span!r} in {path}")
    lo, hi = window
    span_prefix = window_span.rsplit(".", 1)[0] + "."
    busy_total, n_dev = 0.0, 0
    all_busy: List[Interval] = []
    module_ns: Dict[str, float] = {}
    module_count: Dict[str, int] = {}
    op_ns: Dict[str, float] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(span_prefix) and (
                    ev.name != window_span
                ):
                    spans.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
        if plane.name.startswith(device_prefix):
            ops: List[Interval] = []
            for line in plane.lines:
                if line.name.startswith(ops_line):
                    for ev in line.events:
                        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                        if e <= lo or s >= hi:
                            continue
                        ops.append((s, e))
                        op_ns[ev.name] = op_ns.get(ev.name, 0.0) + (
                            min(e, hi) - max(s, lo)
                        )
                if line.name.startswith(modules_line):
                    for ev in line.events:
                        s = ev.start_ns
                        if s < lo or s + ev.duration_ns > hi:
                            continue
                        module_ns[ev.name] = (
                            module_ns.get(ev.name, 0.0) + ev.duration_ns
                        )
                        module_count[ev.name] = (
                            module_count.get(ev.name, 0) + 1
                        )
            if ops:
                n_dev += 1
                merged = union(clip(ops, lo, hi))
                busy_total += covered(merged)
                all_busy.extend(merged)
    idle = sorted(
        gaps(all_busy, lo, hi), key=lambda g: g[1] - g[0], reverse=True
    )[:top]
    return Reduced(
        window_ns=hi - lo,
        busy_ns=busy_total / max(n_dev, 1),
        n_devices=n_dev,
        module_ns=module_ns,
        module_count=module_count,
        op_ns=op_ns,
        idle_gaps=[(label_gap(g, spans), (g[1] - g[0]) / 1e9) for g in idle],
    )


# container instructions whose events span the operations inside them
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """An HLO op event's instruction name ('%fusion.12 = (...) ...'
    -> 'fusion.12'); other names unchanged."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The device operations that took most time (container ops such
    as the scan's while left out: their time is their body's) and the
    longest idle gaps."""
    ops = {}
    for k, v in r.op_ns.items():
        name = op_name(k)
        if name.split(".")[0] in CONTAINERS:
            continue
        ops[name] = ops.get(name, 0.0) + v
    ops = sorted(ops.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "device_ops": [[k, v / 1e9] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in r.idle_gaps[:top]],
    }
