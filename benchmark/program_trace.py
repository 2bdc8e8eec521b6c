"""What the program's own measurement points say in a traced run.

The fused program names each stage of its pipeline with a
`jax.named_scope` (engine/datapath.py: unpack, prefilter, ct, lb,
ipcache, lattice, verdict, accounting), and each launch records host
spans that mirror into the profiler trace
(`PersistentPairDispatcher.submit`: `datapath.launch` over
`datapath.stack`, `.upload`, `.enqueue`, `.outputs`).  This module
reads the traced run's xplane once (cached by path) and reduces it
with benchmark/trace.py's interval functions:

- the device time of each operation of the persistent program
  (`jit_program`) in the window, put down to the stage scope of its
  op_name.  The v5e trace's operation events carry no op_name, so it
  comes from the `metadata` of the instruction of that name in the
  program's optimized HLO.  A fusion carries its root's op_name, so a
  fusion goes to its root's stage, as xprof's op profile does; the
  rules for fusions and instructions the compiler inserted without a
  scope are hlo_stages';
- the window time in which the device is idle while the host is
  inside given spans.

A program without these points (a commit older than them) reads as
nothing: the readers return None.
"""

from __future__ import annotations

import functools
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import trace as T

STAGES = (
    "unpack", "prefilter", "ct", "lb", "ipcache", "lattice", "verdict",
    "accounting",
)
# the stages with a metric of their own; the rest reads as `other`
NAMED = ("ct", "lb", "ipcache", "lattice", "accounting")
MODULE = "jit_program"
SPAN_PREFIX = "datapath."
# the host's staging work before the program runs, and after it
STAGING = ("datapath.stack", "datapath.upload", "datapath.enqueue")
OUTPUTS = ("datapath.outputs",)

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?op_name="([^"]*)"')
_FUSION = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .* fusion\(.*calls=%([\w.\-]+)"
)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{$")
# the opcode follows the shape; layouts inside a shape ("T(8,128)")
# are upper case and not preceded by a space
_OPCODE = re.compile(
    r"(?m)^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\("
)
_OPERANDS = re.compile(
    r"(?m)^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s[a-z][a-z0-9\-]*\(([^)]*)\)"
)


def stage_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost stage scope on an op_name path
    ('jit(program)/while/body/closed_call/ct/gather' -> 'ct'); None
    when no component is a stage.  Of a merged name ('a;b') the first
    is read."""
    if not op_name:
        return None
    for part in reversed(op_name.split(";")[0].split("/")):
        if part in STAGES:
            return part
    return None


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its op_name metadata, for every instruction
    of an HLO module's text that has one (names are unique in a
    module)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def hlo_stages(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> stage: its own op_name's; else, for a fusion
    whose root has none, the one stage fused into it; else, for an
    instruction other than a fusion with no metadata at all (one the
    compiler inserted: a layout copy, the done of an async copy, the
    sort of a scatter's indices), the stage of its nearest producer
    that has one.  An
    instruction whose op_name names no stage (the scan's stacking of
    its outputs) stays None."""
    names = hlo_op_names(hlo_text)
    out = {name: stage_of(op) for name, op in names.items()}
    fused = fused_stages(hlo_text)
    for name, inside in fused.items():
        if out.get(name) is None and len(inside) == 1:
            out[name] = inside[0]
    operands = {
        m.group(1): re.findall(r"%([\w.\-]+)", m.group(2))
        for m in _OPERANDS.finditer(hlo_text)
    }
    for name in operands:
        if name in names or name in fused:
            continue
        seen, frontier = {name}, [name]
        while frontier and out.get(name) is None:
            nxt = []
            for n in frontier:
                for o in operands.get(n, ()):
                    if o in seen:
                        continue
                    seen.add(o)
                    if out.get(o) is not None:
                        out[name] = out[o]
                        break
                    nxt.append(o)
                if out.get(name) is not None:
                    break
            frontier = nxt
    return out


def fused_stages(hlo_text: str) -> Dict[str, List[str]]:
    """Fusion instruction -> the stages among the instructions of the
    computation it calls (what root attribution leaves out)."""
    comps: Dict[str, set] = {}
    current = None
    calls = {}
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = comps.setdefault(head.group(1), set())
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            st = stage_of(m.group(2))
            if st:
                current.add(st)
        f = _FUSION.match(line)
        if f:
            calls[f.group(1)] = f.group(2)
    return {k: sorted(comps.get(c, ())) for k, c in calls.items()}


def overlap(a: Sequence[T.Interval], b: Sequence[T.Interval]) -> float:
    """Length of the intersection of two interval sets."""
    a, b = T.union(a), T.union(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class ProgramTrace:
    window: T.Interval
    n_devices: int
    busy: List[T.Interval]  # every device's operations, clipped
    # (instruction name, ns in the window) of each operation of the
    # program (container operations left out)
    ops: List[Tuple[str, float]] = field(default_factory=list)
    spans: Dict[str, List[T.Interval]] = field(default_factory=dict)
    # the readers' per-run results, computed once
    memo: dict = field(default_factory=dict)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def span_ms(self, name: str) -> Optional[float]:
        """Mean duration of the window's spans of that name, in ms."""
        got = self.spans.get(name)
        if not got:
            return None
        return sum(e - s for s, e in got) / len(got) / 1e6

    def idle_under(self, names: Sequence[str]) -> Optional[float]:
        """Share (%) of the window in which the device is idle and the
        host is inside one of the named spans; None when the trace has
        no such span or no device."""
        spans = [iv for n in names for iv in self.spans.get(n, ())]
        if not spans or self.n_devices == 0:
            return None
        lo, hi = self.window
        idle = T.gaps(self.busy, lo, hi)
        return 100.0 * overlap(idle, T.clip(spans, lo, hi)) / self.window_ns

    def op_ns(self) -> Dict[str, float]:
        """Device ns in the window per instruction of the program."""
        out: Dict[str, float] = {}
        for name, ns in self.ops:
            out[name] = out.get(name, 0.0) + ns
        return out

    def stage_ns(
        self, stages: Dict[str, Optional[str]]
    ) -> Dict[Optional[str], float]:
        """Device ns of the program's operations per stage (None: no
        stage scope), given instruction name -> stage (hlo_stages)."""
        out: Dict[Optional[str], float] = {}
        for name, ns in self.op_ns().items():
            st = stages.get(name)
            out[st] = out.get(st, 0.0) + ns
        return out


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


@functools.lru_cache(maxsize=4)
def load(
    path: str,
    window_span: str,
    device_prefix: str = T.DEVICE_PREFIX,
    ops_line: str = T.OPS_LINE,
    modules_line: str = T.MODULES_LINE,
    module: str = MODULE,
) -> ProgramTrace:
    """Read the trace at `path` once.  Device planes, operation and
    module lines are named as in trace.reduce.  An operation belongs to
    `module` by its `hlo_module` stat, or where it has none, by lying
    inside an event of that module on the plane's modules line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    spans: Dict[str, List[T.Interval]] = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == window_span:
                    window = iv
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.setdefault(ev.name, []).append(iv)
    if window is None:
        raise ValueError(f"no span {window_span!r} in {path}")
    lo, hi = window
    pt = ProgramTrace(window=window, n_devices=0, busy=[])
    pt.spans = {
        k: [iv for iv in v if lo <= iv[0] < hi] for k, v in spans.items()
    }
    for plane in pd.planes:
        if not plane.name.startswith(device_prefix):
            continue
        runs = [
            (ev.start_ns, ev.start_ns + ev.duration_ns)
            for line in plane.lines if line.name.startswith(modules_line)
            for ev in line.events if module in ev.name
        ]
        busy = []
        for line in plane.lines:
            if not line.name.startswith(ops_line):
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                busy.append((s, e))
                st = _stats(ev)
                name = st.get("hlo_op") or T.op_name(ev.name)
                if name.split(".")[0] in T.CONTAINERS:
                    continue
                mod = st.get("hlo_module")
                mine = (
                    module in str(mod) if mod is not None
                    else any(a <= s and e <= b for a, b in runs)
                )
                if mine:
                    pt.ops.append((name, min(e, hi) - max(s, lo)))
        if busy:
            pt.n_devices += 1
            pt.busy.extend(T.union(T.clip(busy, lo, hi)))
    return pt


# ---------------------------------------------------------------------------
# the readers' side: one traced run of the replay loop
# ---------------------------------------------------------------------------


def for_run(ctx) -> Optional[ProgramTrace]:
    """The traced run's ProgramTrace, or None for an untraced run."""
    from benchmark import harness

    if ctx.reduced is None:
        return None
    path = T.find_xplane(harness.TRACE_DIR)
    if path is None:
        return None
    return load(path, ctx.loop.window_span)


def program_hlo(loop) -> str:
    """The persistent program's optimized HLO at the loop's tables and
    shapes, compiled after the window.  The persistent compile cache
    keys programs without their metadata, so an entry another commit
    wrote would bring that commit's op_names: the key takes the
    metadata here."""
    import jax
    import jax.numpy as jnp

    from cilium_tpu.engine.datapath import persistent_pair_program

    pairs = jax.ShapeDtypeStruct(
        (loop.k, 2, 4, loop.half), jnp.uint32,
        sharding=loop.pd.acc.sharding,
    )
    flag = "jax_compilation_cache_include_metadata_in_key"
    prev = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return (
            persistent_pair_program(loop.k)
            .lower(loop.tables, pairs, loop.pd.acc, loop.pd.telem)
            .compile()
            .as_text()
        )
    finally:
        jax.config.update(flag, prev)


def stages_per_tuple(ctx) -> Optional[Dict[str, float]]:
    """ns per tuple of the persistent program by stage, over the same
    launches and tuples as fused.device_ns_per_tuple: each named stage,
    and `other`, the rest of the program's module time (unpack,
    prefilter, verdict, operations with no stage scope, and time in
    the module between operations).  None when the run is untraced or
    no operation carries a stage scope."""
    pt = for_run(ctx)
    if pt is None:
        return None
    if "stages" not in pt.memo:
        pt.memo["stages"] = _stages(ctx, pt)
    return pt.memo["stages"]


def _stages(ctx, pt: ProgramTrace) -> Optional[Dict[str, float]]:
    module_ns, launches = ctx.reduced.program_ns(MODULE)
    if launches == 0 or not pt.ops:
        return None
    hlo_text = program_hlo(ctx.loop)
    stages = hlo_stages(hlo_text)
    by_stage = pt.stage_ns(stages)
    if not any(k is not None for k in by_stage):
        return None
    tuples = launches * ctx.loop.tuples_per_launch
    out = {st: by_stage.get(st, 0.0) / tuples for st in STAGES}
    named = sum(by_stage.get(s, 0.0) for s in NAMED)
    out["other"] = (module_ns - named) / tuples
    _report(pt, stages, by_stage, module_ns, hlo_text)
    return out


def _report(pt, stages, by_stage, module_ns, hlo_text) -> None:
    """One stderr line for PERF.md: seconds per stage, the unscoped
    share, the operations' cover of the module time, the ten largest
    operations with their stage and the stages fused into them, and
    the ten largest with no stage, with their opcode."""
    import json

    op_ns = pt.op_ns()
    fused = fused_stages(hlo_text)
    opcode = dict(_OPCODE.findall(hlo_text))
    ranked = sorted(op_ns.items(), key=lambda kv: kv[1], reverse=True)
    unscoped = [(k, v) for k, v in ranked if stages.get(k) is None]
    doc = {
        "module_s": module_ns / 1e9,
        "ops_s": sum(op_ns.values()) / 1e9,
        "stage_s": {str(k): v / 1e9 for k, v in by_stage.items()},
        "unscoped_share": by_stage.get(None, 0.0) / module_ns,
        "top_ops": [
            [k, v / 1e9, stages.get(k), fused.get(k)] for k, v in ranked[:10]
        ],
        "top_unscoped": [
            [k, v / 1e9, opcode.get(k)] for k, v in unscoped[:10]
        ],
    }
    print("benchmark: program stages " + json.dumps(doc), file=sys.stderr,
          flush=True)
