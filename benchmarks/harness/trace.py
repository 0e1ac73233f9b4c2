"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read. Read with ``harness/xplane.py``: the protobuf runtime alone.

What a TPU trace holds (looked at by hand, PR 32; tests/test_trace.py checks
the reduction against that reading on a recorded trace):

* one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one
  event per execution of a compiled program, named ``jit_<fn>(<fingerprint>)``),
  ``XLA Ops`` (one event per HLO instruction executed on the core, named by
  the instruction's text, ``%name = shape opcode(...)``; they do not overlap),
  ``Async XLA Ops`` (the spans of asynchronous copies and slices, which
  overlap the others and are not counted as busy), ``Steps``;
* on each op's *metadata*, the stat ``hlo_category``: XLA:TPU puts a
  convolution and what it fuses around it into a fusion named after its root
  ops (``%convert_reduce_fusion.5``, ``kind=kOutput``), so the name does not
  say that a convolution is inside; the category ("convolution fusion") does;
* ``/host:CPU``, whose ``TraceAnnotation`` events (the benchmark's own are
  named ``bench:...``) are on the same clock as the device's events. At the
  profiler's default host level its runtime threads log millions of transfer
  events (300 MB for six steps); the harness traces at host level 1.

The step program is the module with the most device time in the trace: no
name of the program under test is written here.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from . import xplane

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ANNOTATION_PREFIX = "bench:"
_OPCODE = re.compile(r"^%?[\w.\-]+\s*=\s*.*?\s([\w\-]+)\(")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def op_label(text: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``fusion.12``."""
    name = text.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def op_class(text: str, category: str = "") -> str:
    """``matmul`` for a convolution or a dot and the fusions around them,
    ``collective`` for an exchange between chips, ``other`` otherwise: by the
    trace's ``hlo_category`` where it gives one, else by the instruction's
    opcode."""
    m = _OPCODE.match(text)
    opcode = m.group(1).lower() if m else ""
    what = (category or opcode).lower()
    if any(what.startswith(c) for c in _COLLECTIVES):
        return "collective"
    if "convolution" in what or what == "dot" or what.startswith("dot "):
        return "matmul"
    return "other"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _covered(merged: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


@dataclass
class DeviceTrace:
    index: int
    ops: List[Tuple[float, float, str, str]]    # start, end (ns), text, class
    modules: List[Tuple[float, float, str]]


@dataclass
class TraceReduction:
    chips: int
    window_s: float                  # first op's start to last op's end
    busy_s: float                    # op time's union, averaged over chips
    busy_s_least: float              # on the chip that was busy least
    steps: int                       # executions of the step program, chip 0
    step_program: str
    step_intervals_s: List[float]    # gaps between consecutive step starts
    matmul_s: float                  # conv/dot ops inside step programs, avg
    collective_s: float
    collective_exposed_s: float
    op_seconds: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self) -> Dict[str, list]:
        return {"device_ops": [[n, s] for n, s in self.op_seconds[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def read_planes(path: str):
    space = xplane.parse(path)
    devices: List[DeviceTrace] = []
    annotations: List[Tuple[float, float, str]] = []
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not (m or plane.name.startswith("/host:")):
            continue
        metadata, stat_names = xplane.plane_tables(plane)
        if m:
            ops, modules = [], []
            category: Dict[int, str] = {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for s, e, ev in xplane.events(plane, line):
                        md = metadata[ev.metadata_id]
                        if ev.metadata_id not in category:
                            category[ev.metadata_id] = next(
                                (str(xplane.stat_value(st)) for st in md.stats
                                 if stat_names.get(st.metadata_id)
                                 == "hlo_category"), "")
                        ops.append((s, e, md.name,
                                    op_class(md.name,
                                             category[ev.metadata_id])))
                elif line.name == MODULES_LINE:
                    modules = [(s, e, metadata[ev.metadata_id].name)
                               for s, e, ev in xplane.events(plane, line)]
            devices.append(DeviceTrace(int(m.group(1)), ops, modules))
        else:
            for line in plane.lines:
                for s, e, ev in xplane.events(plane, line):
                    name = metadata[ev.metadata_id].name
                    if name.startswith(ANNOTATION_PREFIX):
                        annotations.append((s, e, name))
    devices.sort(key=lambda d: d.index)
    return devices, annotations


def _label_gap(start: float, end: float, step_spans, annotations) -> str:
    """What the host was doing in an idle gap, by the benchmark's own
    annotations: the innermost that covers the gap's middle; and whether the
    gap lies between two steps of one epoch or at an epoch's boundary."""
    mid = 0.5 * (start + end)
    inner = None
    for s, e, name in annotations:
        if s <= mid <= e and (inner is None or e - s < inner[0]):
            inner = (e - s, name[len(ANNOTATION_PREFIX):])
    where = inner[1] if inner else "outside_annotations"
    before = [e for _, e in step_spans if e <= mid]
    after = [s for s, _ in step_spans if s >= mid]
    if not before:
        return f"{where}:before_first_step"
    if not after:
        return f"{where}:after_last_step"
    return f"{where}:between_steps"


TRACED_SPAN = ANNOTATION_PREFIX + "traced_steps"


def _inside(devices: Sequence[DeviceTrace], annotations):
    """Only what started inside the host's ``bench:traced_steps`` span, where
    the trace has one: the profiler's own start-up is left outside it."""
    spans = [(s, e) for s, e, name in annotations if name == TRACED_SPAN]
    if not spans:
        return list(devices)
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    return [DeviceTrace(d.index,
                        [op for op in d.ops if lo <= op[0] <= hi],
                        [m for m in d.modules if lo <= m[0] <= hi])
            for d in devices]


def reduce_devices(devices: Sequence[DeviceTrace], annotations,
                   chips: int) -> Optional[TraceReduction]:
    devices = [d for d in _inside(devices, annotations) if d.ops][:chips]
    if not devices:
        return None
    t_lo = min(d.ops[0][0] for d in devices)
    t_hi = max(max(op[1] for op in d.ops) for d in devices)
    busy, matmul, coll, coll_exposed = [], [], [], []
    totals: Dict[str, float] = {}
    for d in devices:
        merged = _union([(op[0], op[1]) for op in d.ops])
        busy.append(_covered(merged))
        mm = cc = exposed = 0.0
        other = []
        colls = []
        for s, e, text, kind in d.ops:
            if kind == "matmul":
                mm += e - s
            if kind == "collective":
                cc += e - s
                colls.append((s, e))
            else:
                other.append((s, e))
            if d is devices[0]:
                label = op_label(text)
                totals[label] = totals.get(label, 0.0) + (e - s)
        if colls:
            hidden = _union(other)
            for s, e in colls:
                over = sum(min(e, he) - max(s, hs) for hs, he in hidden
                           if hs < e and he > s)
                exposed += (e - s) - over
        matmul.append(mm)
        coll.append(cc)
        coll_exposed.append(exposed)
    d0 = devices[0]
    by_module: Dict[str, float] = {}
    for s, e, name in d0.modules:
        by_module[name] = by_module.get(name, 0.0) + (e - s)
    step_program = max(by_module, key=by_module.get) if by_module else ""
    step_spans = sorted((s, e) for s, e, n in d0.modules
                        if n == step_program)
    starts = [s for s, _ in step_spans]
    merged0 = _union([(op[0], op[1]) for op in d0.ops])
    gaps = [(merged0[i][1], merged0[i + 1][0])
            for i in range(len(merged0) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(_label_gap(s, e, step_spans, annotations), (e - s) / 1e9)
            for s, e in gaps[:10]]
    n = len(devices)
    return TraceReduction(
        chips=n, window_s=(t_hi - t_lo) / 1e9,
        busy_s=sum(busy) / n / 1e9, busy_s_least=min(busy) / 1e9,
        steps=len(step_spans), step_program=step_program,
        step_intervals_s=[(b - a) / 1e9 for a, b in zip(starts, starts[1:])],
        matmul_s=sum(matmul) / n / 1e9, collective_s=sum(coll) / n / 1e9,
        collective_exposed_s=sum(coll_exposed) / n / 1e9,
        op_seconds=sorted(((k, v / 1e9) for k, v in totals.items()),
                          key=lambda kv: -kv[1]),
        idle_gaps=idle)


def reduce_xplane(path: str, chips: int) -> Optional[TraceReduction]:
    devices, annotations = read_planes(path)
    return reduce_devices(devices, annotations, chips)
