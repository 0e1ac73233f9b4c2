"""Device time of a traced step's operations by the program's named scopes.

``jax.named_scope`` names end up in each HLO instruction's ``op_name``, and
the TPU profiler keeps that path on the operation's metadata (a string stat;
which one depends on the runtime, so every string the metadata carries is
searched). A scope's time is the sum of the durations of the operations on
device 0's ``XLA Ops`` line whose path holds the scope's name, inside the
host's ``bench:traced_steps`` span where the trace has one. A ``while`` or a
``conditional`` is on that line as one event around its body's operations:
each event counts for its self time (its duration less the events inside
it), so scopes' times add up to at most ``all``, the device's busy time.

Returns nothing where the trace holds no scope name at all (a program
without them, a runtime that drops the paths).
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

from . import trace as trace_mod, xplane

KERNEL = re.compile(r"\bcustom-call\(")


def _strings(md, stat_names) -> str:
    parts = [md.display_name]
    for st in md.stats:
        if st.str_value:
            parts.append(st.str_value)
        elif st.ref_value:               # a string kept once, by reference
            parts.append(stat_names.get(st.ref_value, ""))
        elif st.bytes_value:
            parts.append(st.bytes_value.decode(errors="replace"))
    return " ".join(parts)


def scope_seconds(path: str, scopes: Sequence[str]
                  ) -> Optional[Dict[str, float]]:
    """``{scope: seconds, scope + ":kernels": seconds of its custom calls,
    "all": seconds of every operation}`` on device 0, over the traced
    steps."""
    space = xplane.parse(path)
    span = None
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        md, _ = xplane.plane_tables(plane)
        for line in plane.lines:
            for s, e, ev in xplane.events(plane, line):
                if md[ev.metadata_id].name == trace_mod.TRACED_SPAN:
                    span = (s, e) if span is None else (min(s, span[0]),
                                                        max(e, span[1]))
    planes = sorted((int(m.group(1)), p) for p in space.planes
                    for m in [trace_mod.DEVICE_PLANE.match(p.name)] if m)
    if not planes:
        return None
    plane = planes[0][1]
    md, stat_names = xplane.plane_tables(plane)
    text: Dict[int, str] = {}
    out = {name: 0.0 for name in scopes}
    out.update({f"{name}:kernels": 0.0 for name in scopes})
    out["all"] = 0.0
    found = False
    events = sorted(((s, e, ev.metadata_id) for line in plane.lines
                     if line.name == trace_mod.OPS_LINE
                     for s, e, ev in xplane.events(plane, line)
                     if span is None or span[0] <= s <= span[1]),
                    key=lambda t: (t[0], -t[1]))
    # a `while` or a `conditional` is an event that spans its body's
    # operations: every operation is counted for the time no operation
    # inside it runs (its self time), so that nothing is counted twice
    self_s = [e - s for s, e, _ in events]
    open_: list = []
    for i, (s, e, _) in enumerate(events):
        while open_ and events[open_[-1]][1] <= s:
            open_.pop()
        if open_ and e <= events[open_[-1]][1]:
            self_s[open_[-1]] -= e - s
        open_.append(i)
    for (s, e, mid), own in zip(events, self_s):
        m = md[mid]
        if mid not in text:
            text[mid] = _strings(m, stat_names)
        dur = max(own, 0.0) / 1e9
        out["all"] += dur
        for name in scopes:
            if name in text[mid]:
                found = True
                out[name] += dur
                if KERNEL.search(m.name):
                    out[f"{name}:kernels"] += dur
    return out if found else None


def stat_names_seen(path: str, limit: int = 5):
    """For a look by hand: the stats a few operations' metadata carry."""
    space = xplane.parse(path)
    seen = []
    for plane in space.planes:
        if not trace_mod.DEVICE_PLANE.match(plane.name):
            continue
        md, stat_names = xplane.plane_tables(plane)
        for m in list(md.values())[:2000]:
            if "fusion" in m.name or "custom-call" in m.name:
                seen.append({"name": m.name[:120], "display": m.display_name,
                             "stats": {stat_names.get(st.metadata_id):
                                       (stat_names.get(st.ref_value, "?")
                                        if st.ref_value else
                                        str(xplane.stat_value(st)))[:300]
                                       for st in m.stats}})
                if len(seen) >= limit:
                    return seen
    return seen
