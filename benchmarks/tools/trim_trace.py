#!/usr/bin/env python3
"""Look at an ``xplane.pb`` by hand and cut it down to a size a repository
can keep: which planes and lines it has, how many events and bytes each, and
a copy that holds only the device planes' ``XLA Modules`` and ``XLA Ops``
lines over the first ``--steps`` executions of the step program, with the
benchmark's own host annotations.

Reads and writes with ``harness/xplane.py``. Of an event's metadata it keeps
the name and the ``hlo_category`` stat, which the reduction reads.

    python benchmarks/tools/trim_trace.py in.xplane.pb --out small.xplane.pb --steps 3
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def print_categories(space, tables) -> None:
    """Device time per ``hlo_category`` on the first device plane: the
    by-hand reading that tests/test_trace.py quotes."""
    from harness import xplane
    for i, plane in enumerate(space.planes):
        if not plane.name.startswith("/device:TPU:"):
            continue
        emd, smd = tables[i]
        per = {}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                cat = next((str(xplane.stat_value(s))
                            for s in emd[ev.metadata_id].stats
                            if smd.get(s.metadata_id) == "hlo_category"),
                           "?")
                n, t = per.get(cat, (0, 0))
                per[cat] = (n + 1, t + ev.duration_ps)
        for cat, (n, t) in sorted(per.items(), key=lambda kv: -kv[1][1]):
            print(f"CATEGORY {plane.name} {cat!r} events={n} "
                  f"ms={t / 1e9:.3f}")
        break


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--out")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--show", type=int, default=6,
                    help="events to print per line")
    args = ap.parse_args()
    from harness import xplane
    space = xplane.parse(args.xplane)
    tables = {}
    for i, plane in enumerate(space.planes):
        tables[i] = xplane.plane_tables(plane)
    for i, plane in enumerate(space.planes):
        emd, smd = tables[i]
        print(f"PLANE {plane.name!r} id={plane.id} bytes={plane.ByteSize()} "
              f"event_metadata={len(emd)} stat_metadata={len(smd)}")
        for line in plane.lines:
            print(f"  LINE {line.name!r} events={len(line.events)} "
                  f"bytes={line.ByteSize()} t0_ns={line.timestamp_ns}")
            for ev in list(line.events)[:args.show]:
                md = emd[ev.metadata_id]
                stats = {smd.get(s.metadata_id): xplane.stat_value(s)
                         for s in ev.stats}
                mstats = {smd.get(s.metadata_id):
                          str(xplane.stat_value(s))[:80] for s in md.stats}
                print(f"    ev {md.name[:100]!r} disp={md.display_name[:60]!r} "
                      f"off_ps={ev.offset_ps} dur_ps={ev.duration_ps} "
                      f"stats={stats} mstats={mstats}")
    print_categories(space, tables)
    if not args.out:
        return 0
    out = type(space)()
    t_end = None
    for i, plane in enumerate(space.planes):
        emd, smd = tables[i]
        if plane.name.startswith("/device:TPU:"):
            mods = [l for l in plane.lines if l.name == "XLA Modules"]
            if mods and t_end is None:
                by = {}
                for ev in mods[0].events:
                    by.setdefault(ev.metadata_id, []).append(ev)
                step = max(by.values(), key=lambda evs: sum(
                    e.duration_ps for e in evs))
                step.sort(key=lambda e: e.offset_ps)
                last = step[min(args.steps, len(step)) - 1]
                t_end = (mods[0].timestamp_ns * 1000 + last.offset_ps
                         + last.duration_ps)
    for i, plane in enumerate(space.planes):
        emd, smd = tables[i]
        device = plane.name.startswith("/device:TPU:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        new = out.planes.add()
        new.id, new.name = plane.id, plane.name
        used_md, used_st = set(), set()
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            keep = []
            for ev in line.events:
                md = emd[ev.metadata_id]
                if host and not md.name.startswith("bench:"):
                    continue
                start = line.timestamp_ns * 1000 + ev.offset_ps
                if t_end is not None and start > t_end:
                    continue
                keep.append(ev)
            if not keep:
                continue
            nl = new.lines.add()
            nl.id, nl.name, nl.timestamp_ns = line.id, line.name, \
                line.timestamp_ns
            nl.display_name = line.display_name
            for ev in keep:
                nl.events.add().CopyFrom(ev)
                used_md.add(ev.metadata_id)
                used_st.update(s.metadata_id for s in ev.stats)
        cat_id = next((k for k, v in smd.items() if v == "hlo_category"), None)
        for mid in sorted(used_md):
            md = emd[mid]
            entry = new.event_metadata.add(key=mid)
            nm = entry.value
            nm.id, nm.name, nm.display_name = md.id, md.name, md.display_name
            for st in md.stats:
                if st.metadata_id == cat_id:
                    nm.stats.add().CopyFrom(st)
                    used_st.add(cat_id)
        for sid in sorted(used_st):
            entry = new.stat_metadata.add(key=sid)
            entry.value.id, entry.value.name = sid, smd[sid]
    with open(args.out, "wb") as f:
        f.write(out.SerializeToString())
    print(f"wrote {args.out}: {out.ByteSize()} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
