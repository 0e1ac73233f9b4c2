"""From a cell's driver to the result line: the verdict on `correct`, the
metrics named in ``BENCHMARK.json`` for this cell, the device, the breakdown.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, taken by
the driver on the host's clock. With ``--trace 1`` they are its per-layer
metrics: each is read by ``layer_metrics/<name>.py`` from the trace's
reduction, the program's counters and the window's facts; a reader that finds
nothing to read returns ``None`` and the metric is left out of the line.
"""

from __future__ import annotations

import json
import math
import sys
from typing import Dict, Tuple

from . import check, work
from .spec import Cell


def _device_block(devices, peak_bytes: int, reduction) -> Dict:
    import jax
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": int(peak_bytes)}
    if reduction is not None:
        out["busy_s"] = reduction.busy_s
        out["window_s"] = reduction.window_s
    return out


def assemble(cell: Cell, out: Dict, traced: bool) -> Tuple[Dict, int]:
    """The result line and the exit code from a driver's output."""
    correct, table = check.verdict(out["numbers"], cell.limits)
    devices = out["devices"]
    reduction = None
    metrics: Dict[str, Dict] = {}
    if traced:
        reduction = out.get("trace")
        ctx = {"trace": reduction, "facts": out["facts"], "cell": cell,
               "peaks": work.load_peaks(devices[0].device_kind)
               if devices[0].platform != "cpu" else None}
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"])(ctx)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": _device_block(devices, out["memory_peak_bytes"],
                                    reduction)}
    if reduction is not None:
        line["breakdown"] = reduction.breakdown()
    line["compared"] = table
    return line, 0


def print_result(line: Dict) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error; the result as the last line on standard output."""
    for name, row in line["compared"].items():
        mark = "ok" if row["value"] <= row["limit"] else "OVER"
        print(f"compared {name} = {row['value']!r} limit {row['limit']!r} "
              f"{mark}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def run_and_print(cell: Cell, seed: int, seconds: float, traced: bool,
                  t_start: float, **driver_kwargs) -> int:
    out = cell.load("driver").run(cell, seed, seconds, traced, t_start,
                                  **driver_kwargs)
    line, code = assemble(cell, out, traced)
    print_result(line)
    return code
