#!/usr/bin/env python
"""Where the time under one scope of the expert layers goes, op by op, on
the chip.

    python scripts/moe_dispatch_probe.py [--shapes gqa mla] [--steps 4]
    python scripts/moe_dispatch_probe.py --cell trinity_mini.fit.packed16k
    python scripts/moe_dispatch_probe.py --scope moe.router \\
        --layer nemotron3_super.fit.packed8k [--cell ...]

Without ``--cell`` or ``--layer``: ``held_experts_ffn`` alone, forward +
rematerialised forward + backward (under ``jax.checkpoint`` with the
decoder's policy, as a block runs it), at the two older token cells' shapes
(16384 x 2048 tokens, top-8; ``gqa``: 16 held of 128 experts at width 1024,
``mla``: 16 of 256 at width 768) under three seeded routings: ``balanced``
(every expert as likely), ``zipf`` (a few hot experts, some of them held),
``one_hot`` (every token's first choice is one held expert). With ``--layer``:
the whole expert layer of those cells' configurations alone (router, held and
shared experts, the bias's update and the counters, under the same policy) on
the cell's rows a step. With ``--cell``: one traced run of that benchmark
cell, the table taken from the cell's own trace.

Every table is the device's SELF time of every operation whose ``op_name``
path holds ``--scope`` (``moe.experts`` unless given), a step, by phase
(forward, the rematerialised forward, backward) and kind (under
``moe.experts``: sort, gather, scatter, gmm, tgmm, ...; under any other
scope, the router's: dot, top-k / sort, scalar gather, scalar scatter,
elementwise), and the whole rows go to ``chiprun_out/moe_ops_<label>.json``
(``moe_ops_<scope>_<label>.json`` for another scope). Refuses any platform
but ``tpu``: a time comes from the chip.
"""

import argparse
import glob
import json
import os
import re
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

SCOPE = "moe.experts"
SHAPES = {                       # tokens, hidden, top_k, held, experts, width
    "gqa": (16384, 2048, 8, 16, 128, 1024),
    "mla": (16384, 2048, 8, 16, 256, 768),
}
ROUTINGS = ("balanced", "zipf", "one_hot")
OUT_DIR = os.path.join(REPO, "chiprun_out")

PHASES = ("forward", "remat forward", "backward")
_SHAPE = re.compile(r"%?[\w.\-]+ = \(?(\w+)\[([\d,]*)\]")


def phase_of(path: str) -> str:
    if "rematted_computation" in path:
        return "remat forward"
    return "backward" if "transpose(" in path else "forward"


def router_kind_of(path: str, category: str, scope: str) -> str:
    """A row of the router's table: the scores' products, the selection
    (``lax.top_k``, or a sort it lowers to), gathers and scatters of single
    scores or choices, the rest (sigmoid, casts, compare-and-sum fusions)."""
    tail = path.rsplit(scope, 1)[-1]
    if "top_k" in tail or "sort" in tail:
        return "top-k / sort"
    for name in ("scatter", "gather"):
        if name in tail:
            return f"scalar {name}"
    if "dot_general" in tail or "convolution" in category:
        return "dot"
    return "elementwise and other"


def kind_of(hlo: str, path: str, category: str, scope: str = SCOPE) -> str:
    """A row of the table: the grouped products, the sort, the row and the
    scalar gathers and scatter-adds (by the result's type and rank), the
    weights' casts, what a ``cond`` or a ``scan`` adds around its branches
    (zeros for the branch not taken, copies), the rest elementwise. Under
    another scope than ``moe.experts``: :func:`router_kind_of`'s rows."""
    if scope != SCOPE:
        return router_kind_of(path, category, scope)
    tail = path.rsplit(SCOPE, 1)[-1]
    m = _SHAPE.match(hlo)
    dtype, rank = (m.group(1), len([n for n in m.group(2).split(",") if n])) \
        if m else ("?", 0)
    for name in ("tgmm", "gmm", "sort"):
        if name in tail:
            return name
    if category == "custom fusion":
        for name in ("scatter", "gather"):
            if name in tail:
                return f"row {name} {dtype}" if rank >= 2 \
                    else f"scalar {name}"
    if category in ("conditional", "while"):
        return "control (self)"
    if ("/cond" in tail or "/while" in tail) and category in (
            "broadcast", "data formatting"):
        return "zeros and copies of cond/scan"
    if "convert_element_type" in tail and rank == 3:
        return "weight casts"
    return "elementwise and other"


def op_rows(path: str, scope: str = SCOPE):
    """``(rows, steps, all_s)``: one row for each operation of device 0 under
    ``scope`` inside the traced span: its HLO text, ``op_name`` path,
    category, executions and self seconds (``harness/scopes.py``'s rule: a
    ``while`` or a ``conditional`` counts for the time none of its body's
    operations runs); ``steps`` the executions of the module that took
    longest; ``all_s`` every operation's self seconds."""
    from harness import scopes, trace as trace_mod, xplane
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
    plane = planes[0][1]
    md, stat_names = xplane.plane_tables(plane)

    def inside(line_name):
        return sorted(((s, e, ev.metadata_id) for line in plane.lines
                       if line.name == line_name
                       for s, e, ev in xplane.events(plane, line)
                       if span is None or span[0] <= s <= span[1]),
                      key=lambda t: (t[0], -t[1]))

    by_module = {}
    for s, e, mid in inside(trace_mod.MODULES_LINE):
        n, t = by_module.get(mid, (0, 0.0))
        by_module[mid] = (n + 1, t + e - s)
    steps = max(by_module.values(), key=lambda v: v[1])[0] if by_module else 1
    events = inside(trace_mod.OPS_LINE)
    self_ns = [e - s for s, e, _ in events]
    open_ = []
    for i, (s, e, _) in enumerate(events):
        while open_ and events[open_[-1]][1] <= s:
            open_.pop()
        if open_ and e <= events[open_[-1]][1]:
            self_ns[open_[-1]] -= e - s
        open_.append(i)
    rows, all_s = {}, 0.0
    for (_, _, mid), own in zip(events, self_ns):
        own = max(own, 0.0) / 1e9
        all_s += own
        if mid not in rows:
            m = md[mid]
            strings = scopes._strings(m, stat_names)
            if scope not in strings:
                rows[mid] = None
                continue
            stats = {stat_names.get(st.metadata_id): st for st in m.stats}

            def text_of(name):
                st = stats.get(name)
                if st is None:
                    return ""
                if st.str_value:
                    return st.str_value
                if st.ref_value:
                    return stat_names.get(st.ref_value, "")
                return st.bytes_value.decode(errors="replace")
            op_name = next((v for v in (text_of("tf_op"), text_of("op_name"),
                                        text_of("name"))
                            if scope in v), strings)
            rows[mid] = {"hlo": m.name[:400], "op_name": op_name[:600],
                         "category": text_of("hlo_category"),
                         "n": 0, "seconds": 0.0}
        if rows[mid] is not None:
            rows[mid]["n"] += 1
            rows[mid]["seconds"] += own
    found = [r for r in rows.values() if r is not None]
    for r in found:
        r["phase"] = phase_of(r["op_name"])
        r["kind"] = kind_of(r["hlo"], r["op_name"], r["category"], scope)
    found.sort(key=lambda r: -r["seconds"])
    return found, steps, all_s


def table(rows, steps: int):
    """``{(phase, kind): (operations a step, ms a step)}``."""
    out = {}
    for r in rows:
        key = (r["phase"], r["kind"])
        n, ms = out.get(key, (0.0, 0.0))
        out[key] = (n + r["n"] / steps, ms + 1e3 * r["seconds"] / steps)
    return out


def report(label: str, xplane_path: str, extra=None, steps=None,
           scope: str = SCOPE):
    rows, counted, all_s = op_rows(xplane_path, scope)
    steps = steps or counted
    tab = table(rows, steps)
    total = sum(ms for _, ms in tab.values())
    print(f"== {label}: {total:.2f} ms a step under {scope} ({steps} steps, "
          f"all ops {1e3 * all_s / steps:.2f} ms a step); ms (ops) a step")
    print(f"{'':32}" + "".join(f"{p:>17}" for p in PHASES) + f"{'sum':>9}")
    by_kind = {}
    for (phase, kind), cell in tab.items():
        by_kind.setdefault(kind, {})[phase] = cell
    for kind, cells in sorted(by_kind.items(), key=lambda kv: -sum(
            ms for _, ms in kv[1].values())):
        print(f"{kind:<32}" + "".join(
            "{:9.2f} ({:5.1f})".format(*reversed(cells.get(p, (0.0, 0.0))))
            for p in PHASES) + f"{sum(ms for _, ms in cells.values()):9.2f}")
    print(f"{'total':<32}" + "".join(
        f"{sum(c.get(p, (0, 0.0))[1] for c in by_kind.values()):9.2f}"
        + " " * 8 for p in PHASES) + f"{total:9.2f}")
    os.makedirs(OUT_DIR, exist_ok=True)
    stem = f"moe_ops_{label}" if scope == SCOPE \
        else f"moe_ops_{scope}_{label}"
    with open(os.path.join(OUT_DIR, f"{stem}.json"), "w") as f:
        json.dump({"label": label, "scope": scope, "steps": steps,
                   "all_s": all_s,
                   "scope_ms_per_step": total, "extra": extra or {},
                   "table": [[p, k, n, ms] for (p, k), (n, ms)
                             in sorted(tab.items())],
                   "rows": rows}, f, indent=1)
    sys.stdout.flush()
    return total


def routing(kind: str, seed: int, n: int, top_k: int, held: int, experts: int):
    """``(idx (n, top_k) int32, gates (n, top_k) float32)``, the held experts
    being 0 .. held - 1."""
    import numpy as np
    rng = np.random.default_rng(seed)
    noise = rng.gumbel(size=(n, experts))
    if kind == "zipf":
        weight = 1.0 / np.arange(1, experts + 1)
        noise += np.log(weight[rng.permutation(experts)])[None, :]
    elif kind == "one_hot":
        noise[:, 0] = 1e9                        # everyone's first choice
        noise[:, 1:held] = -1e9                  # and no other held expert
    idx = np.argsort(-noise, axis=1)[:, :top_k].astype(np.int32)
    gates = rng.random((n, top_k), dtype=np.float32) + 0.5
    return idx, gates / gates.sum(1, keepdims=True)


def probe(shape: str, kind: str, seed: int, steps: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from analytics_zoo_tpu.parallel.expert_parallel import held_experts_ffn
    from analytics_zoo_tpu.pipeline.api.keras.layers import decoder_lm
    n, d, top_k, held, experts, f = SHAPES[shape]
    rng = np.random.default_rng(seed)
    idx, gates = routing(kind, seed, n, top_k, held, experts)
    x = jnp.asarray(rng.standard_normal((n, d), np.float32), jnp.bfloat16)
    ws = [jnp.asarray(0.02 * rng.standard_normal(s, np.float32))
          for s in ((held, d, f), (held, d, f), (held, f, d))]
    probe_w = jnp.asarray(rng.standard_normal((n, d), np.float32))
    idx, gates = jnp.asarray(idx), jnp.asarray(gates)

    def layer(x, gates, ws):
        y, counters = held_experts_ffn(x, idx, gates, *ws, first_expert=0,
                                       n_experts=experts)
        return y, counters

    def loss(x, gates, ws):
        y, counters = jax.checkpoint(
            layer, policy=decoder_lm._KEPT_ACROSS_REMAT)(x, gates, ws)
        return jnp.sum(y * probe_w), counters

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
    (_, counters), grads = step(x, gates, ws)
    jax.block_until_ready(grads)
    counters = {k: float(v) for k, v in jax.device_get(counters).items()}
    routed = counters["local_rows"]
    moved = counters.get("moved_rows")
    extra = {"shape": shape, "routing": kind, "seed": seed, "routed": routed,
             "moved": moved, "rows_max_over_mean":
                 counters["rows_max_over_mean"],
             "dropped_rows": counters["dropped_rows"]}
    ratio = "not counted" if moved is None or not routed \
        else f"{moved / routed:.3f}"
    print(f"-- {shape} {kind}: routed {routed:.0f} rows, moved over "
          f"routed {ratio}, max over mean "
          f"{counters['rows_max_over_mean']:.2f}, dropped "
          f"{counters['dropped_rows']:.0f}")
    return traced_table(f"{shape}_{kind}", step, (x, gates, ws), steps, extra,
                        SCOPE)


def traced_table(label: str, step, args, steps: int, extra, scope: str):
    """``steps`` calls of a compiled ``step`` under a profiler session, and
    the table of its trace."""
    import jax
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(steps):
                out = step(*args)
            jax.block_until_ready(out)
        found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return report(label, found[0], extra, steps, scope)


def probe_layer(cell_name: str, seed: int, steps: int, scope: str):
    """The expert layer of a cell's configuration alone, as a block runs it:
    ``SparseExperts`` under the blocks' remat policy on the cell's rows a
    step, forward and backward, the bias and the counters written."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import spec
    from analytics_zoo_tpu.pipeline.api.keras.layers import decoder_lm
    cell = spec.load_cell(cell_name)
    model = decoder_lm.DecoderLM.from_config(
        cell.load("factory").model_config(cell.config))
    # the plan's expert layer, under its block's policy (a hybrid family's
    # keeps the shared expert's up product too)
    block, experts = next((b, s.sizes) for b in model.layers
                          for s in b.sublayers if s.kind == "experts")
    layer = nn.remat(decoder_lm.SparseExperts, policy=block.policy)(
        dtype=model.dtype, **experts)
    shape = (int(cell.config["per_chip_batch"]),
             int(cell.traffic["sequence_length"]), model.hidden_size)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(shape, np.float32), model.dtype)
    probe_w = jnp.asarray(rng.standard_normal(shape, np.float32))
    variables = jax.jit(layer.init)(jax.random.PRNGKey(seed % 2 ** 31),
                                    x[:, :128])
    state = {k: v for k, v in variables.items() if k != "params"}

    def loss(params, x):
        y, new = layer.apply({"params": params, **state}, x,
                             mutable=list(state))
        return jnp.sum(y * probe_w), new

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
    args = (variables["params"], x)
    jax.block_until_ready(step(*args))
    print(f"-- {cell_name}: the expert layer alone, {shape[0] * shape[1]} "
          f"rows of {shape[2]}, top-{experts['num_experts_per_tok']} "
          f"of {experts['n_routed_experts']}")
    return traced_table(f"layer_{cell_name}", step, args, steps,
                        {"cell": cell_name, "seed": seed, "rows": shape},
                        scope)


def run_cell(name: str, seed: int, seconds: float, scope: str) -> int:
    """One traced run of a benchmark cell; the table from its own trace."""
    import time
    t_start = time.perf_counter()
    from harness import runner, scopes, spec
    inner = scopes.scope_seconds

    def and_table(path, names):
        report(name, path, {"cell": name, "seed": seed}, scope=scope)
        return inner(path, names)

    scopes.scope_seconds = and_table
    return runner.run_and_print(spec.load_cell(name), seed, seconds, True,
                                t_start)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell")
    ap.add_argument("--layer", nargs="*", default=[], metavar="CELL",
                    help="the expert layer of these cells alone")
    ap.add_argument("--scope", default=SCOPE,
                    help="the named scope the table is of (default "
                         "%(default)s)")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=40)
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--routings", nargs="*", default=list(ROUTINGS),
                    choices=list(ROUTINGS))
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("moe_dispatch_probe: a device time comes from a TPU; found "
              f"{jax.devices()[0].platform!r}. No result.", file=sys.stderr)
        return 3
    for cell in args.layer:
        probe_layer(cell, args.seed, args.steps, args.scope)
    if args.cell:
        return run_cell(args.cell, args.seed, args.seconds, args.scope)
    if args.layer:
        return 0
    for shape in args.shapes:
        for kind in args.routings:
            probe(shape, kind, args.seed, args.steps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
