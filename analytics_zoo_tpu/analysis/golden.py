"""Golden program contracts for the train step.

A *program contract* pins what a train step's program actually does on the
wire — collective launches by kind and by mesh axis, the fsdp gathers'
bytes, the donation set, and how many distinct train executables the legs
compile — next to what the engine *declares* for its layout. The contracts
are committed under ``tests/goldens/`` and diffed in CI, so a regression (a
bucketing change that doubles launches, a donation that silently stops
happening, an ``extra_key`` change that collapses two layouts onto one
executable) fails the gate with a readable delta instead of surfacing as a
slowdown five PRs later.

Three legs on the 8-device simulated mesh:

* ``baseline``          — the default step on a dp mesh: replicated state,
  no explicit collective in the lowered module (GSPMD adds the gradient
  all-reduce when it partitions), donation ``(0, 2)``.
* ``sharding_fsdp``     — ``SpecLayout`` on ``fsdp=8``, measured on the
  COMPILED program (the collectives exist only after the partitioner).
* ``sharding_fsdp_tp``  — ``SpecLayout`` on ``fsdp=4 x tp=2`` with one
  Megatron column/row pair: pins the tp all-reduce beside the gathers.

Regenerate after an *intentional* program change::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m analytics_zoo_tpu.analysis.golden --update

``--check`` (the CI gate) exits 1 on drift and prints one line per
changed field.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from .hlo_lint import (HloLinter, collective_counts,
                       collectives_by_mesh_axes, declared_accounting,
                       parse_collectives)

__all__ = ["capture_contracts", "check", "diff_contracts", "golden_path",
           "load_goldens", "save_goldens"]

GOLDEN_FILE = "program_contracts.json"

# sharding legs: each on its OWN mesh (the baseline runs the ctx's dp mesh;
# fsdp/tp need the factored one) and measured on COMPILED HLO — their
# collectives exist only after the SPMD partitioner runs, so a lowering-only
# capture would pin an empty program.
_SHARDING_LEGS = [
    ("sharding_fsdp", {"dp": 1, "fsdp": -1}),
    ("sharding_fsdp_tp", {"dp": 1, "fsdp": -1, "tp": 2}),
]


def golden_path(root: Optional[str] = None) -> str:
    if root is None:
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), "tests",
            "goldens")
    return os.path.join(root, GOLDEN_FILE)


def _bench_model():
    import flax.linen as nn

    class BenchMLP(nn.Module):
        """Several small Dense leaves: what one fsdp bucket gathers in a
        single launch, and where a per-leaf regression shows."""

        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(32)(x))
            x = nn.relu(nn.Dense(16)(x))
            return nn.Dense(1)(x)[:, 0]

    return BenchMLP()


def _bench_tp_model():
    import flax.linen as nn

    from ..parallel.tensor_parallel import TPMLP

    class BenchTPMLP(nn.Module):
        """BenchMLP plus one Megatron column→row pair: the tp leg's
        contract pins exactly ONE tp all-reduce per step-forward (the row
        matmul's partial-product combine) riding next to the fsdp
        gathers."""

        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(32)(x))
            x = TPMLP(64, out_dim=16, name="tp_mlp")(x)
            return nn.Dense(1)(x)[:, 0]

    return BenchTPMLP()


def _bench_data():
    import numpy as np
    rng = np.random.RandomState(0)
    return {"x": rng.rand(256, 8).astype("float32"),
            "y": rng.rand(256).astype("float32")}


def _first_batch(est, data):
    import numpy as np

    from ..orca.learn.utils import data_to_iterator
    it = data_to_iterator(dict(data), 32, est.mesh, None, None,
                          shuffle=False, config=est.config)
    b0 = next(it.epoch(shuffle=False, prefetch=False))
    est.engine.build(tuple(np.asarray(a) for a in b0.x))
    return b0


def capture_contracts() -> Dict[str, Any]:
    """Lower (and, for the sharding legs, compile) every leg's train step
    and measure its contract. Requires the 8-device simulated mesh
    (tests/conftest.py provides it; the CLI sets XLA_FLAGS itself)."""
    from ..common.context import get_context
    from ..compile.cache import ExecutableCache
    from ..orca.learn.estimator import TPUEstimator
    from ..parallel.mesh import create_mesh
    from ..parallel.sharding import SpecLayout

    ctx = get_context()
    dp = int(ctx.mesh.shape.get("dp", 1)) if ctx.mesh is not None else 1
    if dp < 2:
        raise RuntimeError(
            f"golden contracts need a dp>=2 mesh (got dp={dp}); run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 with "
            f"init_orca_context('cpu-sim', mesh_axes={{'dp': -1}})")

    data = _bench_data()
    # one private cache across all legs: distinct layouts MUST yield
    # distinct executable keys (the compile plane's extra_key contract)
    cache = ExecutableCache()
    contracts: Dict[str, Any] = {"dp": dp}
    train_keys: List[str] = []
    linter = HloLinter()

    est = TPUEstimator(_bench_model(), loss="mse", optimizer="adam", seed=0,
                       compile_cache=cache,
                       config={"steps_per_dispatch": 1})
    b0 = _first_batch(est, data)
    fn = est.engine.ensure_jit_train()
    args = est.engine.train_step_args(b0)
    train_keys.append(fn.cache_key(*args))
    # lowered_text reuses cache_key's lowering
    ops = parse_collectives(fn.lowered_text(*args))
    # the lowered default step holds no explicit collective: the two byte
    # sums pin that at zero
    contracts["baseline"] = {
        "collectives": collective_counts(ops),
        "rs_wire_bytes": sum(op.operand_bytes for op in ops
                             if op.kind == "reduce_scatter"),
        "cp_wire_bytes": sum(op.operand_bytes for op in ops
                             if op.kind == "collective_permute"),
        "donation": sorted(int(i) for i in fn._donate),
    }

    for name, axes in _SHARDING_LEGS:
        mesh = create_mesh(axes)
        model = _bench_tp_model() if "tp" in axes else _bench_model()
        est = TPUEstimator(model, loss="mse", optimizer="adam", seed=0,
                           mesh=mesh, compile_cache=cache,
                           config={"steps_per_dispatch": 1},
                           sharding=SpecLayout())
        b0 = _first_batch(est, data)
        fn = est.engine.ensure_jit_train()
        args = est.engine.train_step_args(b0)
        train_keys.append(fn.cache_key(*args))
        # compiled HLO: the gathers/grad combines appear only post-partition
        text = fn.lower(*args).compile().as_text()
        ops = parse_collectives(text)
        axis_sizes = {a: int(s) for a, s in mesh.shape.items() if s > 1}
        ax = collectives_by_mesh_axes(ops, axis_sizes)
        declared = declared_accounting(est.engine._sharding_key())
        plan = est.engine.fsdp_plan
        entry = {
            "mesh_axes": axis_sizes,
            "collectives": collective_counts(ops),
            "by_mesh_axes": {"by_axis": ax["by_axis"],
                             "global": ax["global"]},
            "fsdp_gather_bytes": int(
                ax["axis_bytes"].get("fsdp", {}).get("all_gather", 0)),
            "tp_collectives": dict(ax["by_axis"].get("tp", {})),
            "buckets": (len(plan.layout.bucket_sizes)
                        if plan is not None else 0),
            "gather_shard_bytes_per_sweep": (
                plan.gather_shard_bytes_per_sweep()
                if plan is not None else 0),
        }
        if declared is not None:
            findings = linter.lint_text(text, label=f"golden:{name}",
                                        declared=declared)
            entry["declared_tp"] = declared.get("tp")
            entry["accounting_verified"] = not findings
            entry["accounting_findings"] = [str(f) for f in findings]
        contracts[name] = entry

    # the tp leg's reason to exist, pinned: the row-parallel matmul really
    # combines partials over the tp groups
    tp_ops = contracts["sharding_fsdp_tp"]["tp_collectives"]
    contracts["tp_all_reduce_present"] = tp_ops.get("all_reduce", 0) >= 1

    # every leg must map to its own executable: a regression in the
    # sharding fingerprint / extra_key salting collapses this number
    contracts["distinct_train_executables"] = len(set(train_keys))
    return contracts


# ---------------------------------------------------------------------------
# persistence + diffing
# ---------------------------------------------------------------------------
def save_goldens(contracts: Dict[str, Any],
                 path: Optional[str] = None) -> str:
    path = path or golden_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(contracts, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_goldens(path: Optional[str] = None) -> Dict[str, Any]:
    path = path or golden_path()
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def diff_contracts(golden: Dict[str, Any], measured: Dict[str, Any],
                   _prefix: str = "") -> List[str]:
    """Readable field-level delta, ``golden -> measured``. Empty list ==
    no drift."""
    lines: List[str] = []
    keys = sorted(set(golden) | set(measured))
    for k in keys:
        if k == "accounting_findings":
            continue
        path = f"{_prefix}{k}"
        if k not in golden:
            lines.append(f"{path}: (absent in golden) -> "
                         f"{measured[k]!r} (regenerate goldens?)")
        elif k not in measured:
            lines.append(f"{path}: {golden[k]!r} -> (absent in measured)")
        elif isinstance(golden[k], dict) and isinstance(measured[k], dict):
            lines += diff_contracts(golden[k], measured[k],
                                    _prefix=path + ".")
        elif golden[k] != measured[k]:
            lines.append(f"{path}: {golden[k]!r} -> {measured[k]!r}")
    return lines


def check(path: Optional[str] = None,
          measured: Optional[Dict[str, Any]] = None
          ) -> Tuple[bool, List[str]]:
    """The CI gate: capture fresh contracts and diff against the
    committed goldens. Returns ``(ok, delta_lines)``."""
    golden = load_goldens(path)
    if measured is None:
        measured = capture_contracts()
    delta = diff_contracts(golden, measured)
    return (not delta, delta)


# ---------------------------------------------------------------------------
# CLI: python -m analytics_zoo_tpu.analysis.golden --update | --check
# ---------------------------------------------------------------------------
def _init_mesh():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    from analytics_zoo_tpu import init_orca_context
    init_orca_context("cpu-sim", mesh_axes={"dp": -1})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Golden program-contract snapshots for the train "
                    "step")
    ap.add_argument("--update", action="store_true",
                    help="regenerate tests/goldens/ from the current tree")
    ap.add_argument("--check", action="store_true",
                    help="diff current tree vs committed goldens; exit 1 "
                         "on drift")
    ap.add_argument("--path", default=None, help="golden file override")
    args = ap.parse_args(argv)
    _init_mesh()
    if args.update:
        contracts = capture_contracts()
        path = save_goldens(contracts, args.path)
        print(f"wrote {path}")
        for name in ["baseline"] + [n for n, _ in _SHARDING_LEGS]:
            print(f"  {name}: collectives={contracts[name]['collectives']}")
        return 0
    ok, delta = check(args.path)
    if ok:
        print("golden program contracts: OK "
              "(no drift vs tests/goldens/)")
        return 0
    print("golden program contracts DRIFTED (golden -> measured):")
    for line in delta:
        print(f"  {line}")
    print("if this change is intentional, regenerate with: "
          "python -m analytics_zoo_tpu.analysis.golden --update")
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
