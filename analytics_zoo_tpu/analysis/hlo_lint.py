"""Linter over lowered jaxpr/StableHLO programs.

The compile plane already sees every lowering in the process
(``ExecutableCache.obtain``), which makes it the one place a program-level
invariant can be checked *before* the executable exists — at lowering time
in CI, not in a bench regression five PRs later (the EQuARX /
MLPerf-TPU-pod lesson: wire-format and collective-count regressions are
silent until pod scale). Rules:

``f64-on-tpu``        64-bit float (or c128) tensors in a program lowered
                      for TPU — x64 leaked past the canonical-dtype wire.
``dtype-promotion``   ``stablehlo.convert`` widening a tensor to a 64-bit
                      element type: promotion happened *inside* the traced
                      program, so no input narrowing can fix it.
``undonated-input``   a donating program (train steps donate params + opt
                      state) keeps a >= ``ZOO_LINT_DONATION_MB`` input
                      buffer undonated — that buffer is held live across
                      the step for nothing.
``host-callback``     ``custom_call`` into a Python host callback inside a
                      train-labelled program — a device->host->device sync
                      every step.
``comms-accounting``  the collectives *measured from the compiled module*
                      must match what the engine declares for its layout
                      (:meth:`FsdpPlan.summary` plus its tp leaves,
                      registered via :func:`declare_accounting`). The
                      bookkeeping is **per mesh axis**: a collective's
                      ``replica_groups`` shape says which named axis it
                      runs over, so the fsdp leg's all-gathers must come
                      in whole sweeps of the declared buckets moving the
                      declared shard bytes, a train program must combine
                      gradients over the fsdp groups, and tp-sharded
                      leaves must bring tp collectives.

The hook (:func:`on_lowering`) is governed by ``ZOO_HLO_LINT``: ``warn``
(default — log + collect into :func:`lint_report`), ``strict`` (raise
:class:`HloLintError` on error-severity findings), ``0`` (off). It must
never break a training loop: everything it does is wrapped by the caller
in a broad guard, and findings deduplicate on the executable cache key so
re-lowerings don't re-report.
"""

from __future__ import annotations

import logging
import re
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..common import knobs

logger = logging.getLogger("analytics_zoo_tpu")

__all__ = ["CollectiveOp", "HloLintError", "HloLinter", "LintFinding",
           "collective_counts", "collectives_by_mesh_axes",
           "declare_accounting", "declared_accounting",
           "lint_report", "on_lowering", "parse_collectives"]

_ELEM_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
               "i64": 8, "i32": 4, "i16": 2, "i8": 1, "i4": 1, "i1": 1,
               "u64": 8, "u32": 4, "u16": 2, "u8": 1,
               "c64": 8, "c128": 16}

_TENSOR_RE = re.compile(r"tensor<([0-9x]*?)((?:f|bf|i|u|c)\d+)>")
_COLLECTIVE_RE = re.compile(
    r"\"?stablehlo\.(all_reduce|reduce_scatter|all_gather|all_to_all|"
    r"collective_permute)\"?\(")
# async collective start/done pairs — what XLA's latency-hiding scheduler
# emits when a collective overlaps compute (HLO `reduce-scatter-start` /
# `-done`, mhlo/stablehlo `_start`/`_done` forms). One start+done pair is
# ONE launch on the wire: starts count under the base kind, dones are
# skipped — otherwise an overlapped program double-counts every collective
# against the declared accounting.
_ASYNC_COLLECTIVE_RE = re.compile(
    r"[\"% ]\s*(?:stablehlo\.|mhlo\.)?"
    r"(all[-_]reduce|reduce[-_]scatter|all[-_]gather|all[-_]to[-_]all|"
    r"collective[-_]permute)[-_](start|done)\"?\(")
# hyphenated sync HLO text form: `%cp = s8[288]{0} collective-permute(...)`
# — what a ppermute ring looks like in an HLO dump. The caller checks for
# a preceding `=` (an op definition) so attribute/metadata strings can't
# false-match; the async start/done forms are matched (and consumed)
# first. Deliberately NO `=.*?` prefix in the pattern itself: the lazy
# scan goes quadratic on the megabyte-long `dense<...>` constant lines of
# real model lowerings (this regex runs on every line of every linted
# module).
_HLO_SYNC_RE = re.compile(
    r"[\s)](all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)\(")
_CONVERT_RE = re.compile(
    r"stablehlo\.convert\s.*:\s*\(tensor<([0-9x]*?)((?:f|bf|i|u|c)\d+)>\)"
    r"\s*->\s*tensor<[0-9x]*?((?:f|bf|i|u|c)\d+)>")
_CALLBACK_RE = re.compile(
    r"custom_call\s+@(\w*(?:python|callback|py_func)\w*)")
_SIG_RE = re.compile(r":\s*\(([^)]*)\)\s*->")


class HloLintError(RuntimeError):
    """Raised in strict mode when a lowering has error-severity findings."""


@dataclass
class LintFinding:
    rule: str
    severity: str          # "error" | "warning"
    label: str             # compile-plane label of the program
    message: str
    details: Dict[str, Any] = field(default_factory=dict)

    def __str__(self):
        return (f"[{self.severity}] {self.rule} ({self.label or '?'}): "
                f"{self.message}")


@dataclass
class CollectiveOp:
    kind: str              # all_reduce / reduce_scatter / all_gather / ...
    operand_bytes: int
    result_bytes: int
    # replica-group shape (num_groups, group_size) from the op's
    # replica_groups attribute — what classifies a collective onto a named
    # mesh axis. None when the op carries no groups (pre-groups modules).
    group_shape: Optional[Tuple[int, int]] = None


# stablehlo/mhlo attribute form: replica_groups = dense<...> : tensor<GxSxi64>
_GROUPS_DENSE_RE = re.compile(
    r"replica_groups\s*=\s*dense<[^>]*>\s*:\s*tensor<(\d+)x(\d+)xi64>")
# HLO text form: replica_groups={{0,1,2,3},{4,5,6,7}}
_GROUPS_HLO_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
# HLO iota form: replica_groups=[2,4]<=[4,2]T(1,0) — G groups of S members
# listed as a transposed iota (what the SPMD partitioner emits for an
# all-gather over one named axis of a multi-axis mesh)
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
# collective_permute carries source_target_pairs instead of replica_groups.
# stablehlo/mhlo: source_target_pairs = dense<[[0,1],[1,0]]> : tensor<Nx2xi64>
_PAIRS_DENSE_RE = re.compile(
    r"source_target_pairs\s*=\s*dense<([^>]*)>\s*:\s*tensor<\d+x2xi64>")
# HLO text: source_target_pairs={{0,1},{1,0}}
_PAIRS_HLO_RE = re.compile(
    r"source_target_pairs=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")


def _permute_group_shape(line: str) -> Optional[Tuple[int, int]]:
    """Replica-group shape equivalent for a ``collective_permute``:
    connected components of its undirected source->target pairs graph.
    A ring inside each group of an axis gives the same ``(groups, size)``
    shape a grouped collective over that axis declares."""
    m = _PAIRS_DENSE_RE.search(line)
    if m is not None:
        vals = [int(t) for t in re.findall(r"-?\d+", m.group(1))]
        pairs = list(zip(vals[0::2], vals[1::2]))
    else:
        m = _PAIRS_HLO_RE.search(line)
        if m is None:
            return None
        pairs = []
        for g in re.findall(r"\{([^}]*)\}", m.group(1)):
            t = [int(x) for x in g.split(",") if x.strip()]
            if len(t) == 2:
                pairs.append((t[0], t[1]))
    if not pairs:
        return None
    parent: Dict[int, int] = {}

    def _find(x: int) -> int:
        parent.setdefault(x, x)
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in pairs:
        ra, rb = _find(a), _find(b)
        if ra != rb:
            parent[ra] = rb
    comps: Dict[int, set] = {}
    for d in parent:
        comps.setdefault(_find(d), set()).add(d)
    sizes = {len(c) for c in comps.values()}
    if len(sizes) == 1:
        return len(comps), sizes.pop()
    return None


def _group_shape(line: str) -> Optional[Tuple[int, int]]:
    m = _GROUPS_DENSE_RE.search(line)
    if m is not None:
        return int(m.group(1)), int(m.group(2))
    m = _GROUPS_IOTA_RE.search(line)
    if m is not None:
        return int(m.group(1)), int(m.group(2))
    m = _GROUPS_HLO_RE.search(line)
    if m is not None:
        groups = re.findall(r"\{([^}]*)\}", m.group(1))
        sizes = {len([t for t in g.split(",") if t.strip()])
                 for g in groups}
        if len(sizes) == 1:
            return len(groups), sizes.pop()
    return _permute_group_shape(line)


def _tensor_bytes(types: str) -> int:
    total = 0
    for dims, elem in _TENSOR_RE.findall(types):
        n = 1
        for d in dims.split("x"):
            if d:
                n *= int(d)
        total += n * _ELEM_BYTES.get(elem, 4)
    return total


# HLO text type tokens (`s8[288]{0}`) — byte accounting for modules that
# arrive as an HLO dump rather than stablehlo (no `: (...) -> ...`
# signature line to parse)
_HLO_TYPE_RE = re.compile(
    r"\b(pred|bf16|f16|f32|f64|s4|s8|s16|s32|s64|u8|u16|u32|u64|c64|c128)"
    r"\[([0-9,]*)\]")
_HLO_ELEM_ALIAS = {"pred": "i1", "s4": "i4", "s8": "i8", "s16": "i16",
                   "s32": "i32", "s64": "i64"}


def _hlo_type_bytes(elem: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _ELEM_BYTES.get(_HLO_ELEM_ALIAS.get(elem, elem), 4)


def _hlo_text_bytes(segment: str) -> int:
    return sum(_hlo_type_bytes(elem, dims)
               for elem, dims in _HLO_TYPE_RE.findall(segment))


def parse_collectives(text: str) -> List[CollectiveOp]:
    """Collective ops in a StableHLO module, with operand/result byte
    sizes taken from their type signatures. Ops with a reduction region
    (all_reduce, reduce_scatter) carry the signature on the region-closing
    ``}) : (...) -> ...`` line; region-free ops carry it inline.

    Async start/done-style collectives (an overlapped program's
    ``reduce-scatter-start`` / ``-done`` pairs) count as ONE launch of the
    base kind: the ``start`` carries the wire operand and is recorded, the
    matching ``done`` is skipped."""
    out = []
    lines = text.splitlines()

    def _signature(i: int, call: int):
        """The op's type signature — on its own line, or (for ops carrying
        a reduction region, sync AND async-start forms alike) on the
        region-closing ``}) : (...) -> ...`` line further down. ``call``
        is where the op's argument list opens on line ``i``."""
        sig_line = lines[i]
        if _SIG_RE.search(sig_line) is None:
            for j in range(i + 1, min(i + 40, len(lines))):
                if "}) :" in lines[j] or "}> :" in lines[j]:
                    sig_line = lines[j]
                    break
        sig = _SIG_RE.search(sig_line)
        if sig is not None:
            return _tensor_bytes(sig.group(1)), _tensor_bytes(
                sig_line[sig.end():])
        # HLO text form: `%cp = s8[288]{0} collective-permute(s8[288] %p)`
        # — result type after the `=`, operand types (when annotated)
        # inside the call parens. An async start is typed as the tuple
        # `(operand, result)`. Some XLA builds print operands bare
        # (`all-gather(%param)`): the operand's size then follows from the
        # result's and the group size, whose ratio the op's kind fixes.
        line = lines[i]
        head, inner = line[:call], line[call + 1:line.find(")", call)]
        types = [_hlo_type_bytes(*t) for t in _HLO_TYPE_RE.findall(head)]
        if head.endswith("-start") and len(types) >= 2:
            return types[0], types[1]
        result = sum(types)
        operand = _hlo_text_bytes(inner)
        if not operand:
            size = (_group_shape(line) or (1, 1))[1]
            if head.endswith("all-gather"):
                operand = result // size
            elif head.endswith("reduce-scatter"):
                operand = result * size
            else:
                operand = result
        return operand, result

    for i, line in enumerate(lines):
        m = _ASYNC_COLLECTIVE_RE.search(line)
        if m is not None:
            if m.group(2) == "done":
                continue                      # the pair's start was counted
            operand, result = _signature(i, m.end() - 1)
            out.append(CollectiveOp(kind=m.group(1).replace("-", "_"),
                                    operand_bytes=operand,
                                    result_bytes=result,
                                    group_shape=_group_shape(line)))
            continue
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            m = _HLO_SYNC_RE.search(line)
            if m is not None and "=" not in line[:m.start()]:
                m = None                      # not an op definition
        if not m:
            continue
        operand, result = _signature(i, m.end() - 1)
        out.append(CollectiveOp(kind=m.group(1).replace("-", "_"),
                                operand_bytes=operand,
                                result_bytes=result,
                                group_shape=_group_shape(line)))
    return out


def collective_counts(ops: Sequence[CollectiveOp]) -> Dict[str, int]:
    """Launches by collective kind (shared with the golden capture)."""
    counts: Dict[str, int] = {}
    for op in ops:
        counts[op.kind] = counts.get(op.kind, 0) + 1
    return counts


def collectives_by_mesh_axes(ops: Sequence[CollectiveOp],
                             axis_sizes: Dict[str, int]) -> Dict[str, Any]:
    """Classify collectives onto named mesh axes by replica-group shape:
    a collective over axis ``a`` of size ``s`` on an ``n``-device mesh runs
    ``n/s`` groups of ``s`` members. ``axis_sizes`` maps axis name -> size
    (trivial axes may be included; they classify nothing). Ops matching no
    axis — or carrying no groups — land in ``global``. Two nontrivial axes
    of EQUAL size produce identical shapes; the result is then flagged
    ``ambiguous`` (first listed axis wins the label) and callers must fall
    back to combined totals. Shared by the accounting rule, the golden
    capture's fsdp/tp legs and ``bench --only sharding``."""
    n = 1
    for s in axis_sizes.values():
        n *= int(s)
    shapes: Dict[Tuple[int, int], str] = {}
    ambiguous = False
    for name, s in axis_sizes.items():
        s = int(s)
        if s <= 1:
            continue
        shape = (n // s, s)
        if shape in shapes:
            ambiguous = True
            continue
        shapes[shape] = name
    out: Dict[str, Any] = {"by_axis": {name: {} for name in shapes.values()},
                           "axis_bytes": {name: {} for name in shapes.values()},
                           "global": {}, "ambiguous": ambiguous}
    for op in ops:
        name = shapes.get(op.group_shape) if op.group_shape else None
        if name is None:
            out["global"][op.kind] = out["global"].get(op.kind, 0) + 1
            continue
        out["by_axis"][name][op.kind] = (
            out["by_axis"][name].get(op.kind, 0) + 1)
        out["axis_bytes"][name][op.kind] = (
            out["axis_bytes"][name].get(op.kind, 0) + op.operand_bytes)
    return out


# ---------------------------------------------------------------------------
# declared accounting (the engine registers, the linter verifies)
# ---------------------------------------------------------------------------
_declared_lock = threading.Lock()
_declared: Dict[str, Dict[str, Any]] = {}


def declare_accounting(key: str, summary: Dict[str, Any]) -> None:
    """Register a layout's declared per-step collectives
    (:meth:`FsdpPlan.summary` plus the tp leaves) under the engine's
    sharding fingerprint — the same ``extra_key`` its executables are
    salted with, so the linter can pair a program with exactly the
    accounting that claims to describe it."""
    if not key:
        return
    with _declared_lock:
        _declared[str(key)] = dict(summary)


def declared_accounting(key: Optional[str]) -> Optional[Dict[str, Any]]:
    if key is None:
        return None
    with _declared_lock:
        return _declared.get(str(key))


# ---------------------------------------------------------------------------
# the linter
# ---------------------------------------------------------------------------
class HloLinter:
    """One ruleset pass over one lowered program's StableHLO text.

    ``target`` is the backend the program will run on ("tpu"/"cpu"/"gpu";
    None = ``jax.default_backend()``) — backend-conditional rules (f64)
    only fire for TPU targets. ``donation_threshold_mb`` overrides
    ``ZOO_LINT_DONATION_MB``."""

    def __init__(self, target: Optional[str] = None,
                 donation_threshold_mb: Optional[float] = None,
                 rules: Optional[Sequence[str]] = None,
                 record_verified: bool = False):
        self.target = target
        self.donation_threshold_mb = donation_threshold_mb
        self.rules = set(rules) if rules is not None else None
        # only the compile-plane hook records passing accounting cross-checks
        # into the process-wide report; a standalone linter (golden
        # capture, notebooks, tests) must not inflate that counter
        self.record_verified = record_verified

    def _on(self, rule: str) -> bool:
        return self.rules is None or rule in self.rules

    def _backend(self) -> str:
        if self.target is not None:
            return self.target
        try:
            import jax
            return jax.default_backend()
        except Exception:  # noqa: BLE001 — no backend: be conservative
            return "cpu"

    # -- entry point ---------------------------------------------------------
    def lint_text(self, text: str, label: str = "",
                  donate_argnums: Sequence[int] = (),
                  arg_bytes: Optional[Sequence[int]] = None,
                  declared: Optional[Dict[str, Any]] = None
                  ) -> List[LintFinding]:
        """Lint one module. ``arg_bytes`` is the per-positional-arg total
        buffer size (what :func:`on_lowering` computes from the call's
        actual pytrees); ``declared`` is the layout's accounting to verify
        against (None = skip the accounting rule)."""
        findings: List[LintFinding] = []
        if self._on("f64-on-tpu"):
            findings += self._rule_f64(text, label)
        if self._on("dtype-promotion"):
            findings += self._rule_promotion(text, label)
        if self._on("host-callback"):
            findings += self._rule_callback(text, label)
        if self._on("undonated-input") and arg_bytes:
            findings += self._rule_donation(label, donate_argnums, arg_bytes)
        if self._on("comms-accounting") and declared is not None:
            findings += self._rule_accounting(text, label, declared)
        return findings

    def lint_lowered(self, lowered, label: str = "",
                     donate_argnums: Sequence[int] = (),
                     args: Optional[Tuple] = None,
                     declared: Optional[Dict[str, Any]] = None,
                     text: Optional[str] = None) -> List[LintFinding]:
        """``text`` lets a caller that already rendered the module (the
        compile plane keys on the same text) avoid a second as_text()."""
        return self.lint_text(text if text is not None
                              else lowered.as_text(), label=label,
                              donate_argnums=donate_argnums,
                              arg_bytes=(arg_sizes(args)
                                         if args is not None else None),
                              declared=declared)

    # -- rules ---------------------------------------------------------------
    def _rule_f64(self, text: str, label: str) -> List[LintFinding]:
        if self._backend() != "tpu":
            return []
        hits = {elem for _, elem in _TENSOR_RE.findall(text)
                if elem in ("f64", "c128")}
        if not hits:
            return []
        return [LintFinding(
            rule="f64-on-tpu", severity="error", label=label,
            message=(f"{'/'.join(sorted(hits))} tensors reach a TPU "
                     f"program — x64 leaked past the canonical-dtype "
                     f"wire (narrow_wire / jax_enable_x64)"),
            details={"dtypes": sorted(hits)})]

    def _rule_promotion(self, text: str, label: str) -> List[LintFinding]:
        findings = []
        seen = set()
        for dims, src, dst in _CONVERT_RE.findall(text):
            if dst not in ("f64", "i64", "u64", "c128"):
                continue
            if _ELEM_BYTES.get(src, 8) >= _ELEM_BYTES.get(dst, 8):
                continue                      # narrowing or same width
            if (src, dst) in seen:
                continue
            seen.add((src, dst))
            sev = ("error" if dst in ("f64", "c128")
                   and self._backend() == "tpu" else "warning")
            findings.append(LintFinding(
                rule="dtype-promotion", severity=sev, label=label,
                message=(f"convert {src}->{dst} inside the traced program "
                         f"— a 64-bit promotion no input narrowing can "
                         f"undo"),
                details={"from": src, "to": dst}))
        return findings

    def _rule_callback(self, text: str, label: str) -> List[LintFinding]:
        targets = sorted(set(_CALLBACK_RE.findall(text)))
        if not targets:
            return []
        in_step = label.startswith("train")
        return [LintFinding(
            rule="host-callback",
            severity="error" if in_step else "warning", label=label,
            message=(f"host callback(s) {', '.join(targets)} inside "
                     + ("the train step — a device->host->device sync "
                        "every step" if in_step else "a jitted program")),
            details={"targets": targets})]

    def _rule_donation(self, label: str, donate_argnums: Sequence[int],
                       arg_bytes: Sequence[int]) -> List[LintFinding]:
        if not donate_argnums or not label.startswith("train"):
            # a non-donating program (predict) holds its inputs by design,
            # and eval legitimately keeps params live across batches (only
            # its metric states are donated); the rule is about buffers
            # forgotten by a *train* step that already donates its state
            return []
        threshold = self.donation_threshold_mb
        if threshold is None:
            threshold = knobs.get("ZOO_LINT_DONATION_MB")
        limit = float(threshold) * 1024 * 1024
        donated = set(int(i) for i in donate_argnums)
        findings = []
        for i, nbytes in enumerate(arg_bytes):
            if i in donated or nbytes < limit:
                continue
            findings.append(LintFinding(
                rule="undonated-input", severity="warning", label=label,
                message=(f"arg {i} ({nbytes / 2**20:.1f} MiB) is not "
                         f"donated in a donating program — that buffer "
                         f"stays live across the step"),
                details={"argnum": i, "bytes": int(nbytes),
                         "threshold_mb": float(threshold)}))
        return findings

    def _rule_accounting(self, text: str, label: str,
                         declared: Dict[str, Any]) -> List[LintFinding]:
        """Per-mesh-axis accounting for the sharding plane (the engine
        declares :meth:`FsdpPlan.summary` plus tp info): the fsdp leg's
        all-gather launches must be whole sweeps of the declared buckets
        moving exactly sweep × shard bytes, a train program must combine
        grads over the fsdp groups, and a program with tp-sharded leaves
        must actually launch tp collectives.

        The sharding plane's collectives exist only AFTER the SPMD
        partitioner runs — a pre-partition StableHLO module (what the
        compile-plane hook lints) legitimately contains none, so an
        op-free module passes; the compiled-HLO cross-check runs where
        the compiled text is in hand (golden capture, bench)."""
        ops = parse_collectives(text)
        if not ops:
            return []
        fsdp = declared.get("fsdp") or {}
        axes = dict(fsdp.get("axes") or {})
        axis = fsdp.get("axis", "fsdp")
        buckets = int(fsdp.get("buckets") or 0)
        ax = collectives_by_mesh_axes(ops, axes)
        findings: List[LintFinding] = []

        def _fail(msg, **details):
            findings.append(LintFinding(
                rule="comms-accounting", severity="error", label=label,
                message=msg,
                details={"by_axis": ax["by_axis"], "global": ax["global"],
                         "declared": declared, **details}))

        if ax["ambiguous"]:
            # two nontrivial axes of equal size: group shapes cannot tell
            # the legs apart; only the combined gather-launch multiple
            # stays checkable
            total_ag = sum(leg.get("all_gather", 0)
                           for leg in ax["by_axis"].values())
            if buckets and (total_ag < buckets or total_ag % buckets):
                _fail(f"program launches {total_ag} grouped all-gathers — "
                      f"not a whole number of {buckets}-bucket sweeps "
                      f"(equal-size axes: legs indistinguishable)")
            if not findings and self.record_verified:
                _record_verified()
            return findings
        leg = ax["by_axis"].get(axis, {})
        if buckets:
            ag = leg.get("all_gather", 0)
            if ag < buckets or ag % buckets != 0:
                _fail(f"fsdp leg launches {ag} all-gathers but accounting "
                      f"declares {buckets} buckets per assembly sweep")
            else:
                sweeps = ag // buckets
                measured = ax["axis_bytes"][axis].get("all_gather", 0)
                want = sweeps * int(
                    fsdp.get("gather_shard_bytes_per_sweep") or 0)
                if measured != want:
                    _fail(f"fsdp gathers move {measured} B/step in the "
                          f"lowered program but accounting declares "
                          f"{want} B/step ({sweeps} sweep(s) x "
                          f"{fsdp.get('gather_shard_bytes_per_sweep')} B)",
                          measured_gather_bytes=measured)
            if label.startswith("train"):
                combine = (leg.get("all_reduce", 0)
                           + leg.get("reduce_scatter", 0))
                if combine < 1:
                    _fail("train program combines no gradients over the "
                          "fsdp groups (no all-reduce/reduce-scatter on "
                          "the fsdp leg)")
        tp = declared.get("tp") or {}
        if int(tp.get("axis_size") or 1) > 1 and int(
                tp.get("sharded_leaves") or 0) > 0:
            tleg = ax["by_axis"].get(tp.get("axis", "tp"), {})
            if sum(tleg.values()) < 1:
                _fail(f"{tp.get('sharded_leaves')} tp-sharded leaves "
                      f"declared but the tp leg launches no collectives")
        if not findings and self.record_verified:
            _record_verified()
        return findings


def arg_sizes(args: Tuple) -> List[int]:
    """Total buffer bytes per top-level positional arg."""
    import jax
    sizes = []
    for arg in args:
        total = 0
        for leaf in jax.tree_util.tree_leaves(arg):
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is None:
                shape = getattr(leaf, "shape", None)
                dtype = getattr(leaf, "dtype", None)
                if shape is None or dtype is None:
                    continue
                n = 1
                for d in shape:
                    n *= int(d)
                nbytes = n * getattr(dtype, "itemsize", 4)
            total += int(nbytes)
        sizes.append(total)
    return sizes


# ---------------------------------------------------------------------------
# process-wide report + the compile-plane hook
# ---------------------------------------------------------------------------
_report_lock = threading.Lock()
_findings: List[LintFinding] = []
_seen_keys: set = set()
_error_keys: Dict[str, str] = {}    # dedup key -> strict-mode error message
_programs_linted = 0
_comms_verified = 0


def _record_verified() -> None:
    global _comms_verified
    with _report_lock:
        _comms_verified += 1


def lint_report(reset: bool = False) -> Dict[str, Any]:
    """Cumulative hook findings: programs linted, findings by rule, and
    the accounting cross-checks that PASSED (measured==declared).
    ``scripts/run_tier1.sh`` prints this as the ``ANALYSIS=`` snapshot."""
    with _report_lock:
        by_rule: Dict[str, int] = {}
        for f in _findings:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        snap = {"programs_linted": _programs_linted,
                "findings": [{"rule": f.rule, "severity": f.severity,
                              "label": f.label, "message": f.message}
                             for f in _findings],
                "by_rule": by_rule,
                "comms_verified": _comms_verified}
        if reset:
            _reset_locked()
        return snap


def _reset_locked():
    global _programs_linted, _comms_verified
    _findings.clear()
    _seen_keys.clear()
    _error_keys.clear()
    _comms_verified = 0
    _programs_linted = 0


def reset_report():
    with _report_lock:
        _reset_locked()


def on_lowering(label: str, lowered, donate_argnums: Sequence[int] = (),
                args: Optional[Tuple] = None,
                extra_key: Optional[str] = None,
                key: Optional[str] = None,
                text: Optional[str] = None) -> List[LintFinding]:
    """Compile-plane hook: lint one lowering before it compiles.

    Called by ``ExecutableCache.obtain`` with the cache ``key`` for
    dedup — a program is linted once per structural identity no matter
    how many signatures or engines re-lower it. Mode rides
    ``ZOO_HLO_LINT`` (warn | strict | 0)."""
    global _programs_linted
    mode = str(knobs.get("ZOO_HLO_LINT") or "warn").lower()
    if mode in ("0", "off", "false", "no", ""):
        return []
    dedup = key or f"{label}:{extra_key}"
    with _report_lock:
        # check-and-claim in ONE critical section: two threads lowering
        # the same program concurrently must not both lint and
        # double-count it
        cached_error = _error_keys.get(dedup)
        if cached_error is None:
            if dedup in _seen_keys:
                return []
            _seen_keys.add(dedup)
            _programs_linted += 1
    if cached_error is not None:
        # a supervisor/estimator retry re-lowers the same blocked
        # program: re-raise without re-recording (counters and findings
        # already carry it exactly once)
        if mode == "strict":
            raise HloLintError(cached_error)
        return []
    linter = HloLinter(record_verified=True)
    findings = linter.lint_lowered(
        lowered, label=label, donate_argnums=donate_argnums, args=args,
        declared=declared_accounting(extra_key), text=text)
    if findings:
        with _report_lock:
            _findings.extend(findings)
        for f in findings:
            logger.warning("hlo-lint %s", f)
        if mode == "strict" and any(f.severity == "error" for f in findings):
            # the raise blocks this compile, but a supervisor/estimator
            # retry re-lowers the SAME program under the same key —
            # remember the error so every retry re-raises (instead of
            # sailing past the gate as "already linted") without
            # double-counting the findings
            msg = "; ".join(str(f) for f in findings
                            if f.severity == "error")
            with _report_lock:
                _error_keys[dedup] = msg
            raise HloLintError(msg)
    return findings
