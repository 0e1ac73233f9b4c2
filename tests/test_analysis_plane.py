"""Analysis plane (PR 9): StableHLO linter, golden program contracts,
runtime race detector, repo lint, knob registry.

Every lint rule is proven by a *seeded violation* (a planted f64
promotion, an undonated buffer, a host callback in a train step, a
lock-order inversion under two threads, an unregistered knob read, ...)
and by staying silent on the clean tree — the acceptance criteria of
ISSUE 9. The golden program-contract gate is shown to fail on an injected
collective-count regression, and the committed goldens carry
``accounting_verified: true`` for every sharded leg (launches and bytes
measured per mesh axis in the compiled program == the accounting the engine
declares for its layout).
"""

import json
import textwrap
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from analytics_zoo_tpu.analysis import golden as golden_mod
from analytics_zoo_tpu.analysis import hlo_lint, repolint
from analytics_zoo_tpu.analysis.hlo_lint import (HloLinter, HloLintError,
                                                 lint_report, on_lowering,
                                                 parse_collectives,
                                                 reset_report)
from analytics_zoo_tpu.analysis.races import RaceDetector
from analytics_zoo_tpu.common import knobs
from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator


# ---------------------------------------------------------------------------
# hlo_lint: per-rule seeded violations + clean-tree silence
# ---------------------------------------------------------------------------
def test_f64_rule_fires_on_planted_x64_program():
    """A real jax lowering with x64 enabled leaks f64 tensors; the rule
    fires for a TPU target and stays silent for CPU (where f64 is legal)."""
    with jax.enable_x64(True):
        lowered = jax.jit(lambda x: x * 2.0).lower(
            jnp.ones((8, 8), jnp.float64))
        text = lowered.as_text()
    tpu = HloLinter(target="tpu").lint_text(text, label="train")
    assert any(f.rule == "f64-on-tpu" and f.severity == "error"
               for f in tpu)
    assert not HloLinter(target="cpu").lint_text(text, label="train")


def test_f64_rule_silent_on_clean_f32_program():
    text = jax.jit(lambda x: x * 2.0).lower(
        jnp.ones((8, 8), jnp.float32)).as_text()
    assert HloLinter(target="tpu").lint_text(text, label="train") == []


def test_promotion_rule_fires_on_planted_f64_promotion():
    """An astype(f64) *inside* the traced program is a promotion no input
    narrowing can undo — exactly what the rule exists for."""
    with jax.enable_x64(True):
        text = jax.jit(lambda x: x.astype(jnp.float64) * 2.0).lower(
            jnp.ones((8,), jnp.float32)).as_text()
    found = HloLinter(target="tpu").lint_text(text, label="train")
    promos = [f for f in found if f.rule == "dtype-promotion"]
    assert promos and promos[0].details == {"from": "f32", "to": "f64"}
    assert promos[0].severity == "error"          # f64 on a TPU target
    # narrowing converts (f64 -> f32) must NOT fire the rule
    with jax.enable_x64(True):
        narrow = jax.jit(lambda x: x.astype(jnp.float32)).lower(
            jnp.ones((8,), jnp.float64)).as_text()
    assert not [f for f in HloLinter(target="cpu").lint_text(narrow)
                if f.rule == "dtype-promotion"]


def test_host_callback_rule_fires_inside_train_step():
    def step(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct((8,), jnp.float32), x)
        return y + 1.0

    text = jax.jit(step).lower(jnp.ones((8,), jnp.float32)).as_text()
    found = HloLinter(target="cpu").lint_text(text, label="train")
    cbs = [f for f in found if f.rule == "host-callback"]
    assert cbs and cbs[0].severity == "error"     # train-labelled program
    # same program under a non-train label is only a warning
    found = HloLinter(target="cpu").lint_text(text, label="predict")
    assert [f.severity for f in found
            if f.rule == "host-callback"] == ["warning"]


def test_undonated_input_rule_fires_and_respects_threshold():
    linter = HloLinter(target="cpu", donation_threshold_mb=1.0)
    mib = 1024 * 1024
    found = linter.lint_text("", label="train", donate_argnums=(0,),
                             arg_bytes=[8 * mib, 4 * mib, 100])
    hits = [f for f in found if f.rule == "undonated-input"]
    assert [f.details["argnum"] for f in hits] == [1]   # 0 donated, 2 tiny
    # non-donating programs and eval/predict labels are exempt by design
    assert not linter.lint_text("", label="train", donate_argnums=(),
                                arg_bytes=[8 * mib])
    assert not linter.lint_text("", label="eval", donate_argnums=(2,),
                                arg_bytes=[8 * mib, 0, 0])


_SYNTH_MODULE = textwrap.dedent("""\
    module @jit_step {
      func.func public @main(%arg0: tensor<840xf32>) -> tensor<840xf32> {
        %0 = "stablehlo.reduce_scatter"(%arg0) <{scatter_dimension = 0 : i64}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<840xf32>) -> tensor<105xf32>
        %1 = "stablehlo.all_gather"(%0) <{all_gather_dim = 0 : i64}> : (tensor<105xf32>) -> tensor<840xf32>
        return %1 : tensor<840xf32>
      }
    }
    """)


def test_parse_collectives_reads_region_and_inline_signatures():
    ops = parse_collectives(_SYNTH_MODULE)
    kinds = {op.kind for op in ops}
    assert kinds == {"reduce_scatter", "all_gather"}
    rs = next(op for op in ops if op.kind == "reduce_scatter")
    assert rs.operand_bytes == 840 * 4 and rs.result_bytes == 105 * 4
    ag = next(op for op in ops if op.kind == "all_gather")
    assert ag.operand_bytes == 105 * 4 and ag.result_bytes == 840 * 4


def _declared_fsdp(axes, shard_bytes, buckets=1, tp_leaves=0):
    """What the engine declares for a SpecLayout (FsdpPlan.summary() plus
    its tp leaves), cut to the fields the accounting rule reads."""
    return {"plane": "sharding",
            "fsdp": {"axis": "fsdp", "axes": dict(axes), "buckets": buckets,
                     "gather_shard_bytes_per_sweep": shard_bytes},
            "tp": {"axis": "tp", "axis_size": axes.get("tp", 1),
                   "sharded_leaves": tp_leaves}}


_ASYNC_MODULE = textwrap.dedent("""\
    module @jit_step_async {
      func.func public @main(%arg0: tensor<840xf32>) -> tensor<840xf32> {
        %0 = "stablehlo.reduce_scatter_start"(%arg0) <{scatter_dimension = 0 : i64}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<840xf32>) -> tensor<105xf32>
        %1 = "stablehlo.reduce_scatter_done"(%0) : (tensor<105xf32>) -> tensor<105xf32>
        %2 = "stablehlo.all_gather_start"(%1) : (tensor<105xf32>) -> tensor<840xf32>
        %3 = "stablehlo.all_gather_done"(%2) : (tensor<840xf32>) -> tensor<840xf32>
        return %3 : tensor<840xf32>
      }
    }
    """)


def test_parse_collectives_counts_async_start_done_pairs_once():
    """Start/done-style async collectives (what XLA's latency-hiding
    scheduler emits for an overlapped program, PR 11) are ONE launch per
    pair: the start carries the wire operand — including when it carries
    a reduction REGION, where the signature sits on the region-closing
    line (how reduce_scatter_start actually prints) — and the done is
    skipped; double-counting would fail every overlapped program's
    accounting."""
    ops = parse_collectives(_ASYNC_MODULE)
    kinds = [op.kind for op in ops]
    assert sorted(kinds) == ["all_gather", "reduce_scatter"]
    rs = next(op for op in ops if op.kind == "reduce_scatter")
    assert rs.operand_bytes == 840 * 4 and rs.result_bytes == 105 * 4
    # HLO-text style (hyphenated) counts the same way, launches only
    hlo = ("%rs = f32[105] reduce-scatter-start(%p)\n"
           "%rsd = f32[105] reduce-scatter-done(%rs)\n")
    assert [op.kind for op in parse_collectives(hlo)] == ["reduce_scatter"]
    # and the accounting rule accepts an async pair as the declared bucket
    hlo = ("%ags = (f32[104], f32[832]) all-gather-start(f32[104]{0} %p), "
           "replica_groups=[1,8]<=[8], dimensions={0}\n"
           "%agd = f32[832] all-gather-done(%ags)\n"
           "%ar = f32[832] all-reduce(f32[832] %g), "
           "replica_groups=[1,8]<=[8], to_apply=%add\n")
    assert parse_collectives(hlo)[0].operand_bytes == 416
    assert HloLinter(target="cpu").lint_text(
        hlo, label="train", declared=_declared_fsdp({"fsdp": 8}, 416)) == []
    # the start's type is the tuple (operand, result): bare operands or not
    bare = hlo.replace("f32[104]{0} %p", "%p")
    assert parse_collectives(bare)[0].operand_bytes == 416


_PERMUTE_MODULE = textwrap.dedent("""\
    module @jit_step_ring {
      func.func public @main(%arg0: tensor<288xi8>) -> tensor<288xi8> {
        %0 = "stablehlo.collective_permute"(%arg0) <{source_target_pairs = dense<[[0, 4], [4, 0], [1, 5], [5, 1], [2, 6], [6, 2], [3, 7], [7, 3]]> : tensor<8x2xi64>}> : (tensor<288xi8>) -> tensor<288xi8>
        %1 = "stablehlo.all_to_all"(%0) <{replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>}> : (tensor<288xi8>) -> tensor<288xi8>
        return %1 : tensor<288xi8>
      }
    }
    """)


def test_parse_collectives_recognizes_permute_and_all_to_all():
    """PR 16: a ppermute-based wire must be visible to the accounting
    gate. stablehlo sync, async start/done, and hyphenated HLO-text forms
    all count with dtype-true (int8, not x4) bytes, and a permute's
    source->target pairs classify it onto a mesh axis the way
    replica_groups classify a reduce-scatter."""
    from analytics_zoo_tpu.analysis.hlo_lint import collectives_by_mesh_axes
    ops = parse_collectives(_PERMUTE_MODULE)
    assert sorted(op.kind for op in ops) == ["all_to_all",
                                             "collective_permute"]
    cp = next(op for op in ops if op.kind == "collective_permute")
    assert cp.operand_bytes == 288            # int8: one byte per element
    # 4 disjoint 2-cycles == the group shape of the tp axis on fsdp=4 x tp=2
    assert cp.group_shape == (4, 2)
    a2a = next(op for op in ops if op.kind == "all_to_all")
    assert a2a.operand_bytes == 288 and a2a.group_shape == (1, 8)
    by = collectives_by_mesh_axes(ops, {"fsdp": 4, "tp": 2})
    assert by["by_axis"]["tp"] == {"collective_permute": 1}
    assert by["axis_bytes"]["tp"] == {"collective_permute": 288}
    assert by["global"] == {"all_to_all": 1}      # over all 8, no one axis
    # async start/done pair = ONE launch (what the latency-hiding
    # scheduler emits when the ring hop overlaps compute)
    async_txt = (
        '%0 = "stablehlo.collective_permute_start"(%arg0) '
        '<{source_target_pairs = dense<[[0, 1], [1, 0]]> : tensor<2x2xi64>}>'
        ' : (tensor<96xi8>) -> tensor<96xi8>\n'
        '%1 = "stablehlo.collective_permute_done"(%0) '
        ': (tensor<96xi8>) -> tensor<96xi8>\n')
    ops = parse_collectives(async_txt)
    assert [op.kind for op in ops] == ["collective_permute"]
    assert ops[0].operand_bytes == 96 and ops[0].group_shape == (1, 2)
    # hyphenated HLO text: bytes come from the s8[...] type tokens (no
    # stablehlo tensor<> signature to read), sync and start/done alike
    hlo = ("%cp = s8[288]{0} collective-permute(s8[288]{0} %p), "
           "source_target_pairs={{0,4},{4,0},{1,5},{5,1},"
           "{2,6},{6,2},{3,7},{7,3}}\n"
           "%cps = s8[96] collective-permute-start(s8[96] %q), "
           "source_target_pairs={{0,1},{1,0}}\n"
           "%cpd = s8[96] collective-permute-done(%cps)\n")
    ops = parse_collectives(hlo)
    assert [op.kind for op in ops] == ["collective_permute"] * 2
    assert ops[0].operand_bytes == 288 and ops[0].group_shape == (4, 2)
    assert ops[1].operand_bytes == 96 and ops[1].group_shape == (1, 2)


# a compiled fsdp=4 x tp=2 train step in miniature: one bucket's gather over
# the fsdp groups (2 groups of 4), the gradient combine over the same
# groups, the row-parallel matmul's combine over the tp groups (4 of 2)
_SHARDED_HLO = (
    "%ag = f32[608]{0} all-gather(f32[152]{0} %p), channel_id=1, "
    "replica_groups=[2,4]<=[4,2]T(1,0), dimensions={0}\n"
    "%ar = f32[608]{0} all-reduce(f32[608]{0} %g), channel_id=2, "
    "replica_groups=[2,4]<=[4,2]T(1,0), to_apply=%add\n"
    "%tp = f32[4,32]{1,0} all-reduce(f32[4,32]{1,0} %h), channel_id=3, "
    "replica_groups=[4,2]<=[8], to_apply=%add\n")


def test_comms_accounting_rule_verifies_and_catches_drift():
    declared = _declared_fsdp({"fsdp": 4, "tp": 2}, 152 * 4, tp_leaves=3)
    linter = HloLinter(target="cpu")
    assert linter.lint_text(_SHARDED_HLO, label="train",
                            declared=declared) == []
    # an XLA build that prints operands bare reads the same bytes: a
    # gather's operand is its result over the group size
    bare = _SHARDED_HLO.replace("f32[152]{0} %p", "%p")
    assert parse_collectives(bare)[0].operand_bytes == 152 * 4
    assert linter.lint_text(bare, label="train", declared=declared) == []
    # an injected byte regression (declared != compiled) must fail
    bad = _declared_fsdp({"fsdp": 4, "tp": 2}, 152 * 4 * 2, tp_leaves=3)
    found = linter.lint_text(_SHARDED_HLO, label="train", declared=bad)
    assert [f.rule for f in found] == ["comms-accounting"]
    assert "B/step" in found[0].message
    # an injected launch regression (a second declared bucket) must fail
    bad = _declared_fsdp({"fsdp": 4, "tp": 2}, 152 * 4, buckets=2,
                         tp_leaves=3)
    found = linter.lint_text(_SHARDED_HLO, label="train", declared=bad)
    assert any("all-gathers" in f.message for f in found)
    # a train step that lost its gradient combine, or its tp combine
    no_grad = "\n".join(l for l in _SHARDED_HLO.splitlines()
                        if not l.startswith("%ar"))
    found = linter.lint_text(no_grad, label="train", declared=declared)
    assert any("combines no gradients" in f.message for f in found)
    no_tp = "\n".join(l for l in _SHARDED_HLO.splitlines()
                      if not l.startswith("%tp"))
    found = linter.lint_text(no_tp, label="train", declared=declared)
    assert any("tp leg launches no collectives" in f.message for f in found)


# ---------------------------------------------------------------------------
# the compile-plane hook
# ---------------------------------------------------------------------------
class _FakeLowered:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


_CALLBACK_TEXT = ('func.func @main() { stablehlo.custom_call '
                  '@xla_python_cpu_callback() : () -> tensor<f32> }')


def test_on_lowering_strict_raises_and_raises_again_on_retry(monkeypatch):
    """A strict-mode failure must NOT enter the dedup set: a supervisor /
    estimator retry re-lowers the same program under the same cache key,
    and the gate has to block that compile too — not wave it through
    because the first attempt was 'already linted'."""
    reset_report()
    monkeypatch.setenv("ZOO_HLO_LINT", "strict")
    with pytest.raises(HloLintError):
        on_lowering("train", _FakeLowered(_CALLBACK_TEXT), key="k-strict")
    with pytest.raises(HloLintError):
        on_lowering("train", _FakeLowered(_CALLBACK_TEXT), key="k-strict")
    # the retry re-raises but records nothing twice
    rep = lint_report()
    assert rep["by_rule"] == {"host-callback": 1}
    assert rep["programs_linted"] == 1
    # a clean program IS deduped on its key (linted once per identity)
    clean = _FakeLowered("func.func @main() { return }")
    assert on_lowering("train", clean, key="k-clean") == []
    before = lint_report()["programs_linted"]
    assert on_lowering("train", clean, key="k-clean") == []
    assert lint_report()["programs_linted"] == before
    reset_report()


def test_on_lowering_warn_collects_and_off_disables(monkeypatch):
    reset_report()
    monkeypatch.setenv("ZOO_HLO_LINT", "warn")
    found = on_lowering("train", _FakeLowered(_CALLBACK_TEXT), key="k-warn")
    assert [f.rule for f in found] == ["host-callback"]
    rep = lint_report()
    assert rep["programs_linted"] == 1
    assert rep["by_rule"] == {"host-callback": 1}
    monkeypatch.setenv("ZOO_HLO_LINT", "0")
    assert on_lowering("train", _FakeLowered(_CALLBACK_TEXT),
                       key="k-off") == []
    reset_report()


def test_hook_verifies_comms_accounting_on_real_fit(orca_context):
    """End to end: a SpecLayout fit routes its train lowering through
    ExecutableCache -> on_lowering and registers what it declares under
    its sharding key. The layout's collectives exist only once the
    partitioner has run, so the hook's module holds none and passes; the
    compiled program, linted the way the hook lints (recording), checks
    against that declaration."""
    from analytics_zoo_tpu.analysis.hlo_lint import declared_accounting
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    from analytics_zoo_tpu.parallel.sharding import SpecLayout
    reset_report()

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(24)(x))
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    data = {"x": rng.rand(128, 8).astype(np.float32),
            "y": rng.rand(128).astype(np.float32)}
    est = TPUEstimator(M(), loss="mse", optimizer="adam", seed=0,
                       mesh=create_mesh({"dp": 1, "fsdp": -1}),
                       config={"steps_per_dispatch": 1},
                       sharding=SpecLayout())
    est.fit(dict(data), epochs=1, batch_size=32, verbose=False)
    rep = lint_report()
    assert rep["programs_linted"] >= 1
    assert rep["findings"] == []
    declared = declared_accounting(est.engine._sharding_key())
    assert declared is not None and declared["fsdp"]["buckets"] == 1
    it = data_to_iterator(dict(data), 32, est.mesh, None, None,
                          shuffle=False, config=est.config)
    b0 = next(it.epoch(shuffle=False, prefetch=False))
    fn = est.engine.ensure_jit_train()
    text = fn.lower(*est.engine.train_step_args(b0)).compile().as_text()
    assert HloLinter(record_verified=True).lint_text(
        text, label="train", declared=declared) == []
    assert lint_report(reset=True)["comms_verified"] == 1


# ---------------------------------------------------------------------------
# golden program contracts
# ---------------------------------------------------------------------------
def test_golden_contracts_match_committed_goldens(orca_context):
    """The CI gate itself: fresh capture over the three legs equals
    the committed tests/goldens/program_contracts.json."""
    ok, delta = golden_mod.check()
    assert ok, "golden program contracts drifted:\n" + "\n".join(delta)


def test_committed_goldens_carry_verified_accounting():
    contracts = golden_mod.load_goldens()
    # the sharded legs verify per-mesh-axis accounting at capture time:
    # the compiled gathers move exactly what the plan declares
    for name, _ in golden_mod._SHARDING_LEGS:
        entry = contracts[name]
        assert entry["accounting_verified"] is True, (name, entry)
        assert entry["fsdp_gather_bytes"] == \
            entry["gather_shard_bytes_per_sweep"] > 0
    # the default step declares nothing and launches nothing explicit
    assert contracts["baseline"]["collectives"] == {}
    assert contracts["baseline"]["donation"] == [0, 2]
    # every leg lowers to its own executable (extra_key salting intact)
    assert contracts["distinct_train_executables"] == \
        1 + len(golden_mod._SHARDING_LEGS)


def test_golden_gate_fails_on_injected_collective_regression():
    contracts = golden_mod.load_goldens()
    tampered = json.loads(json.dumps(contracts))      # deep copy
    # a per-leaf regression: one gather a parameter leaf, not one a bucket
    tampered["sharding_fsdp"]["collectives"]["all_gather"] += 5
    tampered["sharding_fsdp"]["fsdp_gather_bytes"] *= 8
    # the tp leg's combine lost, a donation that stopped happening, two
    # layouts collapsed onto one executable
    del tampered["sharding_fsdp_tp"]["tp_collectives"]["all_reduce"]
    tampered["tp_all_reduce_present"] = False
    tampered["baseline"]["donation"] = [0]
    tampered["distinct_train_executables"] -= 1
    ok, delta = golden_mod.check(measured=tampered)
    assert not ok
    joined = "\n".join(delta)
    assert "sharding_fsdp.collectives.all_gather" in joined
    assert "sharding_fsdp.fsdp_gather_bytes" in joined
    assert "sharding_fsdp_tp.tp_collectives.all_reduce" in joined
    assert "tp_all_reduce_present" in joined
    assert "baseline.donation" in joined
    assert "distinct_train_executables" in joined
    # the delta is field-level and readable: golden -> measured
    assert any("->" in line for line in delta)


# ---------------------------------------------------------------------------
# race detector
# ---------------------------------------------------------------------------
def test_lock_order_inversion_detected_under_two_threads():
    det = RaceDetector()
    with det.trace():
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def ab():
            with lock_a:
                with lock_b:
                    pass

        def ba():
            with lock_b:
                with lock_a:
                    pass

        t1 = threading.Thread(target=ab, name="t-ab", daemon=True)
        t1.start()
        t1.join()
        t2 = threading.Thread(target=ba, name="t-ba", daemon=True)
        t2.start()
        t2.join()
    rep = det.report()
    assert rep["inversions"], rep
    assert not rep["clean"]


def test_consistent_lock_order_is_clean():
    det = RaceDetector()
    with det.trace():
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def ab():
            with lock_a:
                with lock_b:
                    pass

        for name in ("t1", "t2"):
            t = threading.Thread(target=ab, name=name, daemon=True)
            t.start()
            t.join()
    rep = det.report()
    assert rep["inversions"] == []
    assert rep["clean"]
    assert rep["acquisitions"] >= 4


def test_cross_thread_release_leaves_no_stale_edges():
    """A plain Lock may legally be released by a thread that never
    acquired it (handoff pattern). The acquirer's held-stack entry must
    be cleared, or everything that thread takes afterwards records bogus
    ordering edges against the handed-off lock."""
    det = RaceDetector()
    with det.trace():
        handoff = threading.Lock()
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        handoff.acquire()                 # main thread acquires...

        def releaser():
            handoff.release()             # ...worker releases (legal)

        t = threading.Thread(target=releaser, name="t-rel", daemon=True)
        t.start()
        t.join()
        # main thread's stack must be empty now: this nesting would
        # otherwise record handoff->a and handoff->b edges
        with lock_a:
            with lock_b:
                pass

        def ba_then_handoff():
            with lock_b:
                with handoff:             # b held while handoff acquired
                    pass

        t = threading.Thread(target=ba_then_handoff, name="t-ba",
                             daemon=True)
        t.start()
        t.join()
    rep = det.report()
    # without the cross-thread clear this reports the fake cycle
    # handoff->b / b->handoff
    assert rep["inversions"] == [], rep
    assert rep["clean"]


def test_reentrant_rlock_does_not_self_edge():
    det = RaceDetector()
    with det.trace():
        rl = threading.RLock()
        with rl:
            with rl:                      # re-acquire: no A->A edge
                pass
    assert det.report()["inversions"] == []


class _SharedState:
    def __init__(self):
        self.counter = 0


def test_unsynchronized_write_detected():
    det = RaceDetector()
    with det.trace():
        guard = threading.Lock()
    obj = _SharedState()
    try:
        det.watch(obj, guard, name="shared", attrs=("counter",))
        with guard:
            obj.counter = 1               # guarded write, main thread

        def unguarded():
            obj.counter = 2               # second thread, no lock

        t = threading.Thread(target=unguarded, name="t-w", daemon=True)
        t.start()
        t.join()
        flagged = det.unsynchronized()
        assert flagged == [{"object": "shared", "attr": "counter",
                            "threads": 2, "unheld_writes": 1}]
    finally:
        det.unwatch_all()


def test_guarded_writes_from_two_threads_are_clean():
    det = RaceDetector()
    with det.trace():
        guard = threading.Lock()
    obj = _SharedState()
    try:
        det.watch(obj, guard, name="shared", attrs=("counter",))
        with guard:
            obj.counter = 1

        def guarded():
            with guard:
                obj.counter = 2

        t = threading.Thread(target=guarded, name="t-g", daemon=True)
        t.start()
        t.join()
        assert det.unsynchronized() == []
    finally:
        det.unwatch_all()


# ---------------------------------------------------------------------------
# repo lint
# ---------------------------------------------------------------------------
_SEEDED_VIOLATIONS = textwrap.dedent("""\
    import os
    import threading


    def swallow():
        try:
            return os.environ.get("ZOO_NOT_A_REGISTERED_KNOB")
        except Exception:
            pass


    def mutable(default=[]):
        return default


    worker = threading.Thread(target=swallow)
    ok = threading.Thread(target=swallow, name="w", daemon=True)
    """)


def test_repolint_each_rule_fires_on_seeded_file(tmp_path):
    path = tmp_path / "seeded.py"
    path.write_text(_SEEDED_VIOLATIONS)
    findings = repolint.lint_file(str(path))
    by_rule = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    assert by_rule == {"env-knob": 1, "silent-except": 1,
                       "thread-attrs": 1, "mutable-default": 1}
    # rule filtering works (the CLI's --rule flag)
    only = repolint.lint_file(str(path), rules=("env-knob",))
    assert [f.rule for f in only] == ["env-knob"]


def test_repolint_registered_knob_read_is_legal(tmp_path):
    path = tmp_path / "clean.py"
    path.write_text('import os\n'
                    'a = os.environ.get("ZOO_H2D_LANES")\n'
                    'b = os.getenv("ZOO_SHARDING_PLANE")\n'
                    'c = "ZOO_FAULTS" in os.environ\n'
                    'd = os.environ["ZOO_COMPILE_CACHE"]\n')
    assert repolint.lint_file(str(path)) == []


def test_repolint_clean_on_repo():
    """The acceptance criterion: zoo-lint exits 0 on the whole repo after
    the satellite fixes."""
    findings = repolint.lint_paths(repolint.repo_roots())
    assert findings == [], "\n".join(str(f) for f in findings)


def test_zoo_lint_cli_exit_codes(tmp_path, capsys):
    assert repolint.main([]) == 0
    capsys.readouterr()
    bad = tmp_path / "bad.py"
    bad.write_text(_SEEDED_VIOLATIONS)
    assert repolint.main([str(bad), "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["count"] == 4


# ---------------------------------------------------------------------------
# knob registry
# ---------------------------------------------------------------------------
def test_knobs_typed_get_and_defaults(monkeypatch):
    monkeypatch.delenv("ZOO_SERVING_SLACK_MS", raising=False)
    assert knobs.get("ZOO_SERVING_SLACK_MS") == 5.0
    monkeypatch.setenv("ZOO_SERVING_SLACK_MS", "2.5")
    assert knobs.get("ZOO_SERVING_SLACK_MS") == 2.5
    monkeypatch.setenv("ZOO_COMPILE_CACHE_DISABLE", "0")
    assert knobs.get("ZOO_COMPILE_CACHE_DISABLE") is False
    monkeypatch.setenv("ZOO_COMPILE_CACHE_DISABLE", "1")
    assert knobs.get("ZOO_COMPILE_CACHE_DISABLE") is True
    monkeypatch.setenv("ZOO_H2D_LANES", "")      # empty == unset
    assert knobs.get("ZOO_H2D_LANES") == 2
    assert knobs.get("ZOO_H2D_LANES", default=7) == 7


def test_knobs_reject_unregistered_and_invalid(monkeypatch):
    with pytest.raises(KeyError):
        knobs.get("ZOO_NOT_A_REGISTERED_KNOB")
    assert not knobs.is_registered("ZOO_NOT_A_REGISTERED_KNOB")
    monkeypatch.setenv("ZOO_CKPT_IO_RETRIES", "many")
    with pytest.raises(ValueError):
        knobs.get("ZOO_CKPT_IO_RETRIES")


def test_knobs_markdown_table_covers_registry():
    table = knobs.markdown_table()
    for name in knobs.REGISTRY:
        assert f"`{name}`" in table


# ---------------------------------------------------------------------------
# per-mesh-axis classification
# ---------------------------------------------------------------------------
def test_parse_collectives_group_shapes():
    """Replica-group shapes come out of both attribute formats — the
    stablehlo dense tensor and the HLO-text brace form — and classify
    collectives onto the fsdp axis, the tp axis, or neither."""
    import textwrap

    from analytics_zoo_tpu.analysis.hlo_lint import collectives_by_mesh_axes

    mod = textwrap.dedent("""\
        module @jit_step {
          func.func public @main(%arg0: tensor<64xf32>) -> tensor<64xf32> {
            %0 = "stablehlo.reduce_scatter"(%arg0) <{replica_groups = dense<[[0, 1, 2, 3], [4, 5, 6, 7]]> : tensor<2x4xi64>, scatter_dimension = 0 : i64}> ({
            ^bb0(%a: tensor<f32>, %b: tensor<f32>):
              %s = stablehlo.add %a, %b : tensor<f32>
              stablehlo.return %s : tensor<f32>
            }) : (tensor<64xf32>) -> tensor<16xf32>
            %1 = "stablehlo.all_reduce"(%0) <{replica_groups = dense<[[0, 4], [1, 5], [2, 6], [3, 7]]> : tensor<4x2xi64>}> ({
            ^bb0(%a: tensor<f32>, %b: tensor<f32>):
              %s = stablehlo.add %a, %b : tensor<f32>
              stablehlo.return %s : tensor<f32>
            }) : (tensor<16xf32>) -> tensor<16xf32>
            %2 = "stablehlo.all_gather"(%1) <{all_gather_dim = 0 : i64, replica_groups = dense<[[0, 1, 2, 3], [4, 5, 6, 7]]> : tensor<2x4xi64>}> : (tensor<16xf32>) -> tensor<64xf32>
            %3 = "stablehlo.all_reduce"(%2) <{replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>}> ({
            ^bb0(%a: tensor<f32>, %b: tensor<f32>):
              %s = stablehlo.add %a, %b : tensor<f32>
              stablehlo.return %s : tensor<f32>
            }) : (tensor<64xf32>) -> tensor<64xf32>
            return %3 : tensor<64xf32>
          }
        }
        """)
    ops = parse_collectives(mod)
    assert [op.group_shape for op in ops] == [(2, 4), (4, 2), (2, 4),
                                              (1, 8)]
    ax = collectives_by_mesh_axes(ops, {"fsdp": 4, "tp": 2})
    assert ax["by_axis"]["fsdp"] == {"reduce_scatter": 1, "all_gather": 1}
    assert ax["by_axis"]["tp"] == {"all_reduce": 1}
    assert ax["global"] == {"all_reduce": 1}
    assert ax["axis_bytes"]["fsdp"] == {"reduce_scatter": 64 * 4,
                                        "all_gather": 16 * 4}
    assert ax["axis_bytes"]["tp"] == {"all_reduce": 16 * 4}
    assert not ax["ambiguous"]
    # two axes of one size cannot be told apart by group shape
    assert collectives_by_mesh_axes(ops, {"fsdp": 2, "tp": 2})["ambiguous"]
    # HLO-text brace form (post-compile text, async start op)
    hlo = ('%rs = f32[16] reduce-scatter-start(f32[64] %p), '
           'replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, '
           'to_apply=%add : (tensor<64xf32>) -> tensor<16xf32>')
    ops2 = parse_collectives(hlo)
    assert len(ops2) == 1 and ops2[0].group_shape == (2, 4)
