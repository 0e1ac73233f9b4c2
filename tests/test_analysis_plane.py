"""Analysis plane (PR 9): StableHLO linter, golden program contracts,
runtime race detector, repo lint, knob registry.

Every lint rule is proven by a *seeded violation* (a planted f64
promotion, an undonated buffer, a host callback in a train step, a
lock-order inversion under two threads, an unregistered knob read, ...)
and by staying silent on the clean tree — the acceptance criteria of
ISSUE 9. The golden program-contract gate is shown to fail on an injected
collective-count regression, and the committed goldens carry
``accounting_verified: true`` for every comms leg (measured lowered-program
launches/bytes == ``data_pipeline_stats()["comms"]`` declared accounting).
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
    declared = {"buckets": 1, "sharded_update": True, "wire_dtype": "f32",
                "wire_bytes_per_step": 840 * 4}
    assert HloLinter(target="cpu").lint_text(
        _ASYNC_MODULE, label="train", declared=declared) == []


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
    source->target pairs classify it onto a leg the way replica_groups
    classify a reduce-scatter."""
    from analytics_zoo_tpu.analysis.hlo_lint import collectives_by_axis
    ops = parse_collectives(_PERMUTE_MODULE)
    assert sorted(op.kind for op in ops) == ["all_to_all",
                                             "collective_permute"]
    cp = next(op for op in ops if op.kind == "collective_permute")
    assert cp.operand_bytes == 288            # int8: one byte per element
    # 4 disjoint 2-cycles == the (ici=4, dcn=2) DCN-leg group shape
    assert cp.group_shape == (4, 2)
    a2a = next(op for op in ops if op.kind == "all_to_all")
    assert a2a.operand_bytes == 288 and a2a.group_shape == (1, 8)
    by = collectives_by_axis(ops, ici=4, dcn=2)
    assert by["dcn"]["collective_permute"] == 1
    assert by["dcn_wire_bytes"] == 288        # the a2a is global, not DCN
    assert by["global"]["all_to_all"] == 1
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


def test_comms_accounting_rule_verifies_and_catches_drift():
    declared = {"buckets": 1, "sharded_update": True, "wire_dtype": "f32",
                "wire_bytes_per_step": 840 * 4}
    linter = HloLinter(target="cpu")
    assert linter.lint_text(_SYNTH_MODULE, label="train",
                            declared=declared) == []
    # an injected byte regression (declared != lowered) must fail
    bad = dict(declared, wire_bytes_per_step=840 * 4 * 2)
    found = linter.lint_text(_SYNTH_MODULE, label="train", declared=bad)
    assert [f.rule for f in found] == ["comms-accounting"]
    # an injected launch regression (extra declared bucket) must fail
    bad = dict(declared, buckets=2)
    found = linter.lint_text(_SYNTH_MODULE, label="train", declared=bad)
    assert any("reduce-scatter" in f.message for f in found)


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
    """End-to-end acceptance: a bucketed+sharded fit routes its train
    lowering through ExecutableCache -> on_lowering, which cross-checks
    the lowered collectives against the engine's declared accounting."""
    reset_report()

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(24)(x))
            return nn.Dense(1)(x)[:, 0]

    rng = np.random.RandomState(0)
    est = TPUEstimator(M(), loss="mse", optimizer="adam", seed=0,
                       sharded_update=True,
                       config={"steps_per_dispatch": 1,
                               "grad_bucket_mb": 4.0})
    est.fit({"x": rng.rand(128, 8).astype(np.float32),
             "y": rng.rand(128).astype(np.float32)},
            epochs=1, batch_size=32, verbose=False)
    rep = lint_report(reset=True)
    assert rep["programs_linted"] >= 1
    assert rep["comms_verified"] >= 1
    assert rep["findings"] == []


# ---------------------------------------------------------------------------
# golden program contracts
# ---------------------------------------------------------------------------
def test_golden_contracts_match_committed_goldens(orca_context):
    """The CI gate itself: fresh capture over all four bench legs equals
    the committed tests/goldens/program_contracts.json."""
    ok, delta = golden_mod.check()
    assert ok, "golden program contracts drifted:\n" + "\n".join(delta)


def test_committed_goldens_carry_verified_accounting():
    contracts = golden_mod.load_goldens()
    legs = [name for name, _, _ in golden_mod._LEGS if name != "baseline"]
    assert legs
    for name in legs:
        entry = contracts[name]
        assert entry["accounting_verified"] is True, (name, entry)
        assert entry["declared"]["wire_bytes_per_step"] > 0
    # the sharding-plane legs (PR 17) verify per-mesh-axis accounting at
    # capture time too
    for name, _ in golden_mod._SHARDING_LEGS:
        assert contracts[name]["accounting_verified"] is True, name
    # every leg lowers to its own executable (extra_key salting intact)
    assert contracts["distinct_train_executables"] == \
        len(golden_mod._LEGS) + len(golden_mod._SHARDING_LEGS)


def test_golden_gate_fails_on_injected_collective_regression():
    contracts = golden_mod.load_goldens()
    tampered = json.loads(json.dumps(contracts))      # deep copy
    tampered["flat"]["collectives"]["all_reduce"] += 2
    tampered["bucketed_sharded"]["rs_wire_bytes"] *= 2
    # an overlapped launch-count regression (a segment merge collapsing
    # per-bucket reduce-scatters into one) must fail field-level too
    tampered["overlapped"]["collectives"]["reduce_scatter"] = 1
    tampered["overlapped_wire_matches_bucketed"] = False
    # PR 16: the native int8 leg's hop count and wire bytes are pinned —
    # a lost ring hop or a widened payload must fail field-level
    tampered["native_int8"]["collectives"]["collective_permute"] -= 1
    tampered["native_int8"]["cp_wire_bytes"] += 4
    tampered["native_int8"]["declared"]["native_hops"] += 1
    tampered["native_int8_byte_exact"] = False
    ok, delta = golden_mod.check(measured=tampered)
    assert not ok
    joined = "\n".join(delta)
    assert "flat.collectives.all_reduce" in joined
    assert "bucketed_sharded.rs_wire_bytes" in joined
    assert "overlapped.collectives.reduce_scatter" in joined
    assert "overlapped_wire_matches_bucketed" in joined
    assert "native_int8.collectives.collective_permute" in joined
    assert "native_int8.cp_wire_bytes" in joined
    assert "native_int8.declared.native_hops" in joined
    assert "native_int8_byte_exact" in joined
    # the delta is field-level and readable: golden -> measured
    assert any("->" in line for line in delta)


def test_overlapped_golden_leg_contract():
    """The committed overlapped contract: one reduce-scatter launch per
    bucket (a real multi-bucket pipeline), total wire bytes byte-for-byte
    the bucketed leg's, verified accounting, own executable."""
    contracts = golden_mod.load_goldens()
    leg = contracts["overlapped"]
    assert leg["declared"]["overlap"] is True
    assert leg["declared"]["buckets"] >= 2
    assert leg["declared"]["segments"] == leg["declared"]["buckets"]
    assert leg["collectives"]["reduce_scatter"] == leg["declared"]["buckets"]
    assert leg["collectives"]["all_gather"] == 1      # ZeRO-1 param gather
    assert leg["rs_wire_bytes"] == \
        contracts["bucketed_sharded"]["rs_wire_bytes"]
    assert contracts["overlapped_wire_matches_bucketed"] is True
    assert leg["accounting_verified"] is True


def test_native_int8_golden_leg_contract():
    """PR 16: the committed native-int8 contract. The DCN leg is a pure
    collective-permute ring — (dcn-1) hops per bucket, NO reduce-scatter
    or all-reduce — and the measured permute bytes equal the declared
    packed payload+scale cost exactly: no simulated-wire exemption left."""
    contracts = golden_mod.load_goldens()
    leg = contracts["native_int8"]
    d = leg["declared"]
    assert d["native_int8"] is True and d["wire_dtype"] == "int8"
    hier = d["hierarchy"]
    assert hier["quantize_dcn"] is True
    assert d["native_hops"] == d["buckets"] * (hier["dcn_axis"] - 1)
    assert leg["by_axis"]["dcn"]["collective_permute"] == d["native_hops"]
    assert "reduce_scatter" not in leg["by_axis"]["dcn"]
    assert "all_reduce" not in leg["by_axis"]["dcn"]
    # byte-exact: measured permute operands == declared DCN wire cost
    assert leg["cp_wire_bytes"] == hier["dcn_wire_bytes_per_step"]
    assert leg["dcn_wire_bytes"] == leg["cp_wire_bytes"]
    assert contracts["native_int8_byte_exact"] is True
    assert leg["accounting_verified"] is True
    # the int8 hops genuinely shrink the DCN leg: well under the f32
    # reduce-scatter bytes the ICI leg moves for the same gradients
    assert leg["cp_wire_bytes"] * 3 < hier["ici_wire_bytes_per_step"]


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
                    'b = os.getenv("ZOO_COMMS_PLANE")\n'
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
    monkeypatch.delenv("ZOO_GRAD_BUCKET_MB", raising=False)
    assert knobs.get("ZOO_GRAD_BUCKET_MB") == 0.0
    monkeypatch.setenv("ZOO_GRAD_BUCKET_MB", "2.5")
    assert knobs.get("ZOO_GRAD_BUCKET_MB") == 2.5
    monkeypatch.setenv("ZOO_SHARDED_UPDATE", "0")
    assert knobs.get("ZOO_SHARDED_UPDATE") is False
    monkeypatch.setenv("ZOO_SHARDED_UPDATE", "1")
    assert knobs.get("ZOO_SHARDED_UPDATE") is True
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
# PR 12: per-axis accounting + hierarchical / multihost goldens
# ---------------------------------------------------------------------------
def test_parse_collectives_group_shapes():
    """Replica-group shapes come out of both attribute formats — the
    stablehlo dense tensor and the HLO-text brace form — and classify
    ICI vs DCN vs global legs."""
    import textwrap

    from analytics_zoo_tpu.analysis.hlo_lint import collectives_by_axis

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
    ax = collectives_by_axis(ops, 4, 2)
    assert ax["ici"] == {"reduce_scatter": 1, "all_gather": 1}
    assert ax["dcn"] == {"all_reduce": 1}
    assert ax["global"] == {"all_reduce": 1}
    assert ax["ici_wire_bytes"] == 64 * 4
    assert ax["dcn_wire_bytes"] == 16 * 4
    # HLO-text brace form (post-compile text, async start op)
    hlo = ('%rs = f32[16] reduce-scatter-start(f32[64] %p), '
           'replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, '
           'to_apply=%add : (tensor<64xf32>) -> tensor<16xf32>')
    ops2 = parse_collectives(hlo)
    assert len(ops2) == 1 and ops2[0].group_shape == (2, 4)


def test_hierarchical_golden_leg_contract():
    """The committed hierarchical contract: per-axis launch counts (one
    ICI reduce-scatter + one DCN reduce-scatter per bucket under ZeRO-1,
    the two-stage param all-gather) and the DCN shrink pin."""
    contracts = golden_mod.load_goldens()
    entry = contracts["hierarchical"]
    hier = entry["declared"]["hierarchy"]
    assert (hier["ici_axis"], hier["dcn_axis"]) == (4, 2)
    buckets = entry["declared"]["buckets"]
    assert buckets >= 2
    assert entry["by_axis"]["ici"]["reduce_scatter"] == buckets
    assert entry["by_axis"]["dcn"]["reduce_scatter"] == buckets
    assert entry["by_axis"]["ici"]["all_gather"] == 1
    assert entry["by_axis"]["dcn"]["all_gather"] == 1
    assert entry["accounting_verified"] is True
    assert entry["dcn_wire_bytes"] * 4 == entry["ici_wire_bytes"]
    assert contracts["hierarchical_dcn_shrink_ok"] is True


def test_golden_gate_fails_on_dcn_byte_regression():
    """Moving gradient bytes onto the cross-host links must fail the
    gate even when total launches/bytes stay plausible."""
    contracts = golden_mod.load_goldens()
    tampered = json.loads(json.dumps(contracts))      # deep copy
    tampered["hierarchical"]["dcn_wire_bytes"] *= 4
    tampered["hierarchical"]["by_axis"]["dcn"]["reduce_scatter"] += 1
    ok, delta = golden_mod.check(measured=tampered)
    assert not ok
    joined = "\n".join(delta)
    assert "hierarchical.dcn_wire_bytes" in joined
    assert "hierarchical.by_axis.dcn.reduce_scatter" in joined


def test_multihost_golden_matches_simulated_capture(orca_context):
    """The committed multihost contract regenerates exactly on the
    single-process simulated mesh (the program depends only on the
    (n_dev, dcn, ici) factorization) — so the contract is enforced
    everywhere, and the two-process harness additionally proves the
    real topology lowers to the same program."""
    measured = golden_mod.capture_multihost_contract(dcn=2)
    ok, delta = golden_mod.check_multihost(measured)
    assert ok, "multihost contract drifted:\n" + "\n".join(delta)
    assert measured["accounting_verified"] is True
    assert measured["dcn_wire_bytes"] == measured["declared_dcn_wire_bytes"]


def test_accounting_hier_ici_eq_dcn_checks_kinds_and_bytes():
    """ici == dcn meshes: group shapes coincide, but collective kinds and
    combined wire bytes are still verified — a byte regression on the
    grouped legs cannot pass as 'ambiguous'."""
    import textwrap

    mod = textwrap.dedent("""\
        module @jit_step {
          func.func public @main(%arg0: tensor<64xf32>) -> tensor<64xf32> {
            %0 = "stablehlo.reduce_scatter"(%arg0) <{replica_groups = dense<[[0, 1], [2, 3]]> : tensor<2x2xi64>, scatter_dimension = 0 : i64}> ({
            ^bb0(%a: tensor<f32>, %b: tensor<f32>):
              %s = stablehlo.add %a, %b : tensor<f32>
              stablehlo.return %s : tensor<f32>
            }) : (tensor<64xf32>) -> tensor<32xf32>
            %1 = "stablehlo.all_reduce"(%0) <{replica_groups = dense<[[0, 2], [1, 3]]> : tensor<2x2xi64>}> ({
            ^bb0(%a: tensor<f32>, %b: tensor<f32>):
              %s = stablehlo.add %a, %b : tensor<f32>
              stablehlo.return %s : tensor<f32>
            }) : (tensor<32xf32>) -> tensor<32xf32>
            %2 = "stablehlo.all_gather"(%1) <{all_gather_dim = 0 : i64, replica_groups = dense<[[0, 1], [2, 3]]> : tensor<2x2xi64>}> : (tensor<32xf32>) -> tensor<64xf32>
            %3 = "stablehlo.all_reduce"(%2) <{replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>}> ({
            ^bb0(%a: tensor<f32>, %b: tensor<f32>):
              %s = stablehlo.add %a, %b : tensor<f32>
              stablehlo.return %s : tensor<f32>
            }) : (tensor<64xf32>) -> tensor<64xf32>
            return %3 : tensor<64xf32>
          }
        }
        """)
    declared = {"buckets": 1, "sharded_update": False, "wire_dtype": "f32",
                "grad_leaves": 3, "collectives_per_step": 3,
                "wire_bytes_per_step": 64 * 4 + 32 * 4,
                "hierarchy": {"active": True, "ici_axis": 2, "dcn_axis": 2,
                              "quantize_dcn": True,
                              "ici_wire_bytes_per_step": 64 * 4,
                              "dcn_wire_bytes_per_step": 32 * 4}}
    linter = HloLinter()
    assert not linter.lint_text(mod, label="train", declared=declared)
    # combined grouped bytes drift -> caught even without a per-leg split
    bad = json.loads(json.dumps(declared))
    bad["hierarchy"]["dcn_wire_bytes_per_step"] += 64
    found = linter.lint_text(mod, label="train", declared=bad)
    assert found and any("ici==dcn" in f.message for f in found)
    # a lost param all-gather is caught by kind
    bad2 = mod.replace("all_gather", "all_gather_DISABLED")
    found2 = linter.lint_text(bad2, label="train", declared=declared)
    assert found2 and any("all-gather" in f.message for f in found2)


def test_hier_capture_on_ici_eq_dcn_mesh_verifies(orca_context):
    """The placement-free multihost capture on a 4-device (2-host x
    2-chip) submesh — the ici==dcn case end-to-end through the real
    lowered program."""
    import jax as _jax

    from analytics_zoo_tpu.parallel.mesh import create_mesh

    mesh = create_mesh({"dp": -1}, devices=_jax.devices()[:4])
    contract = golden_mod.capture_multihost_contract(mesh, dcn=2)
    assert (contract["ici_axis"], contract["dcn_axis"]) == (2, 2)
    assert contract["accounting_verified"] is True, contract
