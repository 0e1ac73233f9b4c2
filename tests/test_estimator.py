import numpy as np
import pytest

from analytics_zoo_tpu.orca.data import XShards
from analytics_zoo_tpu.orca.learn import Estimator
from analytics_zoo_tpu.orca.learn.trigger import SeveralIteration


def make_linear_data(n=512, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 4).astype(np.float32)
    w = np.array([1.0, -2.0, 3.0, 0.5], np.float32)
    y = x @ w + 0.1
    return x, y.astype(np.float32)


def linear_model_creator(config):
    import flax.linen as nn

    class LinReg(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)[:, 0]

    return LinReg()


def test_fit_linear_regression(orca_context):
    from analytics_zoo_tpu.orca.learn.optimizers import Adam
    x, y = make_linear_data()
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer=Adam(lr=0.05), metrics=["mae"])
    stats = est.fit({"x": x, "y": y}, epochs=30, batch_size=64)
    assert stats[-1]["train_loss"] < stats[0]["train_loss"]
    result = est.evaluate({"x": x, "y": y}, batch_size=64)
    assert result["loss"] < 0.05
    assert "mae" in result


def test_fit_xshards_and_predict(orca_context):
    x, y = make_linear_data()
    shards = XShards.partition({"x": x, "y": y}, num_shards=4)
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="sgd")
    est.fit(shards, epochs=5, batch_size=64)
    preds = est.predict(shards, batch_size=64)
    collected = preds.collect()
    assert len(collected) == 4
    assert "prediction" in collected[0]
    total = sum(len(p["prediction"]) for p in collected)
    assert total == 512
    arr = est.predict({"x": x}, batch_size=100)  # ragged tail is masked out
    assert arr.shape == (512,)


def test_mixed_full_and_padded_batches(orca_context):
    """512 rows at batch 100: five full batches ship w=None (weights
    synthesized in-jit), the padded tail ships a mask — both signatures
    must train/evaluate in one epoch and the eval count only real rows."""
    from analytics_zoo_tpu.orca.learn.optimizers import Adam
    x, y = make_linear_data()
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer=Adam(lr=0.05), metrics=["mae"])
    stats = est.fit({"x": x, "y": y}, epochs=25, batch_size=100,
                    verbose=False)
    assert np.isfinite(stats[-1]["train_loss"])
    assert stats[-1]["num_samples"] == 512     # masked tail not overcounted
    result = est.evaluate({"x": x, "y": y}, batch_size=100)
    assert result["num_samples"] == 512
    assert result["loss"] < 1.0


def test_pandas_xshards_fit(orca_context):
    import pandas as pd
    x, y = make_linear_data(256)
    df = pd.DataFrame({f"f{i}": x[:, i] for i in range(4)})
    df["label"] = y
    from analytics_zoo_tpu.orca.data.shard import HostXShards
    shards = HostXShards([df.iloc[:128], df.iloc[128:]])
    est = Estimator.from_keras(
        lambda cfg: _mlp_multi_feature(), loss="mse")
    stats = est.fit(shards, epochs=10, batch_size=64,
                    feature_cols=[f"f{i}" for i in range(4)],
                    label_cols=["label"])
    assert stats[-1]["train_loss"] < stats[0]["train_loss"]


def _mlp_multi_feature():
    import flax.linen as nn
    import jax.numpy as jnp

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, *feats):
            x = jnp.stack(feats, -1) if len(feats) > 1 else feats[0]
            return nn.Dense(1)(x)[:, 0]

    return MLP()


def test_save_load_checkpoint(orca_context, tmp_path):
    x, y = make_linear_data(128)
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               model_dir=str(tmp_path))
    est.fit({"x": x, "y": y}, epochs=2, batch_size=32,
            checkpoint_trigger=SeveralIteration(4))
    import os
    ckpts = [d for d in os.listdir(tmp_path) if d.startswith("ckpt-")]
    assert ckpts
    before = est.evaluate({"x": x, "y": y}, verbose=False)["loss"]
    est2 = Estimator.from_keras(linear_model_creator, loss="mse")
    est2.fit({"x": x, "y": y}, epochs=0, batch_size=32)  # build only
    est2.load_checkpoint(str(tmp_path))
    after = est2.evaluate({"x": x, "y": y}, verbose=False)["loss"]
    assert abs(before - after) < 1e-5


def test_ncf_training(orca_context):
    from analytics_zoo_tpu.models.recommendation import NeuralCF

    rng = np.random.RandomState(0)
    n_users, n_items, n = 50, 30, 800
    users = rng.randint(1, n_users, n)
    items = rng.randint(1, n_items, n)
    # deterministic preference rule so the model can learn it
    labels = ((users + items) % 2).astype(np.int64)
    pairs = np.stack([users, items], -1).astype(np.int32)

    model = NeuralCF(user_count=n_users, item_count=n_items, class_num=2,
                     user_embed=8, item_embed=8, hidden_layers=(16, 8),
                     mf_embed=8)
    model.compile(loss="sparse_categorical_crossentropy", optimizer="adam",
                  metrics=["accuracy"])
    stats = model.fit({"x": pairs, "y": labels}, epochs=12, batch_size=64,
                      verbose=False)
    res = model.evaluate({"x": pairs, "y": labels}, batch_size=64,
                         verbose=False)
    assert res["accuracy"] > 0.9, res
    probs = model.predict(pairs[:10])
    assert probs.shape == (10, 2)
    np.testing.assert_allclose(probs.sum(-1), np.ones(10), rtol=1e-3)
    recs = model.recommend_for_user(pairs[:50], max_items=3)
    assert all(len(v) <= 3 for v in recs.values())


def test_gradient_clipping(orca_context):
    """Clip-by-norm must bound the update magnitude (reference plumbs
    clip-by-L2/constant through every estimator, Estimator.scala:68-141)."""
    import jax
    x, y = make_linear_data()
    y = y * 1000.0                      # huge targets -> huge grads
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="sgd")
    est.set_l2_norm_gradient_clipping(1e-3)
    est.fit({"x": x, "y": y}, epochs=1, batch_size=64, verbose=False)
    params = jax.device_get(est.engine.params)
    flat = np.concatenate([np.ravel(v) for v in jax.tree.leaves(params)])
    # 8 steps of SGD(lr=default) with grad-norm <= 1e-3 cannot move params far
    assert np.abs(flat).max() < 1.0
    # constant clipping path compiles and runs too
    est2 = Estimator.from_keras(linear_model_creator, loss="mse",
                                optimizer="sgd")
    est2.set_constant_gradient_clipping(-0.01, 0.01)
    stats = est2.fit({"x": x, "y": y}, epochs=1, batch_size=64, verbose=False)
    assert np.isfinite(stats[-1]["train_loss"])


def test_failure_recovery_from_checkpoint(orca_context, tmp_path):
    """A training step that throws mid-fit must be retried from the latest
    checkpoint (reference: InternalDistriOptimizer retry loop,
    Topology.scala:1256-1337)."""
    x, y = make_linear_data()
    # pin the fuse factor so the fused-dispatch path (the fit() default for
    # small models) is what gets the injected failure
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="adam", model_dir=str(tmp_path),
                               config={"steps_per_dispatch": 4})
    calls = {"n": 0}
    real_group = est.engine.train_batch_group

    def flaky_group(batch):
        calls["n"] += 1
        if calls["n"] == 3:             # fail once, mid-epoch-2
            raise RuntimeError("injected chip failure")
        return real_group(batch)

    est.engine.train_batch_group = flaky_group
    stats = est.fit({"x": x, "y": y}, epochs=3, batch_size=64,
                    checkpoint_trigger=SeveralIteration(4), verbose=False)
    assert len(stats) == 3              # all epochs completed despite failure
    assert calls["n"] == 7              # 6 good groups + 1 failed + 1 retried
    # recovery restored from the step-8 checkpoint, so step counts continue
    assert est.engine.step == 24


def test_fused_dispatch_matches_sequential(orca_context):
    """The scan-fused multi-step path (k train steps per dispatch) must be
    numerically identical to the per-batch loop: same rng folding, same
    optimizer trajectory, same final params."""
    import jax
    x, y = make_linear_data(1024)
    est1 = Estimator.from_keras(linear_model_creator, loss="mse",
                                optimizer="adam",
                                config={"steps_per_dispatch": 1})
    est1.fit({"x": x, "y": y}, epochs=2, batch_size=64, verbose=False)
    est2 = Estimator.from_keras(linear_model_creator, loss="mse",
                                optimizer="adam",
                                config={"steps_per_dispatch": 8})
    est2.fit({"x": x, "y": y}, epochs=2, batch_size=64, verbose=False)
    assert est1.engine.step == est2.engine.step
    for a, b in zip(jax.tree_util.tree_leaves(
                        jax.device_get(est1.engine.params)),
                    jax.tree_util.tree_leaves(
                        jax.device_get(est2.engine.params))):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_auto_probe_rolls_back(orca_context):
    """The 'auto' fuse probe dispatches real train steps but must roll the
    engine back: after fit(epochs=1) the optimizer has taken exactly
    steps_per_epoch steps and the params match a pinned-fuse run."""
    import jax
    x, y = make_linear_data(512)
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="adam")   # default: auto
    est.fit({"x": x, "y": y}, epochs=1, batch_size=64, shuffle=True,
            verbose=False)
    assert est.engine.step == 8                    # 512/64, probe invisible
    est2 = Estimator.from_keras(linear_model_creator, loss="mse",
                                optimizer="adam",
                                config={"steps_per_dispatch": 1})
    # shuffle=True: the probe must not advance the shuffle-seed counter
    # either, or the two runs would see different data orders
    est2.fit({"x": x, "y": y}, epochs=1, batch_size=64, shuffle=True,
             verbose=False)
    for a, b in zip(jax.tree_util.tree_leaves(
                        jax.device_get(est.engine.params)),
                    jax.tree_util.tree_leaves(
                        jax.device_get(est2.engine.params))):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_fused_dispatch_ragged_tail(orca_context):
    """n not divisible by fuse*batch: full groups run fused, the remainder
    runs as single (padded+masked) batches; every sample is seen once."""
    x, y = make_linear_data(64 * 5 + 17)        # 5 full batches + ragged tail
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="sgd",
                               config={"steps_per_dispatch": 2})
    est.fit({"x": x, "y": y}, epochs=1, batch_size=64, verbose=False)
    # 2 fused groups (4 steps) + 1 full single + 1 padded single = 6 steps
    assert est.engine.step == 6


def test_failure_without_model_dir_raises(orca_context):
    x, y = make_linear_data()
    # pin the per-step dispatch path: the monkeypatch below replaces only
    # train_batch, and with auto fusion a structurally identical earlier
    # test may have seeded the compile plane's shared fuse-probe result,
    # steering the loop through train_batch_group instead
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="adam",
                               config={"steps_per_dispatch": 1})

    def exploding(batch):
        raise RuntimeError("boom")

    est.engine.train_batch = exploding
    with pytest.raises(RuntimeError, match="boom"):
        est.fit({"x": x, "y": y}, epochs=1, batch_size=64, verbose=False)


def test_profile_stats(orca_context):
    x, y = make_linear_data()
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="sgd")
    stats = est.fit({"x": x, "y": y}, epochs=1, batch_size=64,
                    verbose=False, profile=True)
    prof = stats[-1]["profile"]
    assert prof["steps"] == 8
    assert prof["mean_step_s"] > 0
    assert prof["mean_data_s"] >= 0


def test_explicit_lr_on_lr_less_optimizer_raises(orca_context):
    from analytics_zoo_tpu.orca.learn.optimizers.optimizers_impl import \
        convert_optimizer
    with pytest.raises(ValueError, match="learning-rate"):
        convert_optimizer("adadelta", learning_rate=0.1)


def test_preemption_sigterm_checkpoints_and_stops(orca_context, tmp_path):
    """SURVEY §5: preemption handling. A SIGTERM mid-fit (the
    spot/preemptible TPU-VM notice) must checkpoint and return cleanly
    instead of killing the process; a fresh estimator resumes from the
    preemption step."""
    import os
    import signal

    x, y = make_linear_data(256)
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               model_dir=str(tmp_path))

    class _SigtermAt(SeveralIteration):
        """Deterministic preemption: raise SIGTERM from inside the hot
        loop at a known iteration (trigger callables run per step)."""

        fired = False

        def __call__(self, state):
            # >= not ==: the fused dispatch loop checks triggers every k
            # steps, so an exact iteration may never be observed
            if state.iteration >= 10 and not self.fired:
                self.fired = True     # one shot: a second SIGTERM is the
                os.kill(os.getpid(), signal.SIGTERM)   # force-stop path
            return False

    stats = est.fit({"x": x, "y": y}, epochs=200, batch_size=32,
                    checkpoint_trigger=_SigtermAt(10_000),
                    verbose=False)
    assert 0 < len(stats) < 200, "fit should stop early on preemption"
    assert stats[-1].get("preempted") is True
    assert stats[-1].get("partial_epoch") is True
    step_at_stop = est.engine.step
    ckpts = [d for d in os.listdir(tmp_path) if d.startswith("ckpt-")]
    assert f"ckpt-{step_at_stop}" in ckpts, (ckpts, step_at_stop)

    est2 = Estimator.from_keras(linear_model_creator, loss="mse")
    est2.fit({"x": x, "y": y}, epochs=0, batch_size=32)   # build only
    est2.load_checkpoint(str(tmp_path))
    assert est2.engine.step == step_at_stop


def test_fused_evaluate_matches_sequential(orca_context):
    """evaluate() through the fused eval path must produce identical
    metrics/loss to the per-batch loop (eval is stateless apart from the
    metric accumulators, so fusing must be exactly semantics-preserving,
    ragged tail included)."""
    x, y = make_linear_data(64 * 5 + 17)
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="sgd", metrics=["mae"])
    est.fit({"x": x, "y": y}, epochs=1, batch_size=64, verbose=False)
    r_fused = est.evaluate({"x": x, "y": y}, batch_size=64, verbose=False)
    est.config["steps_per_dispatch"] = 1
    r_seq = est.evaluate({"x": x, "y": y}, batch_size=64, verbose=False)
    assert r_fused["num_samples"] == r_seq["num_samples"] == 64 * 5 + 17
    for k in r_seq:
        np.testing.assert_allclose(r_fused[k], r_seq[k], rtol=1e-6,
                                   atol=1e-7)


def test_composite_trigger_cap_and_arm(orca_context, tmp_path):
    """A SeveralIteration nested in TriggerOr must still cap the fuse
    factor (checkpoint cadence preserved) and arm to the run's starting
    iteration (round-5 review)."""
    from analytics_zoo_tpu.orca.learn.trigger import (MinLoss, TriggerOr,
                                                      TrainerState)
    trig = TriggerOr(SeveralIteration(4), MinLoss(-1.0))  # MinLoss never
    assert trig.fuse_cap() == 4
    trig.arm(TrainerState(iteration=150))
    assert not trig(TrainerState(iteration=151))   # mid-interval: no fire
    assert trig(TrainerState(iteration=152))       # 152//4 > 150//4

    import os
    x, y = make_linear_data(512)
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="sgd", model_dir=str(tmp_path),
                               config={"steps_per_dispatch": 64})
    est.fit({"x": x, "y": y}, epochs=2, batch_size=64,
            checkpoint_trigger=TriggerOr(SeveralIteration(4),
                                         MinLoss(-1.0)),
            verbose=False)
    ckpts = sorted(int(d.split("-")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("ckpt-"))
    # fuse capped at the nested interval: checkpoints land every 4 steps,
    # not once per 64-step dispatch
    assert ckpts[-1] == 16 and len(ckpts) >= 4, ckpts


def test_fit_with_validation_uses_cached_eval_fuse(orca_context):
    """fit(validation_data=...) evaluates every epoch; the eval fuse
    probe must run once and be cached, and val metrics must appear in the
    epoch stats."""
    x, y = make_linear_data(512)
    est = Estimator.from_keras(linear_model_creator, loss="mse",
                               optimizer="sgd", metrics=["mae"])
    calls = {"n": 0}
    real_probe = est._auto_probe_eval_fuse

    def counting_probe(*a, **kw):
        calls["n"] += 1
        return real_probe(*a, **kw)

    est._auto_probe_eval_fuse = counting_probe
    stats = est.fit({"x": x, "y": y}, epochs=3, batch_size=64,
                    validation_data={"x": x, "y": y}, verbose=False)
    assert all("val_mae" in s and np.isfinite(s["val_mae"]) for s in stats)
    assert calls["n"] <= 1          # probed once, cached for epochs 2-3


# ---------------------------------------------------------------------------
# ISSUE 34: fit's batch work at call and epoch boundaries. Driven through a
# feed that forwards only epoch() and steps_per_epoch, as the benchmark's
# RecordingFeed does: whatever fit does has to work through those two.
# ---------------------------------------------------------------------------

_FEED_ROWS, _FEED_BS, _FEED_STEPS = 256, 32, 8


def _id_data(seed=0):
    """Rows that carry their own index in feature 0."""
    rng = np.random.RandomState(seed)
    x = rng.rand(_FEED_ROWS, 4).astype(np.float32)
    x[:, 0] = np.arange(_FEED_ROWS)
    return {"x": x, "y": rng.rand(_FEED_ROWS).astype(np.float32)}


def _id_iterator(mesh, seed=7):
    from analytics_zoo_tpu.orca.learn import utils as learn_utils
    return learn_utils.data_to_iterator(_id_data(), _FEED_BS, mesh,
                                        shuffle=True, seed=seed)


def _batch_ids(batch):
    return np.asarray(batch.x[0])[..., 0].astype(int).ravel().tolist()


class _TwoMemberFeed:
    """``epoch()`` and ``steps_per_epoch`` of the pipeline it wraps (and
    the ``stats`` fit shares with it), nothing else of it. Keeps every
    ``epoch()`` call's arguments, the rows of every batch it delivered, and
    when each call's generator opened and closed; ``on_open(k)`` runs as
    call k opens."""

    def __init__(self, pipeline, on_open=None):
        self._pipeline = pipeline
        self._on_open = on_open
        self.calls, self.rows, self.events = [], [], []

    @property
    def steps_per_epoch(self):
        return self._pipeline.steps_per_epoch

    @property
    def stats(self):
        return self._pipeline.stats

    @stats.setter
    def stats(self, value):
        self._pipeline.stats = value

    def epoch(self, *args, prefetch=True, **kwargs):
        k = len(self.calls)
        self.calls.append(dict(kwargs, prefetch=prefetch))
        fed = []
        self.rows.append(fed)
        self.events.append(("open", k))
        if self._on_open is not None:
            self._on_open(k)
        gen = self._pipeline.epoch(*args, prefetch=prefetch, **kwargs)
        try:
            for batch in gen:
                fed.append(_batch_ids(batch))
                yield batch
        finally:
            gen.close()
            self.events.append(("close", k))


def _pump_threads():
    import threading
    return [t for t in threading.enumerate()
            if t.name == "zoo-infeed-pump" and t.is_alive()]


def _built_estimator(**kwargs):
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    kwargs.setdefault("config", {"steps_per_dispatch": 1})
    est = TPUEstimator(linear_model_creator({}), loss="mse", optimizer="adam",
                       seed=0, **kwargs)
    est.engine.build((_id_data()["x"][:1],))
    return est


def _fail_once(real, at):
    """``real`` with its ``at``-th call (from 1) raising instead."""
    seen = {"n": 0}

    def flaky(*a, **kw):
        seen["n"] += 1
        if seen["n"] == at:
            raise RuntimeError("injected chip failure")
        return real(*a, **kw)
    return flaky


# case -> what fit is asked for, and what the feed must then have seen:
# `epoch_calls` calls of epoch(), `open_ahead` of them before a sync, the
# batches each call delivered
_BOUNDARY_CASES = {
    "plain": dict(epochs=3, epoch_calls=3, open_ahead=2,
                  delivered=[8, 8, 8]),
    # the pump has batches left when fit leaves the epoch: its generator is
    # closed before the next opens
    "short_epochs": dict(epochs=3, steps_per_epoch=2, epoch_calls=3,
                         open_ahead=2, delivered=[2, 2, 2]),
    # evaluate runs its own pump while the next epoch's sits filled
    "validation": dict(epochs=3, validation=True, epoch_calls=3,
                       open_ahead=2, delivered=[8, 8, 8]),
    "fused": dict(epochs=3, fuse=2, epoch_calls=3, open_ahead=2,
                  delivered=[4, 4, 4]),
    # a step of epoch 1 raises: that epoch is run again from a fresh epoch()
    "step_fails": dict(epochs=3, retries=True, fail_step=8 + 3,
                       epoch_calls=4, open_ahead=2, delivered=[8, 3, 8, 8]),
    # epoch 0's sync raises after epoch 1 was opened ahead: what was opened
    # is closed (one batch delivered, none trained), epoch 0 runs again
    "sync_fails": dict(epochs=2, retries=True, fail_sync=1,
                       epoch_calls=4, open_ahead=2, delivered=[8, 1, 8, 8]),
    # the notice comes while epoch 1 opens epoch 2 ahead: epoch 1 ends whole,
    # epoch 2 never runs and its iterator is closed
    "preempted": dict(epochs=5, retries=True, sigterm_at_open=2,
                      epoch_calls=3, open_ahead=2, delivered=[8, 8, 1],
                      stats=2),
}


@pytest.mark.parametrize("case", sorted(_BOUNDARY_CASES))
def test_fit_opens_next_epoch_before_the_sync(orca_context, tmp_path,
                                              monkeypatch, case):
    """On a built engine fit takes no sample; it opens epoch k+1, and waits
    for its first batch, after epoch k's last dispatch and before epoch k's
    sync; the rows fed are those of a twin iterator walked in turn; never
    two of its pumps alive, none left when it returns."""
    import itertools
    import os
    import signal

    from analytics_zoo_tpu.obs import trace
    from analytics_zoo_tpu.resilience import watchdog
    want = dict(_BOUNDARY_CASES[case])
    epochs, fuse = want["epochs"], want.get("fuse", 1)
    est = _built_estimator(
        model_dir=str(tmp_path) if want.get("retries") else None)
    if fuse > 1:
        monkeypatch.setattr(est, "_choose_fuse", lambda *a, **k: fuse)
    if "fail_step" in want:
        monkeypatch.setattr(est.engine, "train_batch", _fail_once(
            est.engine.train_batch, want["fail_step"]))
    if "fail_sync" in want:
        syncs = _fail_once(lambda: None, want["fail_sync"])

        def watched(label, fn, *a, **kw):
            if label == "engine.sync":
                syncs()
            return fn(*a, **kw)
        monkeypatch.setattr(watchdog, "watched", watched)
    on_open = None
    if "sigterm_at_open" in want:
        def on_open(k):
            if k == want["sigterm_at_open"]:
                os.kill(os.getpid(), signal.SIGTERM)
    feed = _TwoMemberFeed(_id_iterator(est.mesh), on_open)
    trace.clear()
    with trace.tracing():
        stats = est.fit(
            feed, epochs=epochs, batch_size=_FEED_BS, verbose=False,
            steps_per_epoch=want.get("steps_per_epoch"),
            validation_data=_id_data(1) if want.get("validation") else None,
            max_failure_retries=2 if want.get("retries") else None)
        spans = trace.drain()
    assert _pump_threads() == []
    assert len(stats) == want.get("stats", epochs)
    if "sigterm_at_open" in want:
        assert stats[-1]["preempted"] is True
    if want.get("validation"):
        assert all(np.isfinite(s["val_loss"]) for s in stats)

    # (a) no sample; (b) epoch() as often as epochs ran, never two open
    assert [c for c in feed.calls if not c["prefetch"]] == []
    assert len(feed.calls) == want["epoch_calls"]
    assert all(c.get("fuse", 1) == fuse for c in feed.calls)
    assert feed.events == [(what, k) for k in range(want["epoch_calls"])
                           for what in ("open", "close")]
    snap = est.data_pipeline_stats()
    assert snap["open_ahead_n"] == want["open_ahead"]
    assert snap["open_ahead_s"] > 0
    if not want.get("validation"):      # evaluate's pumps count there too
        assert snap["first_batch_n"] == want["epoch_calls"]

    # the rows: a twin with the same seed, one epoch() a call, in turn
    twin = _id_iterator(est.mesh)
    assert [len(fed) for fed in feed.rows] == want["delivered"]
    for fed in feed.rows:
        walked = twin.epoch(fuse=fuse) if fuse > 1 else twin.epoch()
        assert fed == [_batch_ids(b)
                       for b in itertools.islice(walked, len(fed))]
        walked.close()
    assert all(sorted(sum(fed, [])) == list(range(_FEED_ROWS))
               for fed in feed.rows if len(fed) * fuse == _FEED_STEPS)

    # the order: an epoch's open-ahead ends before that epoch's sync starts
    by_id = {s.span_id: s for s in spans}
    ahead = [s for s in spans if s.name == "epoch.open_ahead"]
    assert len(ahead) == want["open_ahead"]
    for oa in ahead:
        ep = by_id[oa.parent_id]
        assert ep.name == "epoch"
        assert oa.attrs["epoch"] == ep.attrs["epoch"] + 1
        (sync,) = [s for s in spans if s.name == "epoch.sync"
                   and s.parent_id == ep.span_id]
        last = max(s.t1 for s in spans if s.name == "engine.dispatch"
                   and s.parent_id == ep.span_id)
        assert last <= oa.t0 <= oa.t1 <= sync.t0
        (first,) = [s for s in spans if s.name == "infeed.first_batch"
                    and s.parent_id == oa.span_id]
        assert oa.t0 <= first.t0 <= first.t1 <= oa.t1


@pytest.mark.parametrize("built", [False, True], ids=["unbuilt", "built"])
def test_fit_prepare_samples_only_an_unbuilt_engine(orca_context, built):
    """An unbuilt engine takes one unprefetched sample for its build; a
    built one (every call but an estimator's first) none. A call's last
    epoch opens nothing ahead."""
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    est = _built_estimator() if built else TPUEstimator(
        linear_model_creator({}), loss="mse", optimizer="adam", seed=0,
        config={"steps_per_dispatch": 1})
    feed = _TwoMemberFeed(_id_iterator(est.mesh))
    est.fit(feed, epochs=1, batch_size=_FEED_BS, verbose=False)
    samples = [c for c in feed.calls if not c["prefetch"]]
    assert len(samples) == (0 if built else 1)
    assert all(c["shuffle"] is False for c in samples)
    assert len(feed.calls) == 1 + len(samples)
    assert est.data_pipeline_stats()["open_ahead_n"] == 0
    assert est.engine.params is not None and _pump_threads() == []
    est.fit(feed, epochs=2, batch_size=_FEED_BS, verbose=False)
    assert len(feed.calls) == 3 + len(samples)      # and no second sample
    assert est.data_pipeline_stats()["open_ahead_n"] == 1


@pytest.mark.parametrize("counter", ["_epoch", "_epoch_idx"])
def test_fit_on_built_engine_keeps_each_calls_shuffle_seeds(orca_context,
                                                            counter):
    """The sample a built engine no longer takes consumed one shuffle seed a
    call; where the iterator shows its counter, fit advances it by that one,
    so that every call's epochs draw the seeds they drew before (and
    ``initial_epoch`` segments stay equal to an uninterrupted run)."""
    from analytics_zoo_tpu.orca.learn import utils as learn_utils

    class _Counting:
        """The two members, and a shuffle counter under ``counter``."""
        steps_per_epoch = _FEED_STEPS

        def __init__(self, mesh):
            self._it = _id_iterator(mesh)
            setattr(self, counter, 0)
            self.seeds = []

        def epoch(self, shuffle=True, prefetch=True):
            self.seeds.append(getattr(self, counter))
            self._it._epoch = getattr(self, counter)
            setattr(self, counter, getattr(self, counter) + 1)
            yield from self._it.epoch(shuffle=shuffle, prefetch=prefetch)

    est = _built_estimator()
    it = _Counting(est.mesh)
    assert learn_utils.data_to_iterator(it, _FEED_BS, est.mesh) is it
    est.fit(it, epochs=2, batch_size=_FEED_BS, verbose=False)
    est.fit(it, epochs=1, batch_size=_FEED_BS, verbose=False)
    est.fit(it, epochs=2, batch_size=_FEED_BS, verbose=False, initial_epoch=7)
    # each call skips the seed its sample took: 0, 3, 7 are never drawn
    assert it.seeds == [1, 2, 4, 8, 9]
