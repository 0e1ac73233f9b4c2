"""The single training engine.

This is the TPU-native replacement for all five of the reference's training
backends (SURVEY.md §2.3): BigDL InternalDistriOptimizer
(zoo/.../keras/models/Topology.scala:1145-1552), TF2 MultiWorkerMirrored
(pyzoo/zoo/orca/learn/tf2/tf_runner.py:281-360), PyTorch DDP-gloo
(torch_runner.py:136-140), Horovod-on-Ray and MXNet-PS. Where the reference
exports graphs across a py4j boundary and allreduces grads through the Spark
block manager per iteration (SURVEY.md §3.2 hot loop), here the whole step —
forward, backward, gradient reduction, optimizer update — is ONE jitted XLA
program over the device mesh: gradients reduce over ICI because params are
replicated over the data axes and XLA inserts the collectives; optimizer state
can shard over the ``fsdp`` axis (ZeRO-style weight-update sharding, cf.
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel Training",
arXiv:2004.13336).
"""

from __future__ import annotations

import inspect
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...obs import trace as _trace
from ...parallel.sharding import FsdpPlan, SpecLayout
from ...resilience import faults as _faults
from ...resilience import watchdog as _watchdog
from .metrics import Metric
from .utils import Batch


def _module_train_kwarg(module) -> Optional[str]:
    """Detect whether the flax module's __call__ takes train/training/
    deterministic so both our model zoo and user modules work."""
    try:
        sig = inspect.signature(type(module).__call__)
    except (TypeError, ValueError):
        return None
    for name in ("train", "training"):
        if name in sig.parameters:
            return name
    if "deterministic" in sig.parameters:
        return "deterministic"
    return None


class TrainEngine:
    """Owns the jitted train/eval/predict steps for one model.

    Parameters
    ----------
    module : flax.linen.Module
    tx : optax.GradientTransformation
    loss_fn : (y_true_tuple, y_pred) -> per-example loss  (or None: model
        returns loss directly)
    metrics : dict name -> Metric
    mesh : device mesh (dp/fsdp/tp/sp axes)
    """

    def __init__(self, module, tx: optax.GradientTransformation,
                 loss_fn: Optional[Callable], metrics: Dict[str, Metric],
                 mesh: Mesh, seed: int = 0,
                 fsdp_params: bool = False, compile_cache=None,
                 prologue=None,
                 sharding: Optional[SpecLayout] = None):
        from ...compile import resolve_cache
        # every jitted step goes through the process-wide compile plane
        # (ExecutableCache): structurally identical engines share ONE XLA
        # executable instead of each paying compilation. ``compile_cache``
        # False opts this engine out (plain jax.jit).
        self.compile_cache = resolve_cache(compile_cache)
        self.module = module
        self.tx = tx
        self.loss_fn = loss_fn
        self.metrics = metrics
        self.mesh = mesh
        self.seed = seed
        # on-device input prologue (BatchPrologue): cast/normalize/one-hot
        # runs INSIDE every jitted step, so the host ships narrow source
        # dtypes (uint8 images, int32 ids) and XLA fuses the float prologue
        # into the first layer — see orca/learn/prologue.py
        self.prologue = prologue
        self.fsdp_params = fsdp_params and mesh.shape.get("fsdp", 1) > 1
        # sharding plane (parallel/sharding.py): SpecLayout-driven fsdp×tp
        # over the multi-axis mesh — params live as a bucketed flat vector
        # P("fsdp") plus tp-sharded held leaves, assembled (gathered) inside
        # every jitted step. GSPMD owns all its collectives.
        self.sharding = sharding
        self.fsdp_plan: Optional[FsdpPlan] = None
        if self.sharding is not None and self.fsdp_params:
            raise ValueError(
                "sharding=SpecLayout supersedes fsdp_params (the legacy "
                "per-leaf ZeRO split) — pass one or the other")
        self._train_kwarg = _module_train_kwarg(module)
        self.params = None
        self.extra_vars: Dict[str, Any] = {}
        self.opt_state = None
        self.step = 0
        # PartitionSpec tree (aligned with unboxed params) when the module
        # declares tensor-parallel shardings via nn.with_partitioning —
        # see parallel/tensor_parallel.py
        self._tp_specs = None
        self._repl = NamedSharding(mesh, P())
        self._jit_train = None
        self._jit_train_multi = None
        self._jit_eval = None
        self._jit_eval_multi = None
        self._jit_predict = None
        self._clip_norm: Optional[float] = None
        self._clip_min: Optional[float] = None
        self._clip_max: Optional[float] = None
        # optional PipelineStats (set by the estimator): the engine records
        # its dispatch time under the "step" stage so the data-plane timers
        # (assemble/h2d/stall) have a compute-side denominator. Host-side
        # dispatch time, deliberately: blocking on the result every step
        # would serialize async dispatch.
        self.pipeline_stats = None

    # --- gradient clipping (reference plumbs clip-by-L2 / clip-constant
    # through every estimator: zoo/.../pipeline/estimator/Estimator.scala:
    # 68-141) — applied to grads inside the jitted step, so clipping config
    # never changes the optax state structure ---------------------------------
    _KEEP = object()                    # "leave this clip setting as-is"

    def set_gradient_clipping(self, *, norm=_KEEP, min_value=_KEEP,
                              max_value=_KEEP):
        """Update clip settings; unspecified kwargs keep their current value
        (so norm- and constant-clipping can be configured independently)."""
        if norm is not TrainEngine._KEEP:
            self._clip_norm = norm
        if min_value is not TrainEngine._KEEP:
            self._clip_min = min_value
        if max_value is not TrainEngine._KEEP:
            self._clip_max = max_value
        self._jit_train = None          # clip constants are baked into the jit
        self._jit_train_multi = None

    def clear_gradient_clipping(self):
        self.set_gradient_clipping(norm=None, min_value=None, max_value=None)

    def _clip_grads(self, grads):
        if self._clip_norm is not None:
            gnorm = optax.global_norm(grads)
            scale = jnp.minimum(1.0, self._clip_norm /
                                jnp.maximum(gnorm, 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
        if self._clip_min is not None or self._clip_max is not None:
            grads = jax.tree.map(
                lambda g: jnp.clip(g, self._clip_min, self._clip_max), grads)
        return grads

    # --- init ---------------------------------------------------------------
    def build(self, sample_x: Tuple[np.ndarray, ...]):
        if self.params is not None:
            return
        # set-up stages (obs/trace.py): what a build costs is counted
        # whether or not anyone traces, the eager init apart from the
        # placement of the state it shapes
        with _trace.stage("engine.build"):
            rng = jax.random.PRNGKey(self.seed)
            small = tuple(jnp.asarray(a[:1]) for a in sample_x)
            if self.prologue is not None:
                # the module sees post-prologue tensors at init, exactly as
                # it will inside the jitted steps
                small = self.prologue.apply_x(small)
            with _trace.stage("engine.init_vars"):
                variables = dict(self._init_vars(rng, small))
            with _trace.stage("engine.place_params"):
                # a parameterless graph (e.g. a pure merge/functional model)
                # inits with no "params" collection at all
                params = variables.pop("params", {})
                params, variables = self._capture_tp_specs(params, variables)
                if self.sharding is not None:
                    params = self._build_sharding(params)
                self.params = jax.device_put(params,
                                             self._param_sharding(params))
                self.extra_vars = jax.device_put(
                    variables, jax.tree.map(lambda _: self._repl, variables))
            with _trace.stage("engine.opt_init"):
                if self.fsdp_plan is not None:
                    self.opt_state = self._init_sharded_tree_opt()
                else:
                    opt_state = self.tx.init(self.params)
                    self.opt_state = jax.device_put(
                        opt_state, self._opt_sharding(opt_state))
            self.step = 0

    # --- sharding plane (parallel/sharding.py) ------------------------------
    def _build_sharding(self, params):
        """Bind the SpecLayout to this param tree: merge module-declared tp
        specs with the layout's rules, build the FsdpPlan over the leaves
        left trivially-sharded, and convert params to the composite form
        (bucketed flat vector P(fsdp) + held leaves). Returns the tree the
        engine will own — composite when anything rides, else unchanged."""
        self._tp_specs = self.sharding.merge_specs(params, self._tp_specs,
                                                   self.mesh)
        if self.sharding.fsdp:
            self.fsdp_plan = FsdpPlan.build(
                params, self._tp_specs, self.mesh,
                axis=self.sharding.fsdp_axis,
                bucket_mb=self.sharding.bucket_mb)
        if self.fsdp_plan is None:
            return params
        return self.fsdp_plan.to_composite(jax.device_get(params))

    def _init_sharded_tree_opt(self):
        """Optimizer state over the composite params, jitted with sharded
        out_shardings so no device ever materializes a full moment vector
        (the model may be bigger than one chip: a plain ``tx.init`` would
        build them whole on device 0 before any resharding ran)."""
        template = jax.eval_shape(self.tx.init, self.params)
        return jax.jit(self.tx.init,
                       out_shardings=self._opt_sharding(template))(
            self.params)

    def _init_vars(self, rng, small_x):
        kwargs = {}
        if self._train_kwarg == "deterministic":
            kwargs["deterministic"] = True
        elif self._train_kwarg:
            kwargs[self._train_kwarg] = False
        return self.module.init(
            {"params": rng, "dropout": jax.random.fold_in(rng, 1)},
            *small_x, **kwargs)

    def _capture_tp_specs(self, params, variables):
        """If any param carries flax partitioning metadata (the TP layers in
        parallel/tensor_parallel.py declare their Megatron column/row specs
        that way), record the PartitionSpec tree and unbox — the engine then
        works with plain arrays and the specs drive NamedShardings; GSPMD
        inserts the tp collectives."""
        import flax.linen as nn

        def boxed(tree):
            return any(isinstance(l, nn.Partitioned) for l in
                       jax.tree.leaves(tree, is_leaf=lambda x: isinstance(
                           x, nn.Partitioned)))

        if boxed(params):
            self._tp_specs = nn.get_partition_spec(params)
            params = nn.unbox(params)
        if boxed(variables):
            variables = nn.unbox(variables)
        return params, variables

    def _leaf_sharding(self, leaf, spec) -> NamedSharding:
        if spec is not None and any(a is not None for a in spec):
            return NamedSharding(self.mesh, spec)
        if self.fsdp_params:
            return self._leaf_fsdp_sharding(leaf)
        return self._repl

    def _leaf_fsdp_sharding(self, leaf) -> NamedSharding:
        """ZeRO-style sharding rule: split the largest dim divisible by the
        fsdp axis size; replicate params too small to shard. XLA then
        all-gathers params for fwd/bwd and reduce-scatters grads — the
        weight-update sharding of arXiv:2004.13336 without any manual
        collective code."""
        size = self.mesh.shape.get("fsdp", 1)
        shape = getattr(leaf, "shape", ())
        if size <= 1 or not shape or int(np.prod(shape)) < 2 * size:
            return self._repl
        dims = sorted(range(len(shape)), key=lambda d: -shape[d])
        for d in dims:
            if shape[d] % size == 0:
                spec = [None] * len(shape)
                spec[d] = "fsdp"
                return NamedSharding(self.mesh, P(*spec))
        return self._repl

    def _param_sharding(self, params):
        if self.fsdp_plan is not None and FsdpPlan.is_composite(params):
            return self.fsdp_plan.composite_shardings()
        if self._tp_specs is not None:
            try:
                from jax.sharding import PartitionSpec
                return jax.tree.map(
                    self._leaf_sharding, params, self._tp_specs,
                    is_leaf=lambda x: x is None or isinstance(x,
                                                              PartitionSpec))
            except ValueError:
                pass  # structure mismatch (foreign tree) → default rules
        if self.fsdp_params:
            return jax.tree.map(self._leaf_fsdp_sharding, params)
        return jax.tree.map(lambda _: self._repl, params)

    @staticmethod
    def _path_names(path) -> Tuple:
        return tuple(getattr(k, "key", getattr(k, "name", getattr(k, "idx",
                                                                  None)))
                     for k in path)

    def _opt_sharding(self, opt_state):
        """Optimizer moments share the param sharding rule (same leaf
        shapes). With TP specs, each opt leaf whose tree path ends with a
        full param path (optax moments embed the entire params tree) adopts
        that param's sharding; counters/scalars fall through to the default
        rules."""
        if self.fsdp_plan is not None:
            # moment nodes over composite params ARE composites (optax
            # inherits the structure); counters/scalars replicate
            return jax.tree.map(
                lambda node: (self.fsdp_plan.composite_shardings()
                              if FsdpPlan.is_composite(node)
                              else self._repl),
                opt_state, is_leaf=FsdpPlan.is_composite)
        if self._tp_specs is None or self.params is None:
            return self._param_sharding_default(opt_state)
        shapes = {self._path_names(p): getattr(l, "shape", None)
                  for p, l in jax.tree_util.tree_flatten_with_path(
                      self.params)[0]}
        param_sh = {
            self._path_names(path): sh
            for path, sh in jax.tree_util.tree_flatten_with_path(
                self._param_sharding(self.params))[0]}

        def rule(path, leaf):
            names = self._path_names(path)
            for start in range(len(names)):
                key = names[start:]
                sh = param_sh.get(key)
                if sh is not None:
                    # factored optimizers (adafactor) keep reduced-shape
                    # state at param paths — only adopt the param's sharding
                    # when the leaf actually has the param's shape
                    if getattr(leaf, "shape", None) == shapes.get(key):
                        return sh
                    break
            return (self._leaf_fsdp_sharding(leaf) if self.fsdp_params
                    else self._repl)

        return jax.tree_util.tree_map_with_path(rule, opt_state)

    def _param_sharding_default(self, tree):
        if self.fsdp_params:
            return jax.tree.map(self._leaf_fsdp_sharding, tree)
        return jax.tree.map(lambda _: self._repl, tree)

    # --- model application --------------------------------------------------
    def _apply(self, params, extra, x, train: bool, rng=None):
        if self.fsdp_plan is not None and FsdpPlan.is_composite(params):
            # the fsdp gathers: one all-gather per bucket, traced into this
            # step; the assembled tree is a temporary of the forward
            params = self.fsdp_plan.assemble(params)
        variables = {"params": params, **extra}
        kwargs = {}
        if self._train_kwarg == "deterministic":
            kwargs["deterministic"] = not train
        elif self._train_kwarg:
            kwargs[self._train_kwarg] = train
        mutable = [k for k in extra.keys()] if train and extra else False
        rngs = {"dropout": rng} if (train and rng is not None) else None
        out = self.module.apply(variables, *x, mutable=mutable, rngs=rngs,
                                **kwargs)
        if mutable:
            preds, new_extra = out
            return preds, dict(new_extra)
        return out, extra

    def _compute_loss(self, y, preds, w):
        if self.loss_fn is None:
            per_ex = preds  # model returned loss directly
        else:
            y0 = y[0] if (isinstance(y, tuple) and len(y) == 1) else y
            per_ex = self.loss_fn(y0, preds)
        per_ex = per_ex.reshape(per_ex.shape[0], -1).mean(-1)
        if w is None:       # full batch, weights synthesized (all ones)
            return jnp.mean(per_ex)
        return jnp.sum(per_ex * w) / jnp.maximum(jnp.sum(w), 1e-8)

    def _pre(self, x, y):
        """Apply the on-device prologue (traced into every jitted step; a
        no-op without one). The wire carries the narrow source dtypes; the
        step starts by casting/normalizing them in f32 on device — bit-
        identical to a host-side f32 pipeline, minus 2-4x the H2D bytes."""
        if self.prologue is None:
            return x, y
        return self.prologue(x, y)

    # --- steps --------------------------------------------------------------
    def _train_step(self, params, extra, opt_state, step, x, y, w):
        # stable scope names on the step's ops (free at run time; no op
        # changes): a trace's reduction splits a step's device time into
        # `jvp(forward)`, `transpose(jvp(forward))` (the backward; JAX wraps
        # the scope's name itself) and `optimizer`, whatever XLA fuses
        with jax.named_scope("prologue"):
            x, y = self._pre(x, y)
        rng = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)

        def loss_of(p):
            with jax.named_scope("forward"):
                preds, new_extra = self._apply(p, extra, x, True, rng)
                loss = self._compute_loss(y, preds, w)
            return loss, (preds, new_extra)

        (loss, (_, new_extra)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        with jax.named_scope("optimizer"):
            grads = self._clip_grads(grads)
            if self.fsdp_plan is not None and FsdpPlan.is_composite(grads):
                # constrain bucket grads back to P(fsdp): XLA combines over
                # the fsdp groups and each device keeps only its own shard,
                # so the optimizer update below is shard-local (ZeRO)
                grads = self.fsdp_plan.constrain_shards(grads)
            updates, new_opt = self.tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            if self.fsdp_plan is not None \
                    and FsdpPlan.is_composite(new_params):
                # pin updated params onto their resting shardings so scan
                # carries and donated outputs keep the 1/N layout
                new_params = self.fsdp_plan.constrain_shards(new_params)
        return new_params, new_extra, new_opt, loss

    def _train_multi_step(self, params, extra, opt_state, step0, xs, ys, ws):
        """k optimizer steps fused into ONE XLA program via ``lax.scan`` over
        stacked batches (leaves shaped ``(k, batch, ...)``). Numerically
        identical to k sequential ``_train_step`` calls — same rng folding,
        same clipping, same optax update — but the host dispatches once per k
        steps, so small models are no longer bound by the per-call dispatch
        latency (the XLA-native analogue of the reference's multi-model-per-
        executor threading, zoo/.../keras/models/Topology.scala:1186-1196)."""
        def body(carry, inp):
            params, extra, opt_state, step = carry
            x, y, w = inp
            new_p, new_e, new_o, loss = self._train_step(
                params, extra, opt_state, step, x, y, w)
            return (new_p, new_e, new_o, step + 1), loss

        (params, extra, opt_state, _), losses = jax.lax.scan(
            body, (params, extra, opt_state, step0), (xs, ys, ws))
        return params, extra, opt_state, losses

    def _eval_step(self, params, extra, metric_states, x, y, w):
        x, y = self._pre(x, y)
        preds, _ = self._apply(params, extra, x, False)
        loss = (self._compute_loss(y, preds, w)
                if (y is not None or self.loss_fn is None) else jnp.zeros(()))
        y0 = None
        if y is not None:
            y0 = y[0] if (isinstance(y, tuple) and len(y) == 1) else y
        if w is None:
            w = jnp.ones(x[0].shape[0], jnp.float32)
        new_states = {}
        for name, m in self.metrics.items():
            new_states[name] = m.update(metric_states[name], y0, preds, w)
        count = jnp.sum(w)
        return new_states, loss * count, count

    def _eval_multi_step(self, params, extra, metric_states, xs, ys, ws):
        """k fused eval steps in ONE dispatch (lax.scan over stacked
        batches) — same dispatch-amortization as _train_multi_step, but
        stateless apart from the metric accumulators, so fusing is always
        semantics-preserving. Returns (states, loss_sum, count) with the
        group's loss/count already summed."""
        def body(carry, inp):
            states, loss_sum, count = carry
            x, y, w = inp
            states, l, n = self._eval_step(params, extra, states, x, y, w)
            return (states, loss_sum + l, count + n), None

        init = (metric_states, jnp.zeros(()), jnp.zeros(()))
        (states, loss_sum, count), _ = jax.lax.scan(body, init, (xs, ys, ws))
        return states, loss_sum, count

    def eval_batch_group(self, metric_states, batch: Batch):
        """Fused-eval entry: batch carries stacked (k, local_batch, ...)
        arrays. Returns (states, summed_loss, summed_count)."""
        if self._jit_eval_multi is None:
            self._jit_eval_multi = self._wrap("eval_multi",
                                              self._eval_multi_step,
                                              donate_argnums=(2,),
                                              extra_key=self._sharding_key())
        t0 = time.perf_counter()
        out = self._jit_eval_multi(self.params, self.extra_vars,
                                   metric_states, batch.x, batch.y,
                                   batch.w)
        if self.pipeline_stats is not None:
            self.pipeline_stats.add("step", time.perf_counter() - t0,
                                    count=int(batch.fused))
        return out

    def _predict_step(self, params, extra, x):
        x, _ = self._pre(x, None)
        preds, _ = self._apply(params, extra, x, False)
        return preds

    # --- public API ---------------------------------------------------------
    def _wrap(self, label: str, fn, donate_argnums=(), extra_key=None):
        """jit through the compile plane when enabled, plain jax.jit
        otherwise. Both return jit-like callables (with ``.lower``)."""
        if self.compile_cache is None:
            return jax.jit(fn, donate_argnums=donate_argnums)
        return self.compile_cache.wrap(fn, label=label,
                                       donate_argnums=donate_argnums,
                                       extra_key=extra_key)

    def _sharding_key(self) -> Optional[str]:
        """Sharding-plane fingerprint for the compile plane's structural
        key: the SpecLayout rules + the fsdp bucket layout are part of
        every step's identity (train AND eval/predict — the gathers are
        traced into all of them), so two engines with different layouts
        never share an executable. None when the plane is off, keeping
        every pre-existing cache key byte-identical."""
        if self.sharding is None:
            return None
        key = self.sharding.fingerprint()
        if self.fsdp_plan is not None:
            key += ":" + self.fsdp_plan.signature()
        return key

    def _declare_sharding_accounting(self):
        """Register the fsdp plan's declared gather accounting under the
        sharding key — the HLO linter cross-checks compiled programs
        salted with it (per-axis launches/bytes == declared)."""
        if self.fsdp_plan is None:
            return
        try:
            from ...analysis.hlo_lint import declare_accounting
        except ImportError:
            return
        summary = self.fsdp_plan.summary()
        tp_axis = self.sharding.tp_axis
        tp_size = self.mesh.shape.get(tp_axis, 1)
        tp_leaves = 0
        if self._tp_specs is not None and tp_size > 1:
            from ...parallel.sharding import _is_spec_leaf

            def _mentions_tp(spec) -> bool:
                if spec is None:
                    return False
                for entry in spec:
                    axes = entry if isinstance(entry, tuple) else (entry,)
                    if tp_axis in axes:
                        return True
                return False

            tp_leaves = sum(
                _mentions_tp(s) for s in jax.tree_util.tree_leaves(
                    self._tp_specs, is_leaf=_is_spec_leaf))
        summary["tp"] = {"axis": tp_axis, "axis_size": int(tp_size),
                         "sharded_leaves": int(tp_leaves)}
        declare_accounting(self._sharding_key(), summary)

    def ensure_jit_train(self):
        """Build (or return) the jitted single-step executable — the one
        place its jit options live, shared by train_batch and the
        estimator's fuse probe."""
        if self._jit_train is None:
            self._declare_sharding_accounting()
            self._jit_train = self._wrap("train", self._train_step,
                                         donate_argnums=(0, 2),
                                         extra_key=self._sharding_key())
        return self._jit_train

    def train_step_args(self, batch: Batch) -> Tuple:
        """The positional args the jitted train step takes for ``batch``."""
        return (self.params, self.extra_vars, self.opt_state,
                jnp.asarray(self.step), batch.x, batch.y, batch.w)

    def train_step_cache_key(self, batch: Batch) -> Optional[str]:
        """Structural key of the single-step train executable for this
        engine + batch signature (lowering only, no compile; the lowering
        is reused by the next dispatch). None when the compile plane is
        off. Stable across warm restarts, so it also keys the estimator's
        persisted fuse-probe results."""
        fn = self.ensure_jit_train()
        if not hasattr(fn, "cache_key"):
            return None
        return fn.cache_key(*self.train_step_args(batch))

    def eval_step_cache_key(self, metric_states, batch: Batch
                            ) -> Optional[str]:
        """Structural key of the single-step eval executable (see
        train_step_cache_key)."""
        fn = self._ensure_jit_eval()
        if not hasattr(fn, "cache_key"):
            return None
        return fn.cache_key(self.params, self.extra_vars, metric_states,
                            batch.x, batch.y, batch.w)

    def train_batch(self, batch: Batch) -> jnp.ndarray:
        self.ensure_jit_train()
        # resilience hooks (one global read each when disarmed): the
        # `engine.dispatch` fault site, and a watchdog section bounding the
        # dispatch so a wedged device becomes a classified hang
        wd = _watchdog.active()
        token = wd.enter("engine.dispatch") if wd is not None else None
        t0 = time.perf_counter()
        try:
            # obs span (one flag check disarmed): the per-step device-time
            # segment the Perfetto timeline renders, step-indexed
            with _trace.span("engine.dispatch", step=self.step):
                _faults.fire("engine.dispatch")
                self.params, self.extra_vars, self.opt_state, loss = \
                    self._jit_train(*self.train_step_args(batch))
        finally:
            if token is not None:
                wd.exit(token)
        t1 = time.perf_counter()
        if self.pipeline_stats is not None:
            self.pipeline_stats.add("step", t1 - t0)
        self.step += 1
        return loss

    def train_batch_group(self, batch: Batch) -> jnp.ndarray:
        """Run k fused train steps in one dispatch. ``batch`` carries stacked
        arrays — every x/y leaf is ``(k, local_batch, ...)`` and w (if any) is
        ``(k, local_batch)``. Returns the per-step losses ``(k,)``."""
        if self._jit_train_multi is None:
            self._declare_sharding_accounting()
            self._jit_train_multi = self._wrap(
                "train_multi", self._train_multi_step,
                donate_argnums=(0, 2),
                extra_key=self._sharding_key())
        wd = _watchdog.active()
        token = wd.enter("engine.dispatch") if wd is not None else None
        t0 = time.perf_counter()
        try:
            with _trace.span("engine.dispatch", step=self.step,
                             fused=int(batch.fused)):
                _faults.fire("engine.dispatch")
                self.params, self.extra_vars, self.opt_state, losses = \
                    self._jit_train_multi(*self.train_step_args(batch))
        finally:
            if token is not None:
                wd.exit(token)
        t1 = time.perf_counter()
        k = int(losses.shape[0])
        if self.pipeline_stats is not None:
            self.pipeline_stats.add("step", t1 - t0,
                                    count=k)
        self.step += k
        return losses

    def init_metric_states(self):
        return {name: jax.device_put(m.init_state(),
                                     jax.tree.map(lambda _: self._repl,
                                                  m.init_state()))
                for name, m in self.metrics.items()}

    def _ensure_jit_eval(self):
        if self._jit_eval is None:
            # metric states are consumed and replaced every batch — donate
            # them so XLA updates in place instead of reallocating
            self._jit_eval = self._wrap("eval", self._eval_step,
                                        donate_argnums=(2,),
                                        extra_key=self._sharding_key())
        return self._jit_eval

    def eval_batch(self, metric_states, batch: Batch):
        self._ensure_jit_eval()
        t0 = time.perf_counter()
        out = self._jit_eval(self.params, self.extra_vars, metric_states,
                             batch.x, batch.y, batch.w)
        if self.pipeline_stats is not None:
            self.pipeline_stats.add("step", time.perf_counter() - t0)
        return out

    def finalize_metrics(self, metric_states, loss_sum, count) -> Dict[str, float]:
        out = {}
        for name, m in self.metrics.items():
            out[name] = float(jax.device_get(m.compute(metric_states[name])))
        out["loss"] = float(loss_sum / max(count, 1e-8))
        out["num_samples"] = int(count)
        return out

    def predict_batch(self, x) -> np.ndarray:
        if self._jit_predict is None:
            self._jit_predict = self._wrap("predict", self._predict_step,
                                           extra_key=self._sharding_key())
        return self._jit_predict(self.params, self.extra_vars, x)

    # --- device-side state snapshot (probe/rollback support) ----------------
    def snapshot(self):
        """On-device copy of the full training state. Lets a caller run real
        train steps (e.g. the fuse-factor timing probe) and roll them back
        exactly — the copies survive buffer donation by the probed steps.
        Costs one transient duplicate of params+opt_state in HBM, so callers
        should gate on model size where that matters."""
        cp = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
        return (cp(self.params), cp(self.extra_vars), cp(self.opt_state),
                self.step)

    def restore_snapshot(self, snap):
        self.params, self.extra_vars, self.opt_state, self.step = snap

    # --- sharding telemetry -------------------------------------------------
    def per_device_state_bytes(self) -> int:
        """Param + optimizer bytes resident on ONE device (device 0's
        shards; sharded leaves count 1/N, replicated leaves count full) —
        the number the "4× one chip's HBM" acceptance bound checks."""
        total = 0
        for leaf in (jax.tree.leaves(self.params)
                     + jax.tree.leaves(self.opt_state)):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += int(shards[0].data.nbytes)
            elif hasattr(leaf, "nbytes"):
                total += int(leaf.nbytes)
        return total

    def sharding_snapshot(self) -> Optional[Dict[str, Any]]:
        """Static sharding-plane accounting (mesh axes, fsdp buckets,
        gather bytes, per-device state bytes); None when the plane is
        off."""
        if self.sharding is None:
            return None
        snap: Dict[str, Any] = {
            "fingerprint": self._sharding_key(),
            "axes": {name: int(size)
                     for name, size in self.mesh.shape.items() if size > 1},
            "tp_axis_size": self.mesh.shape.get(self.sharding.tp_axis, 1),
        }
        if self.fsdp_plan is not None:
            snap["fsdp"] = self.fsdp_plan.summary()["fsdp"]
        if self.params is not None and self.opt_state is not None:
            snap["per_device_state_bytes"] = self.per_device_state_bytes()
        return snap

    def sharding_manifest_meta(self) -> Optional[Dict[str, Any]]:
        """What a checkpoint manifest records about the sharding plane that
        wrote it (state is stored in canonical tree form regardless)."""
        if self.sharding is None:
            return None
        meta = {"fingerprint": self.sharding.fingerprint(),
                "fsdp": self.fsdp_plan is not None}
        if self.fsdp_plan is not None:
            meta["buckets"] = len(self.fsdp_plan.layout.bucket_sizes)
            meta["layout_sig"] = self.fsdp_plan.layout.signature()
        return meta

    # --- state access -------------------------------------------------------
    def get_state(self) -> Dict[str, Any]:
        state = {"params": jax.device_get(self.params),
                 "extra_vars": jax.device_get(self.extra_vars),
                 "opt_state": jax.device_get(self.opt_state),
                 "step": self.step,
                 # PartitionSpecs ride along so a fresh engine restoring
                 # this checkpoint re-shards TP params instead of
                 # replicating them
                 "tp_specs": self._tp_specs}
        if self.fsdp_plan is not None:
            # checkpoints always carry the CANONICAL tree form of params
            # and moments: an fsdp-sharded checkpoint restores into a
            # replicated run and vice versa without either knowing about
            # the other. Padding slots hold zeros, so the conversion is
            # lossless.
            state["params"] = self.fsdp_plan.composite_to_tree(
                state["params"])
            state["opt_state"] = self.fsdp_plan.state_to_tree(
                state["opt_state"])
        return state

    def set_state(self, state: Dict[str, Any]):
        if state.get("tp_specs") is not None:
            self._tp_specs = state["tp_specs"]
        params = state["params"]
        if self.sharding is not None:
            # restoring into a sharded engine (possibly never built —
            # load before fit): bind the plan to the checkpoint's
            # canonical tree and convert to the composite form
            if self.fsdp_plan is None:
                self._tp_specs = self.sharding.merge_specs(
                    params, self._tp_specs, self.mesh)
                if self.sharding.fsdp:
                    self.fsdp_plan = FsdpPlan.build(
                        params, self._tp_specs, self.mesh,
                        axis=self.sharding.fsdp_axis,
                        bucket_mb=self.sharding.bucket_mb)
            if self.fsdp_plan is not None:
                params = self.fsdp_plan.to_composite(params)
        self.params = jax.device_put(params, self._param_sharding(params))
        self.extra_vars = jax.device_put(
            state["extra_vars"], jax.tree.map(lambda _: self._repl,
                                              state["extra_vars"]))
        opt_state = state["opt_state"]
        if self.fsdp_plan is not None:
            # canonical tree-form moments -> composite. eval_shape only
            # (structure template); nothing full-size materializes.
            template = jax.eval_shape(self.tx.init, self.params)
            opt_state = self.fsdp_plan.tree_to_state(opt_state, template)
        self.opt_state = jax.device_put(opt_state,
                                        self._opt_sharding(opt_state))
        self.step = int(state["step"])
