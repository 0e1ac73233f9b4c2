"""Sharding plane: canonical per-layer PartitionSpecs over a (dp, fsdp, tp)
mesh — models bigger than one chip.

Two pieces:

* :class:`SpecLayout` — a small registry mapping parameter paths to
  PartitionSpecs over the named mesh axes (Megatron-style tp columns/rows,
  fsdp×tp embedding tables), plus the batch-axis convention. It is the ONE
  object the estimator, engine, serving (``InferenceModel``) and tests agree
  on. Modules that declare their own specs via ``nn.with_partitioning``
  (parallel/tensor_parallel.py) win; SpecLayout rules fill the rest.

* :class:`FsdpPlan` — parameter sharding over the ``fsdp`` axis through a
  :class:`BucketLayout`: params whose spec is trivial live as a padded flat
  f32 vector split into buckets, each bucket stored ``P("fsdp")`` (1/N per
  device).
  Inside the jitted step every bucket passes through
  ``with_sharding_constraint(bucket, P())`` — GSPMD emits exactly ONE
  all-gather per bucket (operand = the 1/N shard), the forward consumes the
  gathered params and drops them, and the gradient constraint back to
  ``P("fsdp")`` makes XLA combine grads over the fsdp groups (grouped
  all-reduce / reduce-scatter + slice, backend's choice). This is
  weight-update sharding (arXiv:2004.13336) with the parameters sharded
  too: the optimizer moments inherit the flat vector's structure, so
  per-device param+moment bytes scale as 1/fsdp and the largest trainable
  model is the mesh's HBM, not one chip's.

Why buckets and not per-leaf sharding: one all-gather per parameter leaf is
a launch-bound wire (hundreds of small collectives); per-bucket gathers are
few, large, and individually schedulable against the forward's compute.

The composite param pytree
--------------------------
When an :class:`FsdpPlan` is active the engine's ``params`` (and therefore
the optax state, which inherits the structure) is the *composite* form::

    {"__fsdp_flat__": {"b000": f32[bucket0], "b001": ...},   # P("fsdp")
     "__fsdp_held__": {"h000": leaf, ...}}                    # tp/explicit

It is a plain pytree, so every existing code path — ``lax.scan`` multi-step,
``optax`` updates, ``global_norm`` clipping (padding slots hold zero grads),
buffer donation, ``snapshot()`` — works unchanged; only ``_apply`` assembles
the full tree (gather), and checkpoints always store the CANONICAL tree form
(:meth:`FsdpPlan.composite_to_tree`), so fsdp-sharded ↔ replicated restores
are bit-exact in both directions.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import hashlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# canonical rules: embedding tables shard rows over fsdp and columns over tp
# (the friesian/NCF pod-scale recommender layout — one table bigger than any
# chip splits over BOTH model axes); everything else is either declared by
# the module (tensor_parallel.py layers) or rides the fsdp flat vector.
DEFAULT_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    ("*embed_table*", ("fsdp", "tp")),
    ("*embedding*", ("fsdp", "tp")),
)


def _path_names(path) -> Tuple:
    return tuple(getattr(k, "key", getattr(k, "name", getattr(k, "idx",
                                                              None)))
                 for k in path)


def _path_str(path) -> str:
    return "/".join(str(n) for n in _path_names(path))


def _is_spec_leaf(x) -> bool:
    return x is None or isinstance(x, P)


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Canonical sharding layout: which mesh axis each parameter dimension
    lives on, and how the batch splits.

    ``rules`` map glob patterns (matched against the ``"/"``-joined param
    path) to a tuple of mesh-axis names, one per leading dimension
    (``None`` = replicated dim; shorter tuples leave trailing dims
    replicated). First match wins. Axes missing from the mesh, of size 1,
    or not dividing the dimension are dropped per-leaf — a layout written
    for an 8-dev pod degrades cleanly on a 1-dev laptop mesh.

    ``fsdp=True`` additionally shards every *unmatched* big f32 param over
    the ``fsdp`` axis: in the train engine through an :class:`FsdpPlan`
    (bucketed flat vector, explicit per-bucket gathers); in serving
    (``InferenceModel``) per-leaf on the largest divisible dim (no update
    step, so the bucket machinery buys nothing there).
    """

    fsdp: bool = True
    bucket_mb: float = 4.0
    data_axis: str = "dp"
    fsdp_axis: str = "fsdp"
    tp_axis: str = "tp"
    rules: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = DEFAULT_RULES
    # leaves smaller than 2*axis_size never shard (a shard under one
    # element per device is padding, not parallelism)

    # -- resolution ----------------------------------------------------------
    @classmethod
    def resolve(cls, config: Dict[str, Any], arg=None
                ) -> Optional["SpecLayout"]:
        """One resolution path for the estimator/serving kwarg + config +
        env knobs:

        * ``arg`` a SpecLayout → use it; ``arg False`` → plane off.
        * ``arg True`` / config ``sharding: true`` / ``ZOO_SHARDING_PLANE=1``
          → default layout; config ``sharding: {...}`` → field overrides.
        * ``ZOO_FSDP_BUCKET_MB`` overrides the gather bucket size.
        Returns None when the plane is off (the engine then runs the
        untouched replicated program).
        """
        from ..common.knobs import get as _knob
        if isinstance(arg, SpecLayout):
            return arg
        if arg is False:
            return None
        cfg = (config or {}).get("sharding")
        if arg is None and cfg is None:
            cfg = _knob("ZOO_SHARDING_PLANE")
        if not cfg and arg is not True:
            return None
        fields = dict(cfg) if isinstance(cfg, dict) else {}
        if "rules" in fields:
            fields["rules"] = tuple(
                (str(pat), tuple(spec)) for pat, spec in fields["rules"])
        bucket_mb = _knob("ZOO_FSDP_BUCKET_MB")
        if bucket_mb is not None and "bucket_mb" not in fields:
            fields["bucket_mb"] = float(bucket_mb)
        return cls(**fields)

    # -- per-leaf specs ------------------------------------------------------
    def spec_for(self, path_names: Sequence, shape: Sequence[int],
                 mesh: Optional[Mesh] = None) -> P:
        """Rule-matched PartitionSpec for one param (``P()`` when no rule
        matches). With a mesh, non-dividing / absent / size-1 axes drop."""
        key = "/".join(str(n) for n in path_names)
        for pat, axes in self.rules:
            if fnmatch.fnmatchcase(key, pat):
                spec = list(axes[:len(shape)])
                spec += [None] * (len(shape) - len(spec))
                if mesh is not None:
                    for d, a in enumerate(spec):
                        if a is None:
                            continue
                        size = mesh.shape.get(a, 1)
                        if size <= 1 or int(shape[d]) % size != 0:
                            spec[d] = None
                return P(*spec)
        return P()

    def merge_specs(self, params, declared, mesh: Mesh):
        """Spec tree aligned with ``params``: module-declared specs (flax
        ``nn.with_partitioning`` metadata, already captured by the engine)
        win; SpecLayout rules fill the trivial slots. Every leaf gets a
        PartitionSpec (``P()`` = no explicit spec → fsdp/replicated)."""
        decl = {}
        if declared is not None:
            decl = {_path_names(p): s for p, s in
                    jax.tree_util.tree_flatten_with_path(
                        declared, is_leaf=_is_spec_leaf)[0]}

        def rule(path, leaf):
            names = _path_names(path)
            d = decl.get(names)
            if d is not None and any(a is not None for a in d):
                return P(*d)
            return self.spec_for(names, getattr(leaf, "shape", ()), mesh)

        return jax.tree_util.tree_map_with_path(rule, params)

    def _fsdp_leaf_spec(self, leaf, mesh: Mesh) -> P:
        """Per-leaf fsdp fallback (serving / non-bucketed consumers): split
        the trailing dim of >=2-dim leaves (the output-feature dim of
        dense/conv kernels) or dim 0 of vectors (bias adds are elementwise
        over features). Never an inner dim: splitting a *contraction* dim
        makes GSPMD compute partial sums + all-reduce, changing the
        matmul's reduction order and breaking serving bit-identity with
        the replicated layout. Non-dividing / tiny leaves replicate."""
        size = mesh.shape.get(self.fsdp_axis, 1)
        shape = getattr(leaf, "shape", ())
        if (not self.fsdp or size <= 1 or not shape
                or int(np.prod(shape)) < 2 * size):
            return P()
        d = len(shape) - 1
        if shape[d] % size == 0:
            spec = [None] * len(shape)
            spec[d] = self.fsdp_axis
            return P(*spec)
        return P()

    def param_shardings(self, mesh: Mesh, params, declared=None):
        """NamedSharding tree for a param/variable tree — the serving-side
        entry (``InferenceModel``): rule/declared specs first, then the
        per-leaf fsdp split, then replication. The train engine instead
        routes unmatched leaves through an :class:`FsdpPlan` (bucketed
        gathers); both leave every device holding ~1/fsdp of the params."""
        specs = self.merge_specs(params, declared, mesh)

        def rule(leaf, spec):
            if spec is not None and any(a is not None for a in spec):
                return NamedSharding(mesh, spec)
            return NamedSharding(mesh, self._fsdp_leaf_spec(leaf, mesh))

        # tree_map flattens only down to `params`' leaves, so the P()
        # entries of `specs` ride through as opaque values
        return jax.tree.map(rule, params, specs)

    # -- batch convention ----------------------------------------------------
    def batch_axes(self, mesh: Mesh) -> Tuple[str, ...]:
        """Mesh axes the batch dim splits over: dp plus fsdp (which acts as
        an extra data axis for activations — same convention as
        ``mesh.data_sharding``); tp ranks see the FULL local batch."""
        axes = tuple(a for a in (self.data_axis, self.fsdp_axis)
                     if mesh.shape.get(a, 1) > 1)
        return axes or (self.data_axis,)

    def batch_spec(self, mesh: Mesh, ndim: int) -> P:
        return P(self.batch_axes(mesh), *([None] * (ndim - 1)))

    # -- identity ------------------------------------------------------------
    def fingerprint(self) -> str:
        """Stable content hash — salts the compile plane's structural key
        (two engines with different layouts must never share an
        executable) and keys the declared hlo_lint accounting."""
        h = hashlib.sha256(repr(
            (self.fsdp, float(self.bucket_mb), self.data_axis,
             self.fsdp_axis, self.tp_axis, self.rules)).encode())
        return (f"sharding:fsdp={int(self.fsdp)}:"
                f"bucket_mb={float(self.bucket_mb)}:{h.hexdigest()[:16]}")


@dataclasses.dataclass
class BucketLayout:
    """Static placement of a float32 pytree inside a zero-padded flat vector
    cut into buckets.

    Leaf order is ``jax.tree_util.tree_flatten`` order — deterministic for
    a given tree structure (dict keys sort), and the SAME order every
    flatten/unflatten call uses, so assembly/disassembly round-trips
    bit-exactly.

    Every bucket is a whole multiple of the axis size ``n_dev`` (the last
    one padded up with zeros): a bucket stored ``P(axis)`` then puts exactly
    1/N of it on each device, and its all-gather tiles evenly — a bucket
    that did not divide would have to be replicated or re-padded inside
    every step. Padding slots hold zeros and receive zero gradients, so
    they stay zero through any elementwise optimizer.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    n_dev: int
    bucket_sizes: Tuple[int, ...]
    total: int
    padded_total: int
    shard_size: int

    @staticmethod
    def build(tree, n_dev: int, bucket_mb: float) -> "BucketLayout":
        """``bucket_mb`` is the target bucket size in MiB; 0 means one
        bucket over the whole vector."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            raise ValueError("bucket layout: empty parameter tree")

        # metadata only — leaf .dtype/.shape, never np.asarray (which
        # would D2H-copy every on-device param just to read its header)
        def _dtype(l):
            dt = getattr(l, "dtype", None)
            return np.dtype(dt) if dt is not None else np.result_type(l)
        for l in leaves:
            # the flat vector is f32: a bf16/f16 leaf would silently
            # change precision through it and an integer leaf would be
            # rounded, so neither round-trips losslessly
            if _dtype(l) != np.dtype(np.float32):
                raise ValueError(
                    f"bucket layout: leaf of dtype {_dtype(l)} cannot ride "
                    "the f32 flat vector (lossless round-trips are "
                    "f32-only)")
        shapes = tuple(tuple(int(d) for d in np.shape(l)) for l in leaves)
        sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
        total = sum(sizes)
        n_dev = int(n_dev)
        if bucket_mb > 0:
            target = max(int(bucket_mb * (1 << 20)) // 4, n_dev)
            b = (target // n_dev) * n_dev
            n_full = total // b
            rem = total - n_full * b
            bucket_sizes = [b] * n_full
            if rem or not bucket_sizes:
                bucket_sizes.append(-(-rem // n_dev) * n_dev or n_dev)
        else:
            bucket_sizes = [-(-total // n_dev) * n_dev]
        padded_total = sum(bucket_sizes)
        return BucketLayout(
            treedef=treedef, shapes=shapes, sizes=sizes,
            n_dev=n_dev, bucket_sizes=tuple(bucket_sizes), total=total,
            padded_total=padded_total, shard_size=padded_total // n_dev)

    def signature(self) -> str:
        """Content hash of everything that changes a step's program."""
        h = hashlib.sha256(repr((
            self.shapes, self.n_dev, self.bucket_sizes)).encode())
        return h.hexdigest()[:16]

    def flatten_np(self, tree) -> np.ndarray:
        """Pytree -> padded flat f32 vector on the host (bit-exact per
        element)."""
        flat = np.concatenate([np.asarray(l).reshape(-1)
                               for l in jax.tree_util.tree_leaves(tree)])
        return np.pad(flat, (0, self.padded_total - self.total))

    def unflatten(self, flat):
        """Padded flat vector -> pytree (inverse of :meth:`flatten_np`):
        slices and reshapes only, so it serves a numpy vector on the host
        and a traced one inside a jitted step alike."""
        out, off = [], 0
        for shape, size in zip(self.shapes, self.sizes):
            out.append(flat[off:off + size].reshape(shape))
            off += size
        return jax.tree_util.tree_unflatten(self.treedef, out)


class FsdpPlan:
    """Bucketed fsdp parameter sharding bound to one param tree.

    Built once per engine from the param tree + merged spec tree: every f32
    leaf with a trivial spec and >= 2*fsdp elements *rides* the flat vector
    (:class:`BucketLayout` over the fsdp axis); everything else is *held*
    aside with its own (tp/explicit) sharding.
    """

    FLAT_KEY = "__fsdp_flat__"
    HELD_KEY = "__fsdp_held__"

    def __init__(self, mesh: Mesh, axis: str, layout: BucketLayout,
                 treedef, ride_mask: Tuple[bool, ...],
                 held_specs: Tuple[P, ...], bucket_mb: float):
        self.mesh = mesh
        self.axis = axis
        self.layout = layout
        self.treedef = treedef              # FULL param tree structure
        self.ride_mask = ride_mask
        self.held_specs = held_specs
        self.bucket_mb = float(bucket_mb)
        self.n_dev = layout.n_dev
        self.bucket_keys = tuple(f"b{i:03d}"
                                 for i in range(len(layout.bucket_sizes)))
        self.held_keys = tuple(f"h{i:03d}"
                               for i in range(len(held_specs)))

    # -- construction --------------------------------------------------------
    @staticmethod
    def build(params, specs, mesh: Mesh, axis: str = "fsdp",
              bucket_mb: float = 4.0) -> Optional["FsdpPlan"]:
        """None when nothing rides (axis size 1, or every leaf is sharded
        by spec / too small / non-f32) — the engine then falls back to
        plain spec shardings and the program is untouched."""
        n = mesh.shape.get(axis, 1)
        if n <= 1:
            return None
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if not leaves:
            return None
        if specs is None:
            spec_leaves = [P()] * len(leaves)
        else:
            spec_leaves = [s for s in jax.tree_util.tree_leaves(
                specs, is_leaf=_is_spec_leaf)]
        if len(spec_leaves) != len(leaves):
            raise ValueError(
                f"sharding plane: spec tree has {len(spec_leaves)} leaves "
                f"for {len(leaves)} params")

        def rides(leaf, spec) -> bool:
            if spec is not None and any(a is not None for a in spec):
                return False
            dt = getattr(leaf, "dtype", None)
            if np.dtype(dt if dt is not None
                        else np.result_type(leaf)) != np.float32:
                return False
            return int(np.prod(np.shape(leaf)) or 1) >= 2 * n

        mask = tuple(rides(l, s) for l, s in zip(leaves, spec_leaves))
        if not any(mask):
            return None
        ridden = [l for l, m in zip(leaves, mask) if m]
        held_specs = tuple((s if s is not None else P())
                           for s, m in zip(spec_leaves, mask) if not m)
        layout = BucketLayout.build(ridden, n, bucket_mb)
        return FsdpPlan(mesh, axis, layout, treedef, mask, held_specs,
                        bucket_mb)

    # -- composite form ------------------------------------------------------
    @staticmethod
    def is_composite(node) -> bool:
        return (isinstance(node, dict)
                and set(node.keys()) == {FsdpPlan.FLAT_KEY,
                                         FsdpPlan.HELD_KEY})

    def _split(self, tree) -> Tuple[List, List]:
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != len(self.ride_mask):
            raise ValueError(
                f"sharding plane: tree has {len(leaves)} leaves, plan was "
                f"built for {len(self.ride_mask)}")
        ridden = [l for l, m in zip(leaves, self.ride_mask) if m]
        held = [l for l, m in zip(leaves, self.ride_mask) if not m]
        return ridden, held

    def _join(self, ridden: List, held: List):
        it_r, it_h = iter(ridden), iter(held)
        leaves = [next(it_r) if m else next(it_h) for m in self.ride_mask]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def to_composite(self, tree) -> Dict:
        """Canonical tree form -> composite (host-side, numpy): flatten the
        ridden leaves into the padded flat vector and slice per-bucket.
        Bit-exact inverse of :meth:`composite_to_tree` (padding is zeros)."""
        ridden, held = self._split(tree)
        flat = self.layout.flatten_np(ridden)
        buckets, off = {}, 0
        for k, b in zip(self.bucket_keys, self.layout.bucket_sizes):
            buckets[k] = np.asarray(flat[off:off + b])
            off += b
        return {self.FLAT_KEY: buckets,
                self.HELD_KEY: dict(zip(self.held_keys,
                                        [np.asarray(h) for h in held]))}

    def composite_to_tree(self, comp: Dict):
        """Composite -> canonical tree form (host-side, numpy) — what
        checkpoints store, so fsdp-sharded and replicated runs read each
        other's state without either knowing about the other."""
        flat = np.concatenate([np.asarray(comp[self.FLAT_KEY][k]).reshape(-1)
                               for k in self.bucket_keys])
        ridden = jax.tree_util.tree_leaves(self.layout.unflatten(flat))
        held = [np.asarray(comp[self.HELD_KEY][k]) for k in self.held_keys]
        return self._join(ridden, held)

    # -- in-program assembly (the gathers) -----------------------------------
    def assemble(self, comp: Dict):
        """Composite -> full param tree INSIDE the jitted step. Each bucket
        is constrained to replicated — GSPMD emits one all-gather per
        bucket, operand = this device's 1/N shard — then the flat vector
        unflattens and interleaves with the held (tp-sharded) leaves.
        The gathered tree is a temporary of the forward: XLA frees it
        after use, so HBM high-water stays ~shard-sized plus the largest
        live activations, which is the whole point."""
        repl = NamedSharding(self.mesh, P())
        buckets = [jax.lax.with_sharding_constraint(comp[self.FLAT_KEY][k],
                                                    repl)
                   for k in self.bucket_keys]
        flat = jnp.concatenate(buckets)
        ridden = jax.tree_util.tree_leaves(self.layout.unflatten(flat))
        held = [comp[self.HELD_KEY][k] for k in self.held_keys]
        return self._join(ridden, held)

    def constrain_shards(self, comp: Dict) -> Dict:
        """Constrain a composite-shaped tree (grads, updated params) back
        onto its resting shardings: buckets ``P(fsdp)`` — on grads this is
        what makes XLA combine over the fsdp groups and keep only the
        local shard — held leaves their declared specs."""
        fs = NamedSharding(self.mesh, P(self.axis))
        flat = {k: jax.lax.with_sharding_constraint(comp[self.FLAT_KEY][k],
                                                    fs)
                for k in self.bucket_keys}
        held = {k: jax.lax.with_sharding_constraint(
            comp[self.HELD_KEY][k], NamedSharding(self.mesh, s))
            for k, s in zip(self.held_keys, self.held_specs)}
        return {self.FLAT_KEY: flat, self.HELD_KEY: held}

    def composite_shardings(self) -> Dict:
        fs = NamedSharding(self.mesh, P(self.axis))
        return {self.FLAT_KEY: {k: fs for k in self.bucket_keys},
                self.HELD_KEY: {k: NamedSharding(self.mesh, s)
                                for k, s in zip(self.held_keys,
                                                self.held_specs)}}

    # -- optimizer-state canonicalization ------------------------------------
    def state_to_tree(self, opt_state):
        """Optimizer state over composite params (moment nodes ARE
        composites — optax inherits the param structure) -> canonical
        tree form for checkpoints. Padding slots hold zeros (zero grads
        keep zero moments), so the conversion is lossless."""
        return jax.tree.map(
            lambda node: (self.composite_to_tree(node)
                          if self.is_composite(node) else node),
            opt_state, is_leaf=self.is_composite)

    def tree_to_state(self, canonical, template):
        """Inverse of :meth:`state_to_tree`. ``template`` is
        ``eval_shape(tx.init, composite_params)`` — its composite nodes
        mark which positions of the canonical state are param-structured
        moments vs pass-through counters."""
        return jax.tree.map(
            lambda tmpl, node: (self.to_composite(node)
                                if self.is_composite(tmpl) else node),
            template, canonical, is_leaf=self.is_composite)

    # -- identity / accounting -----------------------------------------------
    def signature(self) -> str:
        h = hashlib.sha256(repr(
            (self.axis, self.ride_mask,
             tuple(str(s) for s in self.held_specs))).encode())
        return f"{self.layout.signature()}:{h.hexdigest()[:16]}"

    def gather_shard_bytes_per_sweep(self) -> int:
        """All-gather *operand* bytes one assembly sweep moves per device:
        each bucket's gather operand is its 1/N shard, so one forward's
        gathers read ``padded_total/N`` f32 elements. (XLA may re-gather
        in the backward instead of keeping the full params live — that
        trades one more sweep of wire for HBM high-water; the accounting
        rule therefore checks launches in whole-sweep multiples.)"""
        return self.layout.shard_size * 4

    def summary(self) -> Dict[str, Any]:
        """Declared per-step accounting for the analysis plane (hlo_lint
        cross-checks the compiled program against it) and the sharding
        snapshot/bench surface."""
        lo = self.layout
        return {
            "plane": "sharding",
            "fsdp": {
                "axis": self.axis,
                "axis_size": self.n_dev,
                "axes": {name: int(size)
                         for name, size in self.mesh.shape.items()
                         if size > 1},
                "buckets": len(lo.bucket_sizes),
                "bucket_mb": self.bucket_mb,
                "padded_total": lo.padded_total,
                "shard_size": lo.shard_size,
                "ridden_leaves": int(sum(self.ride_mask)),
                "held_leaves": len(self.held_specs),
                "gather_shard_bytes_per_sweep":
                    self.gather_shard_bytes_per_sweep(),
                "param_bytes_full": lo.total * 4,
                "param_bytes_per_device_ridden": lo.shard_size * 4,
                "layout_sig": lo.signature(),
            },
        }
