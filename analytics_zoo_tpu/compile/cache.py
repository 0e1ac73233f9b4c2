"""Shared + persistent XLA executable cache.

Keying rule: an executable is identified by the SHA-256 of its **lowered
StableHLO text** plus the physical device assignment, donation config and
jax/jaxlib versions. Lowering (tracing) is cheap — tens of milliseconds —
while XLA compilation is seconds on CPU and minutes on TPU pods, so paying
one trace to discover that a structurally identical program was already
compiled is the whole trade. Because the key is the program itself, every
structural input the ISSUE's fingerprint names (flax module tree, input
avals, mesh shape/axes, optimizer structure, clip constants, scan fuse-k)
is captured *exactly*: constants that differ change the text (miss),
values that ride as arguments — e.g. ``optax.inject_hyperparams``'d
learning rates — do not (hit).

Degradation ladder: anything that fails (lowering, AOT compile,
serialization, a deserialized executable rejecting its args) falls back to
plain ``jax.jit`` for that function, counted in ``stats.fallbacks`` —
the plane can only ever cost one failed attempt, never correctness.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..obs import trace as _trace
from .stats import CompileStats

logger = logging.getLogger("analytics_zoo_tpu")

__all__ = ["DEFAULT_CACHE_DIR", "CachedFunction", "ExecutableCache",
           "compile_stats",
           "configure_compile_cache", "get_compile_cache",
           "reset_compile_cache", "resolve_cache"]

_DISK_FORMAT = 1

# unique per-CachedFunction tokens for hit attribution: id() would be
# recycled after garbage collection, misclassifying a new call site as the
# entry's original owner and silently dropping genuine cache_hit counts
_uid_counter = itertools.count(1)


def _leaf_sig(leaf) -> Tuple:
    shape = getattr(leaf, "shape", None)
    if shape is not None and hasattr(leaf, "dtype"):
        return (tuple(shape), str(leaf.dtype),
                bool(getattr(leaf, "weak_type", False)))
    if isinstance(leaf, (int, float, bool, complex)):
        return ("py", type(leaf).__name__, leaf)
    return ("obj", type(leaf).__name__, id(leaf))


def _arg_devices(leaves) -> Tuple:
    """Physical device ids the call's committed arrays live on. StableHLO
    carries only *logical* device indices, so two single-chip meshes over
    different chips lower to identical text — the physical assignment must
    be part of the key or an executable bound to chip 0 would be handed to
    chip 1 (and rejected at call time)."""
    ids = set()
    for leaf in leaves:
        sh = getattr(leaf, "sharding", None)
        if sh is None:
            continue
        try:
            ids.update(d.id for d in sh.device_set)
        except Exception:  # noqa: BLE001 — exotic sharding: key on repr
            ids.add(repr(sh))
    if not ids:
        # uncommitted (host) args execute on the default device
        import jax
        dflt = jax.config.jax_default_device
        try:
            ids.add((dflt or jax.devices()[0]).id)
        except Exception:  # noqa: BLE001
            ids.add(-1)
    return tuple(sorted(ids, key=repr))


class _LoweredProxy:
    """Duck-types ``jax.jit(fn).lower(*args)`` for callers that do
    ``jitted.lower(*args).compile().cost_analysis()`` (bench.py
    ``_step_flops``, the estimator's analytic fuse gate) — routed through
    the cache so the probe's compile IS the training step's compile."""

    def __init__(self, cf: "CachedFunction", args):
        self._cf = cf
        self._args = args

    def compile(self):
        exe = self._cf._ensure_executable(self._args)
        if hasattr(exe, "cost_analysis"):
            return exe
        # plain-jit fallback: its own AOT path still provides cost_analysis
        return exe.lower(*self._args).compile()

    def as_text(self, *a, **k):
        return self._cf._fresh_jit().lower(*self._args).as_text(*a, **k)


class CachedFunction:
    """A jit-like callable whose executables live in a shared
    :class:`ExecutableCache`. Call it like the function; it compiles AOT
    per input signature, reusing any structurally identical executable
    already in the cache (from this or any other engine/model in the
    process, or from disk)."""

    def __init__(self, cache: "ExecutableCache", fn: Callable,
                 label: str = "", donate_argnums: Tuple[int, ...] = (),
                 extra_key: Optional[str] = None):
        self._cache = cache
        self._fn = fn
        self.label = label
        self._uid = next(_uid_counter)
        self._donate = tuple(donate_argnums)
        # caller-supplied structural salt (e.g. the engine's fsdp bucket
        # layout): identity the lowered text alone might not capture
        self._extra_key = extra_key
        self._local: Dict = {}       # sig -> executable (per-callsite fast path)
        self._keyinfo: Dict = {}     # sig -> (key, lowered, text) awaiting compile
        self._plain = None
        self._lock = threading.Lock()

    # --- jit plumbing -------------------------------------------------------
    def _fresh_jit(self):
        import jax
        return jax.jit(self._fn, donate_argnums=self._donate)

    def _plain_jit(self):
        if self._plain is None:
            self._plain = self._fresh_jit()
        return self._plain

    def _signature(self, args) -> Tuple:
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (treedef, tuple(_leaf_sig(l) for l in leaves))

    # --- public surface -----------------------------------------------------
    def cache_key(self, *args) -> Optional[str]:
        """Structural key hash for ``args`` (lowering only, no compile);
        None when lowering fails. The lowering is kept and reused by the
        next call, so probing the key costs nothing extra."""
        sig = self._signature(args)
        with self._lock:
            info = self._keyinfo.get(sig)
        if info is not None:
            return info[0]
        try:
            info = self._lower_keyed(args)
        except Exception as e:  # noqa: BLE001 — untraceable fn
            logger.debug("cache_key lowering failed (%s: %s)",
                         type(e).__name__, e)
            return None
        with self._lock:
            self._keyinfo[sig] = info
        return info[0]

    def _lower_keyed(self, args) -> Tuple:
        """``(key, lowered, text)`` of one signature's first lowering: the
        trace, the rendered StableHLO and its hash, which a warm start pays
        like a cold one. The trace is nearly all of it: 22 s of a token
        cell's 22.3 on a v5e host, its 1.9 MB of text 0.1 s (PERF.md §5)."""
        with _trace.stage("compile.lower", label=self.label) as st:
            t0 = time.perf_counter()
            lowered = self._fresh_jit().lower(*args)
            t1 = time.perf_counter()
            text = lowered.as_text()
            # on the span only: its parts (the rest of it is the key's hash)
            st.set(text_bytes=len(text), trace_s=round(t1 - t0, 3),
                   text_s=round(time.perf_counter() - t1, 3))
            key = self._cache.key_of(lowered, self._donate, args,
                                     extra_key=self._extra_key, text=text)
        self._cache.stats.record_lower(self.label, st.duration_s)
        return key, lowered, text

    def lowered_text(self, *args) -> Optional[str]:
        """Rendered StableHLO of the lowering for ``args``, reusing the
        lowering (and render) that :meth:`cache_key` produced for the
        same signature — callers that want both the key and the text
        (the golden program-contract capture) pay one lower+render."""
        sig = self._signature(args)
        with self._lock:
            info = self._keyinfo.get(sig)
        if info is None:
            if self.cache_key(*args) is None:
                return None
            with self._lock:
                info = self._keyinfo.get(sig)
        return info[2] if info is not None else None

    def _ensure_executable(self, args):
        sig = self._signature(args)
        exe = self._local.get(sig)
        if exe is None:
            with self._lock:
                info = self._keyinfo.pop(sig, None)
            exe = self._cache.obtain(self, args, sig, keyinfo=info)
            self._local[sig] = exe
        return exe

    def lower(self, *args):
        return _LoweredProxy(self, args)

    def __call__(self, *args):
        sig = self._signature(args)
        exe = self._local.get(sig)
        if exe is None:
            # a signature's first call, a set-up stage: the executable
            # found (the compile.* stages nest here) and run once; the
            # call made again then takes the cached path below, its
            # fallback included
            with _trace.stage("compile.first_call", label=self.label):
                self._ensure_executable(args)
                return self(*args)
        try:
            return exe(*args)
        except (TypeError, ValueError) as e:
            # an executable shared across objects can be stricter than jit
            # (aval weak-types, layouts, shardings of uncommitted args): a
            # mismatch must degrade, never break the training loop. Real
            # numeric/runtime errors reraise identically under plain jit.
            if exe is self._plain:
                raise
            logger.warning(
                "compile-plane executable for %r rejected its arguments "
                "(%s: %s); falling back to plain jit for this signature",
                self.label or self._fn, type(e).__name__, e)
            self._cache.stats.record_fallback(self.label)
            self._local[sig] = self._plain_jit()
            return self._local[sig](*args)


class ExecutableCache:
    """Process-wide (or private) executable store + aux result store.

    ``cache_dir`` enables persistence: executables serialize via
    ``jax.experimental.serialize_executable`` into ``<dir>/exe-<key>.pkl``
    and small auxiliary probe results (the estimator's fuse factors) into
    ``<dir>/aux-<ns>-<key>.json``. Every disk operation is best-effort.
    """

    def __init__(self, cache_dir: Optional[str] = None,
                 stats: Optional[CompileStats] = None):
        self.stats = stats or CompileStats()
        self._lock = threading.Lock()
        self._mem: Dict[str, Dict] = {}         # key -> entry
        self._inflight: Dict[str, threading.Event] = {}
        self._aux: Dict[Tuple[str, str], Any] = {}
        self._listeners: List[Callable] = []
        self.cache_dir = None
        if cache_dir:
            self.set_cache_dir(cache_dir)

    # --- configuration ------------------------------------------------------
    def set_cache_dir(self, cache_dir: Optional[str]):
        if not cache_dir:
            self.cache_dir = None
            return
        try:
            os.makedirs(cache_dir, exist_ok=True)
            self.cache_dir = cache_dir
        except OSError as e:
            logger.warning("compile cache dir %s unusable (%s); running "
                           "in-memory only", cache_dir, e)
            self.cache_dir = None

    def clear(self):
        with self._lock:
            self._mem.clear()
            self._aux.clear()

    def __len__(self):
        with self._lock:
            return len(self._mem)

    # --- events (TrialRuntime tails these into its JSONL study log) ---------
    def add_listener(self, fn: Callable[[Dict], None]) -> Callable[[], None]:
        """Subscribe to compile-plane events (dicts with an ``event`` field:
        ``compile``/``cache_hit``/``disk_hit``). Returns an unsubscribe."""
        self._listeners.append(fn)

        def _unsub():
            try:
                self._listeners.remove(fn)
            except ValueError:
                pass
        return _unsub

    def _notify(self, event: str, **fields):
        for fn in list(self._listeners):
            try:
                fn({"event": event, **fields})
            except Exception:  # noqa: BLE001 — telemetry must not break work
                logger.debug("compile-plane listener failed", exc_info=True)

    # --- keying -------------------------------------------------------------
    def key_of(self, lowered, donate_argnums, args,
               extra_key: Optional[str] = None,
               text: Optional[str] = None) -> str:
        import jax
        import jaxlib
        h = hashlib.sha256()
        # rendering StableHLO text is the expensive part of keying; callers
        # that already hold the rendered module pass it in (the lint hook
        # reuses the same text, so one render covers both)
        h.update((text if text is not None
                  else lowered.as_text()).encode())
        h.update(repr((jax.__version__, jaxlib.__version__,
                       jax.default_backend(), tuple(donate_argnums),
                       _arg_devices(jax.tree_util.tree_leaves(args)),
                       _DISK_FORMAT)).encode())
        if extra_key is not None:
            # appended only when set, so pre-existing persisted executables
            # (keyed before extra_key existed) stay valid for every caller
            # that does not use one
            h.update(repr(extra_key).encode())
        return h.hexdigest()

    # --- the wrap/obtain protocol ------------------------------------------
    def wrap(self, fn: Callable, label: str = "",
             donate_argnums: Tuple[int, ...] = (),
             extra_key: Optional[str] = None) -> CachedFunction:
        return CachedFunction(self, fn, label=label,
                              donate_argnums=donate_argnums,
                              extra_key=extra_key)

    def obtain(self, cf: CachedFunction, args, sig, keyinfo=None):
        """Resolve the executable for one call signature: shared memory
        store, then disk, then a real (timed, counted) AOT compile."""
        if keyinfo is None:
            try:
                keyinfo = cf._lower_keyed(args)
            except Exception as e:  # noqa: BLE001 — untraceable: plain jit
                logger.warning(
                    "compile plane cannot lower %r (%s: %s); using plain "
                    "jit", cf.label or cf._fn, type(e).__name__, e)
                self.stats.record_fallback(cf.label)
                return cf._plain_jit()
        key, lowered, text = keyinfo

        # the lint reads the whole text: a part of the lowering's cost
        with _trace.stage("compile.lower", label=cf.label, part="lint") as st:
            self._lint_lowering(cf, key, lowered, args, text=text)
        self.stats.record_lower(cf.label, st.duration_s)

        while True:
            with self._lock:
                entry = self._mem.get(key)
                if entry is not None:
                    break
                ev = self._inflight.get(key)
                if ev is None:
                    self._inflight[key] = threading.Event()
                    entry = None
                    break
            ev.wait()

        if entry is not None:
            if entry["origin"] != cf._uid:
                # cross-object reuse: a compile genuinely avoided
                self.stats.record_hit(cf.label, saved_s=entry["cost"])
                self._notify("cache_hit", label=cf.label,
                             key=key[:16], saved_s=round(entry["cost"], 4))
            return entry["exe"]

        try:
            entry = self._load_disk(cf, key)
            if entry is None:
                with _trace.stage("compile.xla", label=cf.label) as st:
                    exe = lowered.compile()
                dt = st.duration_s
                entry = {"exe": exe, "cost": dt, "origin": cf._uid}
                self.stats.record_compile(cf.label, dt)
                self._notify("compile", label=cf.label, key=key[:16],
                             seconds=round(dt, 4))
                self._save_disk(cf, key, exe, dt)
            with self._lock:
                self._mem[key] = entry
            return entry["exe"]
        except Exception as e:  # noqa: BLE001 — AOT path failed: plain jit
            logger.warning("AOT compile failed for %r (%s: %s); using "
                           "plain jit", cf.label or cf._fn,
                           type(e).__name__, e)
            self.stats.record_fallback(cf.label)
            return cf._plain_jit()
        finally:
            with self._lock:
                ev = self._inflight.pop(key, None)
            if ev is not None:
                ev.set()

    def _lint_lowering(self, cf: CachedFunction, key: str, lowered, args,
                       text: Optional[str] = None):
        """Analysis-plane hook: every lowering the cache resolves is linted
        before it compiles (``ZOO_HLO_LINT``: warn | strict | 0). Dedup is
        on the cache key, so re-lowerings and disk hits lint once per
        process. Only strict mode's :class:`HloLintError` may escape — any
        other failure inside the linter must not break a compile."""
        try:
            from ..analysis import hlo_lint
        except ImportError:
            return
        try:
            hlo_lint.on_lowering(cf.label, lowered,
                                 donate_argnums=cf._donate, args=args,
                                 extra_key=cf._extra_key, key=key,
                                 text=text)
        except hlo_lint.HloLintError:
            raise
        except Exception as e:  # noqa: BLE001 — lint must not break compiles
            logger.debug("hlo-lint hook failed for %r (%s: %s)",
                         cf.label, type(e).__name__, e)

    # --- disk persistence ---------------------------------------------------
    def _exe_path(self, key: str) -> Optional[str]:
        return (os.path.join(self.cache_dir, f"exe-{key}.pkl")
                if self.cache_dir else None)

    def _save_disk(self, cf: CachedFunction, key: str, exe, cost: float):
        path = self._exe_path(key)
        if path is None:
            return
        try:
            import jax
            import jaxlib
            from jax.experimental import serialize_executable as se
            with _trace.stage("compile.save", label=cf.label) as st:
                payload, in_tree, out_tree = se.serialize(exe)
                blob = pickle.dumps({
                    "format": _DISK_FORMAT, "jax": jax.__version__,
                    "jaxlib": jaxlib.__version__,
                    "backend": jax.default_backend(), "cost": float(cost),
                    "payload": payload, "in_tree": in_tree,
                    "out_tree": out_tree})
                st.set(file_bytes=len(blob))
                tmp = path + f".tmp{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
        except Exception as e:  # noqa: BLE001 — backend may not serialize
            logger.debug("executable not persisted (%s: %s)",
                         type(e).__name__, e)

    def _load_disk(self, cf: CachedFunction, key: str) -> Optional[Dict]:
        path = self._exe_path(key)
        if path is None or not os.path.exists(path):
            return None
        try:
            import jax
            from jax.experimental import serialize_executable as se
            with _trace.stage("compile.load", label=cf.label,
                              file_bytes=os.path.getsize(path)) as st:
                with open(path, "rb") as f:
                    blob = pickle.load(f)
                if (blob.get("format") != _DISK_FORMAT
                        or blob.get("jax") != jax.__version__
                        or blob.get("backend") != jax.default_backend()):
                    return None
                exe = se.deserialize_and_load(
                    blob["payload"], blob["in_tree"], blob["out_tree"])
            load_s = st.duration_s
            cost = float(blob.get("cost", 0.0))
            self.stats.record_disk_hit(cf.label, saved_s=cost - load_s)
            self.stats.record_load(cf.label, load_s)
            self._notify("disk_hit", label=cf.label, key=key[:16],
                         saved_s=round(max(cost - load_s, 0.0), 4),
                         load_s=round(load_s, 4))
            return {"exe": exe, "cost": cost, "origin": cf._uid}
        except Exception as e:  # noqa: BLE001 — stale/foreign entry
            logger.debug("disk cache entry %s unusable (%s: %s)", path,
                         type(e).__name__, e)
            return None

    # --- aux results (fuse-probe factors etc.) ------------------------------
    def _aux_path(self, namespace: str, key: str) -> Optional[str]:
        if not self.cache_dir:
            return None
        safe = hashlib.sha256(f"{namespace}:{key}".encode()).hexdigest()[:40]
        return os.path.join(self.cache_dir, f"aux-{namespace}-{safe}.json")

    def get_aux(self, namespace: str, key: str, default=None):
        with self._lock:
            if (namespace, key) in self._aux:
                return self._aux[(namespace, key)]
        path = self._aux_path(namespace, key)
        if path and os.path.exists(path):
            try:
                with open(path, encoding="utf-8") as f:
                    value = json.load(f)["value"]
                with self._lock:
                    self._aux[(namespace, key)] = value
                return value
            except (OSError, ValueError, KeyError, TypeError) as e:
                # corrupt/truncated aux file: treat as a miss (the probe
                # that produced it simply reruns)
                logger.debug("aux cache entry %s unusable (%s: %s)", path,
                             type(e).__name__, e)
        return default

    def put_aux(self, namespace: str, key: str, value):
        with self._lock:
            self._aux[(namespace, key)] = value
        path = self._aux_path(namespace, key)
        if path:
            try:
                tmp = path + f".tmp{os.getpid()}"
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump({"value": value}, f)
                os.replace(tmp, path)
            except OSError:
                pass


# --- the process-wide cache -------------------------------------------------
_global_lock = threading.Lock()
_global_cache: Optional[ExecutableCache] = None

# where the cache goes on an accelerator when nothing names a directory:
# one fixed, git-ignored path at the checkout root. JAX keys its entries on
# the directory, so a temp dir, a pid or a timestamp in it would never hit.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".zoo_compile_cache")


def _env_cache_dir() -> Optional[str]:
    """The directory the environment names: ``JAX_COMPILATION_CACHE_DIR``
    (JAX reads it itself) wins over ``ZOO_COMPILE_CACHE``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.environ.get("ZOO_COMPILE_CACHE") or None)


def get_compile_cache() -> Optional[ExecutableCache]:
    """The process-wide cache (None when ``ZOO_COMPILE_CACHE_DISABLE`` is
    set — every consumer then degrades to private ``jax.jit``)."""
    global _global_cache
    if os.environ.get("ZOO_COMPILE_CACHE_DISABLE", "") not in ("", "0"):
        return None
    with _global_lock:
        if _global_cache is None:
            _global_cache = ExecutableCache(cache_dir=_env_cache_dir())
        return _global_cache


def resolve_cache(spec) -> Optional[ExecutableCache]:
    """Normalize a ``compile_cache`` argument: None -> the process-wide
    cache, False -> disabled (plain jit), an ExecutableCache -> itself."""
    if spec is False:
        return None
    if spec is None:
        return get_compile_cache()
    return spec


def configure_compile_cache(cache_dir: Optional[str] = None
                            ) -> Optional[str]:
    """Place the persistent compile cache — the process-wide executable
    store (``exe-*.pkl``/``aux-*.json``) and JAX's own compilation cache —
    in ONE directory, and return it (None: nothing persists).

    * ``JAX_COMPILATION_CACHE_DIR`` set: that directory. JAX already reads
      the variable, so no ``jax_compilation_cache_dir`` update happens here
      — whoever set it (a machine image, a job launcher) owns the placement.
    * else ``cache_dir`` (``init_orca_context(compile_cache_dir=)``), else
      ``ZOO_COMPILE_CACHE``.
    * else, on an accelerator backend, :data:`DEFAULT_CACHE_DIR`: a compile
      there costs seconds to minutes and every fresh process would pay it
      again. On the CPU backend nothing persists by default — compiles are
      cheap and tests count them.

    Everything JAX compiles is persisted (the size and compile-time
    thresholds are zeroed): the small programs around a model add up to
    most of a cold start's program count.
    """
    import jax
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    target = env_dir or cache_dir or os.environ.get("ZOO_COMPILE_CACHE")
    if not target and jax.default_backend() != "cpu":
        target = DEFAULT_CACHE_DIR
    if not target:
        return None
    os.makedirs(target, exist_ok=True)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache = get_compile_cache()
    if cache is not None:
        cache.set_cache_dir(target)
    return target


def compile_stats(reset: bool = False) -> Dict:
    """Snapshot of the process-wide compile counters (empty dict when the
    plane is disabled). ``reset=True`` zeroes them after reading — used by
    bench.py to attribute compiles per workload."""
    cache = get_compile_cache()
    if cache is None:
        return {}
    snap = cache.stats.snapshot()
    if reset:
        cache.stats.reset()
    return snap


def reset_compile_cache():
    """Drop the process-wide cache and its stats (tests)."""
    global _global_cache
    with _global_lock:
        _global_cache = None
