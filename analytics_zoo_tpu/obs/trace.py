"""Structured spans with explicit cross-thread context propagation.

One trace id follows a request through every plane it touches:
``fit → epoch → engine.dispatch`` on the training loop thread,
``infeed.assemble / infeed.h2d`` on the pump's worker threads,
``ckpt.write`` on the checkpoint writer thread, ``supervisor.restart``
across an estimator teardown/rebuild, and in serving
``serving.request → serving.decode → serving.batch → serving.dispatch →
serving.respond`` across the aiohttp handler, the broker payload and the
batcher thread. The span catalogue lives in ``docs/observability.md``.

Propagation is a contextvar plus an explicit **thread-handoff token**
(:func:`token` / :func:`span_under` / :func:`adopt`): the infeed lanes,
the ckpt writer, the supervisor's segment threads and the serving workers
all cross thread boundaries where a contextvar alone would lose the trace.
The serving path additionally rides the token *through the broker payload
meta*, Dapper-style, so the device-dispatch span in the batcher thread
chains to the HTTP request span that enqueued it.

Liveness: a span is live when tracing is armed (``ZOO_TRACE=1`` at import
time, :func:`arm`, the :func:`tracing` context manager) **or while a JAX
profiler session is collecting in this process** (``jax.profiler
.start_trace`` … ``stop_trace``, ``fit(profile=<dir>)``, a TensorBoard
capture), which ``TraceAnnotation.is_enabled()`` answers. Every hook uses
that one predicate. While a session is collecting, a live span also enters
a ``jax.profiler.TraceAnnotation("zoo:<name>", **attrs)`` for its duration:
the same span is then in the ``.xplane.pb`` on the profiler's clock, on its
own thread's line, beside the device's ops. (:func:`record_span` is
retroactive, so it reaches the ring only.)

Cost discipline (same as ``resilience/faults.py``): the production hook is
:func:`span`, whose off path is one module-global flag check and one
``is_enabled()`` call returning a shared no-op context manager — measured
in ``bench.py --only obs`` and CI-gated below 1% of the NCF smoke step.
Finished spans land in a bounded ring (``ZOO_TRACE_RING`` spans, default
4096, oldest evicted) exported by ``obs/export.py`` as Chrome/Perfetto
``trace_event`` JSON (``ZOO_TRACE_PERFETTO=<path>`` writes it at process
exit).

Set-up stages (:func:`stage`) are the one hook that is never off: a stage
sits where work runs a handful of times a process (a context, an engine's
build, a signature's lowering, load and first call), never on a per-step
path, and always adds its **self time** to ``zoo_setup_seconds_total
{stage}``; when a span would be live it is that span as well. JAX's own
``jax.monitoring`` compile events are filed under the stage that was open
on the thread that compiled (``zoo_jax_compile_*_total{event, stage}``).
"""

from __future__ import annotations

import contextvars
import os
import random
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from jax import monitoring as _monitoring
from jax.profiler import TraceAnnotation as _Annotation

from ..common import knobs
from .registry import REGISTRY

__all__ = ["Span", "span", "span_under", "record_span", "stage",
           "current_stage", "token", "adopt",
           "current_trace_id", "arm", "disarm", "enabled", "tracing",
           "spans", "drain", "clear", "configure"]


class Span:
    """One finished span (ring-buffer record)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0", "t1",
                 "thread", "thread_name", "attrs")

    def __init__(self, name, trace_id, span_id, parent_id, t0, t1,
                 thread, thread_name, attrs):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.t1 = t1
        self.thread = thread
        self.thread_name = thread_name
        self.attrs = attrs

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "trace": self.trace_id,
                "span": self.span_id, "parent": self.parent_id,
                "t0": self.t0, "t1": self.t1, "thread": self.thread,
                "thread_name": self.thread_name, "attrs": dict(self.attrs)}


class _Ring:
    """Bounded span buffer: oldest spans are evicted, never the process."""

    def __init__(self, capacity: int):
        self._lock = threading.Lock()
        self._q: deque = deque(maxlen=max(16, int(capacity)))
        self.recorded = 0       # monotonic, survives eviction

    def append(self, s: Span):
        with self._lock:
            self._q.append(s)
            self.recorded += 1

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._q)

    def drain(self) -> List[Span]:
        with self._lock:
            out = list(self._q)
            self._q.clear()
            return out

    def clear(self):
        with self._lock:
            self._q.clear()
            self.recorded = 0

    def resize(self, capacity: int):
        with self._lock:
            self._q = deque(self._q, maxlen=max(16, int(capacity)))

    @property
    def capacity(self) -> int:
        return self._q.maxlen


RING = _Ring(knobs.get("ZOO_TRACE_RING"))

#: (trace_id, span_id) of the innermost live span on this thread/task
_ctx: contextvars.ContextVar[Optional[Tuple[str, str]]] = \
    contextvars.ContextVar("zoo_trace_ctx", default=None)

_armed = False


# ids come from a generator seeded once a process (and again in a forked
# child), not from ``uuid4``: that reads ``os.urandom``, which releases the
# GIL, and beside the infeed's assembly threads a span site then waited
# milliseconds to get it back (PERF.md, PR 33)
_ids = random.Random()
os.register_at_fork(after_in_child=_ids.seed)


def _new_id() -> str:
    return "%016x" % _ids.getrandbits(64)


# --- arming ------------------------------------------------------------------

def arm():
    global _armed
    _armed = True


def disarm():
    global _armed
    _armed = False


#: true between ``start_trace`` and ``stop_trace`` (host tracer level >= 1)
_profiling = _Annotation.is_enabled


def enabled() -> bool:
    """The one liveness predicate: armed, or a profiler session collects."""
    return _armed or _profiling()


@contextmanager
def tracing(capacity: Optional[int] = None):
    """Arm tracing for a scope (tests, the obs bench's armed leg). Both
    the armed flag AND the ring capacity are restored on exit — a scoped
    capacity=64 must not truncate a ZOO_TRACE_PERFETTO process's atexit
    export for the rest of its life."""
    global _armed
    prev_cap = None
    if capacity is not None:
        prev_cap = RING.capacity
        RING.resize(capacity)
    prev, _armed = _armed, True
    try:
        yield RING
    finally:
        _armed = prev
        if prev_cap is not None:
            RING.resize(prev_cap)


def configure(capacity: Optional[int] = None):
    if capacity is not None:
        RING.resize(capacity)


# --- the production hooks ----------------------------------------------------

class _Noop:
    """Shared do-nothing span: the disarmed return value of every hook."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def set(self, **attrs):
        return self


_NOOP = _Noop()


class _LiveSpan:
    """Live context manager: stamps ids, times the body, records on exit;
    under a profiler session it is a ``zoo:<name>`` annotation as well."""

    __slots__ = ("name", "attrs", "_parent", "trace_id", "span_id",
                 "_t0", "_reset", "_ann")

    def __init__(self, name: str, parent: Optional[Tuple[str, str]],
                 attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self._parent = parent
        self.trace_id = parent[0] if parent else _new_id()
        self.span_id = _new_id()
        self._t0 = 0.0
        self._reset = None
        self._ann = None

    def __enter__(self):
        self._reset = _ctx.set((self.trace_id, self.span_id))
        if _profiling():
            self._ann = _Annotation("zoo:" + self.name, **self.attrs)
            self._ann.__enter__()
        # perf_counter, not time.time(): spans are intervals and the
        # Perfetto export renders t0 relative to the run's first span —
        # an NTP step mid-run must not produce negative durations or
        # scramble the step timeline. perf_counter is process-wide
        # comparable across threads, so cross-thread handoffs line up.
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._reset is not None:
            _ctx.reset(self._reset)
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        t = threading.current_thread()
        RING.append(Span(self.name, self.trace_id, self.span_id,
                         self._parent[1] if self._parent else None,
                         self._t0, t1, t.ident or 0, t.name, self.attrs))
        return False

    def set(self, **attrs):
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self


def span(name: str, **attrs):
    """Open a span under the current context (or start a new trace at a
    root site). Off: one flag check and one ``is_enabled()``, shared no-op
    back."""
    if not enabled():
        return _NOOP
    return _LiveSpan(name, _ctx.get(), attrs)


def span_under(tok: Optional[str], name: str, **attrs):
    """Open a span parented at an explicit handoff ``tok`` (from
    :func:`token`, captured on the originating thread) — the cross-thread
    form of :func:`span`. A ``None`` token falls back to the local
    context (so a disarmed-at-capture pump still nests sanely)."""
    if not enabled():
        return _NOOP
    return _LiveSpan(name, _parse(tok) or _ctx.get(), attrs)


def record_span(name: str, t0: float, t1: float,
                parent: Optional[str] = None, **attrs):
    """Record an already-timed section retroactively (used where the
    parent token is only known after the work ran, e.g. the serving
    decode stage discovering the request's token inside the payload).
    ``t0``/``t1`` must come from ``time.perf_counter()`` — the span
    timebase all live spans use. A past interval cannot be written into a
    profiler session, so it reaches the ring only."""
    if not enabled():
        return
    p = _parse(parent) or _ctx.get()
    t = threading.current_thread()
    RING.append(Span(name, p[0] if p else _new_id(), _new_id(),
                     p[1] if p else None, t0, t1, t.ident or 0, t.name,
                     attrs))


# --- set-up stages ------------------------------------------------------------

_SETUP_SECONDS = REGISTRY.counter(
    "zoo_setup_seconds_total",
    "Self seconds of each set-up stage (trace.stage): its duration less the "
    "stages opened inside it on the same thread, so the stages add up to "
    "their union and nothing is counted twice. Counted armed or not.",
    ("stage",))
_SETUP_EVENTS = REGISTRY.counter(
    "zoo_setup_events_total", "Times each set-up stage ran.", ("stage",))

# innermost open stage of each thread (``.top``): a worker thread's stages
# are no part of the stage that happens to be open on the thread beside it
_stages = threading.local()


class _Stage:
    """One set-up stage: always a pair of clock reads and two counter adds;
    a span of the same name as well when spans are live."""

    __slots__ = ("name", "attrs", "duration_s", "_outer", "_inner_s",
                 "_span", "_t0")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.duration_s = 0.0       # whole, set at exit: the site's own stats
        self._outer = None
        self._inner_s = 0.0
        self._span = None
        self._t0 = 0.0

    def __enter__(self):
        self._outer = getattr(_stages, "top", None)
        _stages.top = self
        if enabled():
            self._span = _LiveSpan(self.name, _ctx.get(), self.attrs)
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.duration_s = time.perf_counter() - self._t0
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        _stages.top = self._outer
        if self._outer is not None:
            self._outer._inner_s += self.duration_s
        _SETUP_SECONDS.labels(stage=self.name).inc(
            self.duration_s - self._inner_s)
        _SETUP_EVENTS.labels(stage=self.name).inc()
        return False

    def set(self, **attrs):
        """Attributes known only once the work ran (a program's bytes)."""
        if self._span is not None:
            self._span.set(**attrs)
        return self


def stage(name: str, **attrs):
    """Open a set-up stage. Unlike :func:`span` it is never a no-op: set-up
    is what nobody arms tracing for, and the benchmark's ``setup_s`` is
    split by these counters. Only for sites that run a few times a process."""
    return _Stage(name, attrs)


def current_stage() -> str:
    """Name of the innermost stage open on the calling thread, or ``none``."""
    top = getattr(_stages, "top", None)
    return top.name if top is not None else "none"


# --- JAX's own compile events, by stage -------------------------------------

_JAX_COMPILE_SECONDS = REGISTRY.counter(
    "zoo_jax_compile_seconds_total",
    "Seconds of jax.monitoring's compile events, every program JAX traces, "
    "lowers, compiles or fetches (eager operations and plain jax.jit "
    "included), by event and by the set-up stage open on the calling thread "
    "(none outside one). In JAX 0.9.0 backend_compile is the time around "
    "compile_or_get_cached (pxla.py): it COVERS a persistent-cache hit's "
    "retrieval, the key's hash and a miss's write, so cache_retrieval is a "
    "part of it and the two are not to be added.",
    ("event", "stage"))
_JAX_COMPILE_EVENTS = REGISTRY.counter(
    "zoo_jax_compile_events_total",
    "Count of the same events, and of the persistent cache's hits and "
    "misses (a miss is counted where the entry is written).",
    ("event", "stage"))

_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "to_mlir",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}


def _on_jax_duration(event: str, duration_secs: float, **_kw):
    short = _JAX_EVENTS.get(event)
    if short is not None:
        where = current_stage()
        _JAX_COMPILE_SECONDS.labels(event=short, stage=where).inc(
            duration_secs)
        _JAX_COMPILE_EVENTS.labels(event=short, stage=where).inc()


def _on_jax_event(event: str, **_kw):
    short = _JAX_EVENTS.get(event)
    if short is not None:
        _JAX_COMPILE_EVENTS.labels(event=short, stage=current_stage()).inc()


# JAX calls these only where it traces, compiles or looks a program up,
# never where it dispatches one: a window that compiles nothing never
# reaches them
_monitoring.register_event_duration_secs_listener(_on_jax_duration)
_monitoring.register_event_listener(_on_jax_event)


# --- handoff tokens ----------------------------------------------------------

def token() -> Optional[str]:
    """The current span context as a portable string token (``trace:span``)
    for thread/process/payload handoff; None when off or outside any
    span."""
    if not enabled():
        return None
    cur = _ctx.get()
    return f"{cur[0]}:{cur[1]}" if cur else None


def _parse(tok: Optional[str]) -> Optional[Tuple[str, str]]:
    if not tok or not isinstance(tok, str) or ":" not in tok:
        return None
    trace_id, _, span_id = tok.partition(":")
    return (trace_id, span_id) if trace_id and span_id else None


@contextmanager
def adopt(tok: Optional[str]):
    """Make ``tok`` the ambient context for a scope on another thread —
    spans opened inside nest under the originating span."""
    parsed = _parse(tok)
    if parsed is None:
        yield
        return
    reset = _ctx.set(parsed)
    try:
        yield
    finally:
        _ctx.reset(reset)


def current_trace_id() -> Optional[str]:
    cur = _ctx.get()
    return cur[0] if cur else None


# --- ring access -------------------------------------------------------------

def spans() -> List[Span]:
    return RING.spans()


def drain() -> List[Span]:
    return RING.drain()


def clear():
    RING.clear()


# whole-process runs arm at import, like ZOO_FAULTS: spans flow from the
# first dispatch on, and ZOO_TRACE_PERFETTO (handled in obs/export.py)
# writes the timeline at exit
if knobs.get("ZOO_TRACE"):
    arm()
