"""Share of the traced ``fit`` call's duration that lies under no leaf span of
the training loop's own thread: above a few per cent the program's spans
(``analytics_zoo_tpu/obs/trace.py``) have a hole there.

A span is live while a profiler session collects, so after a traced run the
ring holds the fit calls the session wrapped (the harness wraps a short call
for the profiler to settle, then the call that is read) and nothing later. The
call that is read is the **last span named ``fit``**; its descendants are
found by ``trace_id`` and ``parent_id``. A leaf is a descendant on the root's
thread with no child on that thread (``fit.prepare``, ``infeed.first_batch``,
``infeed.wait``, ``engine.dispatch``, ``epoch.sync``): an ``epoch`` covers
nothing by itself, so the time inside it between its leaves counts as
unattributed. Where the ring holds no ``fit`` span (a program whose spans are
not live under a profiler session, as before PR 33) there is nothing to read.

``ctx["spans"]``, where a test gives it, stands in for the program's ring.
"""


def call(ctx):
    """``(fit span, its descendants)`` of the last ``fit`` call, or None."""
    spans = ctx.get("spans")
    if spans is None:
        from analytics_zoo_tpu.obs import trace
        spans = trace.spans()
    root = next((s for s in reversed(spans) if s.name == "fit"), None)
    if root is None:
        return None
    children = {}
    for s in spans:
        if s.trace_id == root.trace_id and s.parent_id is not None:
            children.setdefault(s.parent_id, []).append(s)
    below, todo = [], [root]
    while todo:
        kids = children.get(todo.pop().span_id, [])
        below.extend(kids)
        todo.extend(kids)
    return root, below


def read(ctx):
    found = call(ctx)
    if found is None:
        return None
    root, below = found
    if root.t1 <= root.t0:
        return None
    own = [s for s in below if s.thread == root.thread]
    parents = {s.parent_id for s in own}
    covered, edge = 0.0, root.t0
    for s in sorted((s for s in own if s.span_id not in parents),
                    key=lambda s: s.t0):
        lo, hi = max(s.t0, edge), min(s.t1, root.t1)
        if hi > lo:
            covered += hi - lo
            edge = hi
    return 100.0 * (1.0 - covered / (root.t1 - root.t0))
