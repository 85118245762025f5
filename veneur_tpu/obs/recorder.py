"""StageRecorder: lock-cheap per-interval stage tracing.

One recorder lives for exactly one flush interval. Every instrumented
region records ``(path, t0_ns, t1_ns, attrs)`` with monotonic-ns
stamps; the write side is a ``collections.deque`` append (GIL-atomic,
no lock — the same single-writer-then-merge shape as the ingest
lanes), and the merge into a stage tree happens once, at interval end
(:meth:`StageRecorder.finish`).

Stage nesting is carried by the recording thread's own open-stage
stack (``threading.local``): ``stage("fetch")`` entered while
``stage("histograms")`` is open under ``stage("store")`` records as
``store.histograms.fetch``. Threads that aren't part of the flusher's
call tree (sink POST threads, the off-path forward) record absolute
paths with :meth:`StageRecorder.record_abs`.

Every event carries the thread that recorded it, and that is what the
interval's one rule for unnamed time reads (:func:`unstaged_ns`): of
the recorder owner's (the flusher's) on-path stages, those with no
child stage on the same thread are its *leaves*, and what of the
interval's wall the leaves' union leaves out is ``unstaged_ns``. A
stage another thread recorded never covers the flusher's time: the
flusher's wait for that thread is a leaf of its own, which says what
it waits for.

Two clocks meet here. Stages are stamped with ``time.monotonic_ns``;
the profiler's host scopes (``TraceAnnotation``, the ``veneur.*``
scopes of a ``/debug/xprof`` capture) with the wall clock,
``time.time_ns`` (CLOCK_REALTIME, which TraceMe reads on Linux). The
recorder reads the two back to back at its start, and every entry
publishes the pair's wall half as ``wall_start_ns``: a stage lies on a
capture at ``wall_start_ns + start_ns``. ``stage(..., scope=True)``
opens the stage's host scope, named ``veneur.<stage path>``, with it.

The flusher parks the interval's recorder in a thread-local slot
(:func:`activate`) so deep call sites — the store's generation swap,
each digest group's compute/fetch, the breaker ladder's rung choice —
can attach stages and notes without threading a parameter through
every signature. When observability is off (``obs_enabled: false``)
the slot is empty and every hook costs one thread-local read.
"""

from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional

_NS = 1_000_000_000

_tls = threading.local()
_NO_STAGE = nullcontext()


def current() -> Optional["StageRecorder"]:
    """The interval recorder active on this thread tree, or None."""
    return getattr(_tls, "recorder", None)


@contextmanager
def activate(rec: Optional["StageRecorder"]):
    """Park ``rec`` as the current recorder for this thread (the
    flusher wraps the whole interval in this). None deactivates."""
    prev = getattr(_tls, "recorder", None)
    _tls.recorder = rec
    try:
        yield rec
    finally:
        _tls.recorder = prev


def maybe_stage(name: str, scope: bool = False, **attrs):
    """``rec.stage(name)`` against the current recorder, or a no-op
    when observability is off — the one-line hook for deep call
    sites. ``scope=True`` opens the stage's host scope with it (a
    *work* leaf: what the host does there; a *wait* leaf opens none)."""
    rec = current()
    if rec is None:
        return _NO_STAGE
    return _Stage(rec, name, scope, attrs)


def record_child(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """A stretch already clocked by the caller, recorded as a child of
    the innermost open stage of the current recorder (a lock's wait,
    clocked around a ``with lock:`` that a stage cannot split); no-op
    without one."""
    rec = current()
    if rec is not None:
        rec.record_child(name, t0_ns, t1_ns, **attrs)


def note(**attrs) -> None:
    """Attach attrs to the innermost open stage of the current
    recorder (e.g. which breaker rung a flush ran); no-op without
    one."""
    rec = current()
    if rec is not None:
        rec.note(**attrs)


class _Frame:
    __slots__ = ("name", "path", "attrs")

    def __init__(self, name: str, path: str, attrs: dict):
        self.name = name
        self.path = path
        self.attrs = attrs


class _Stage:
    """One stage's ``with``. Its two clock reads are the first and the
    last thing it does, so what the recording costs (the stack, the
    host scope, the event) lies inside the stage and never between two
    leaves, where it would read as unnamed time."""

    __slots__ = ("_rec", "_name", "_scope", "_attrs", "_t0", "_frame",
                 "_annotation")

    def __init__(self, rec: "StageRecorder", name: str, scope: bool,
                 attrs: dict):
        self._rec = rec
        self._name = name
        self._scope = scope
        self._attrs = attrs
        self._annotation = None

    def __enter__(self) -> _Frame:
        rec = self._rec
        self._t0 = rec._clock()
        stack = rec._stack()
        path = stack[-1].path + "." + self._name if stack else self._name
        self._frame = frame = _Frame(self._name, path, self._attrs)
        stack.append(frame)
        if self._scope:
            from veneur_tpu.obs.kernels import host_scope

            self._annotation = host_scope(path)
            self._annotation.__enter__()
        return frame

    def __exit__(self, *exc) -> None:
        rec, frame = self._rec, self._frame
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        rec._stack().pop()
        thread = threading.current_thread()
        rec._events.append((frame.path, self._t0, rec._clock(),
                            frame.attrs, thread.ident, thread.name))


class StageRecorder:
    """Begin/end stage tracer for ONE flush interval."""

    def __init__(self, clock_ns=time.monotonic_ns):
        self._clock = clock_ns
        # (path, t0_ns, t1_ns, attrs, thread ident, thread name) —
        # append is GIL-atomic
        self._events: "collections.deque" = collections.deque()
        self._stacks = threading.local()
        # the thread whose time the interval accounts (the flusher's)
        self._owner = threading.current_thread()
        # the anchor pair, back to back: the stages' clock and the
        # profiler's (module docstring)
        self.t0_ns = clock_ns()
        self.wall_start_ns = time.time_ns()
        self.wall_start = self.wall_start_ns / _NS
        self.entry: Optional[dict] = None  # set by finish()
        # fleet trace plane (obs/tracectx.py): the distributed-trace
        # identity this interval's stage tree publishes under. Zero =
        # unstitched (a bare recorder outside the hop contract).
        self.trace_id = 0
        self.span_id = 0
        self.parent_span_id = 0
        self.hop = ""

    def adopt_trace(self, trace_id: int, span_id: int = 0,
                    parent_id: int = 0, hop: str = "") -> None:
        """Join this recorder's stage tree into a distributed trace:
        the published entry gains ``trace_id``/``span_id``/
        ``parent_span_id``/``hop``, which is what ``GET /debug/trace``
        stitches on. The flusher adopts its flush span's ids; a
        receiving hop adopts the ids off the ``X-Veneur-Trace``
        header."""
        from veneur_tpu.obs import tracectx

        self.trace_id = int(trace_id)
        self.span_id = int(span_id) or tracectx.new_span_id()
        self.parent_span_id = int(parent_id)
        self.hop = hop

    # -- recording ---------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._stacks, "stack", None)
        if st is None:
            st = self._stacks.stack = []
        return st

    def stage(self, name: str, scope: bool = False, **attrs) -> _Stage:
        """Record one nested stage around the with-body; ``scope=True``
        also opens its host scope (``veneur.<path>``) inside it."""
        return _Stage(self, name, scope, attrs)

    def _append(self, path: str, t0: int, t1: int, attrs: dict) -> None:
        thread = threading.current_thread()
        self._events.append((path, t0, t1, attrs, thread.ident,
                             thread.name))

    def note(self, **attrs) -> None:
        stack = self._stack()
        if stack:
            stack[-1].attrs.update(attrs)

    def record_child(self, name: str, t0_ns: int, t1_ns: int,
                     **attrs) -> None:
        """Record a stretch the caller clocked as a child of this
        thread's innermost open stage (at the root without one)."""
        stack = self._stack()
        self._append(stack[-1].path + "." + name if stack else name,
                     t0_ns, t1_ns, attrs)

    def record_abs(self, path: str, t0_ns: int, t1_ns: int,
                   **attrs) -> None:
        """Record a stage at an absolute dotted path — for threads
        outside the flusher's stage stack (per-sink POSTs)."""
        self._append(path, t0_ns, t1_ns, attrs)

    def record_late(self, path: str, t0_ns: int, t1_ns: int,
                    **attrs) -> None:
        """Record a stage AFTER the interval published (the off-path
        forward): the entry already in the ring gains the stage in
        place, so ``/debug/flush-timeline`` shows it once it lands."""
        entry = self.entry
        if entry is None:
            # finish() has not run yet (a fast forward): land in the
            # normal event stream, keeping the off-path marker so
            # coverage accounting excludes it either way
            self._append(path, t0_ns, t1_ns, dict(attrs, off_path=True))
            return
        stage = dict(attrs)
        stage["name"] = path
        stage["start_ns"] = max(0, t0_ns - self.t0_ns)
        stage["duration_ns"] = max(0, t1_ns - t0_ns)
        stage["off_path"] = True
        stage.setdefault("thread", threading.current_thread().name)
        entry["stages"].append(stage)
        entry["tree"].append(dict(stage, children=[]))

    # -- merge -------------------------------------------------------------

    def finish(self, total_ns: Optional[int] = None) -> dict:
        """Merge the recorded events into the interval record: a flat
        ``stages`` list plus a nested ``tree``, both ordered by start,
        each stage with the name of the ``thread`` that recorded it.
        ``unstaged_ns`` is what of ``total_duration_ns`` the owner's
        leaves leave out (:func:`unstaged_ns`), and ``coverage_ratio``
        is ``1 - unstaged_ns / total_duration_ns``; off-path stages
        (the forward, the cumulative ingest and import sums) are in
        neither."""
        end_ns = self._clock()
        if total_ns is None:
            total_ns = end_ns - self.t0_ns
        # drain destructively: a late sink/forward thread may still be
        # appending while this merge runs (deque ops are GIL-atomic;
        # iterating a mutating deque raises) — anything appended after
        # this drain is swept up by the straggler pass below once
        # ``self.entry`` is published
        stages: List[dict] = []
        owned: List[tuple] = []
        for path, t0, t1, attrs, ident, thread in _drain(self._events):
            stage = dict(attrs)
            stage["name"] = path
            stage["start_ns"] = max(0, t0 - self.t0_ns)
            stage["duration_ns"] = max(0, t1 - t0)
            stage["thread"] = thread
            stages.append(stage)
            if ident == self._owner.ident and not stage.get("off_path"):
                owned.append((stage["start_ns"],
                              stage["start_ns"] + stage["duration_ns"],
                              path))
        stages.sort(key=lambda s: (s["start_ns"], s["name"]))
        unstaged = unstaged_ns(owned, int(total_ns))
        entry = {
            "wall_start": self.wall_start,
            "wall_start_ns": self.wall_start_ns,
            "wall_end": self.wall_start + (end_ns - self.t0_ns) / _NS,
            "total_duration_ns": int(total_ns),
            "unstaged_ns": unstaged,
            "coverage_ratio": round(1 - unstaged / total_ns, 4)
            if total_ns else 0.0,
            "thread": self._owner.name,
            "stages": stages,
            "tree": _build_tree(stages),
        }
        if self.trace_id:
            entry["trace_id"] = self.trace_id
            entry["span_id"] = self.span_id
            entry["parent_span_id"] = self.parent_span_id
            entry["hop"] = self.hop
        self.entry = entry
        # straggler pass: events recorded between the drain above and
        # the entry publication (record_late saw entry None and fell
        # back to the stream) land in the published entry after all —
        # nothing recorded is ever silently lost
        for path, t0, t1, attrs, _ident, thread in _drain(self._events):
            self.record_late(path, t0, t1, **dict(attrs, thread=thread))
        return entry


def unstaged_ns(owned: List[tuple], total_ns: int) -> int:
    """The interval's one rule for unnamed time. ``owned``: the
    ``(start_ns, end_ns, path)`` of the owner thread's on-path stages.
    Its *leaves* are those with no child stage (a longer dotted path
    under it, inside its span) among them; the result is
    ``total_ns`` less the length of the leaves' union within
    ``[0, total_ns]``. So a wrapper covers nothing of its own, and a
    parent's time outside its children counts as unstaged."""
    leaves = []
    for start, end, path in owned:
        prefix = path + "."
        if not any(s >= start and e <= end and p.startswith(prefix)
                   for s, e, p in owned):
            leaves.append((max(0, start), min(end, total_ns)))
    covered, edge = 0, 0
    for start, end in sorted(leaves):
        start = max(start, edge)
        if end > start:
            covered += end - start
            edge = end
    return max(0, total_ns - covered)


def _drain(dq: "collections.deque") -> list:
    out = []
    while True:
        try:
            out.append(dq.popleft())
        except IndexError:
            return out


def _build_tree(stages: List[dict]) -> List[dict]:
    """Nest the flat dotted-path stage list: ``store.histograms.fetch``
    hangs under ``store.histograms`` under ``store``. A child whose
    parent path was never recorded attaches at the root (keeps the
    tree total — nothing is dropped)."""
    roots: List[dict] = []
    by_path: Dict[str, dict] = {}
    for stage in stages:
        node = dict(stage, children=[])
        path = stage["name"]
        # the LAST recorded node wins the path slot for parenting;
        # repeated stages (several sinks, retried groups) all stay in
        # the tree, later ones just can't adopt children
        by_path[path] = node
        parent = None
        if "." in path:
            parent = by_path.get(path.rsplit(".", 1)[0])
        if parent is not None:
            parent["children"].append(node)
        else:
            roots.append(node)
    return roots
