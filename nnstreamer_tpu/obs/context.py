"""Request-scoped tracing: trace contexts, spans, Perfetto export (L7).

One request through the full stack — ``QueryClient.request()`` → fabric
router (retries, hedges) → replica query server → serving batcher →
fused device segment — is ONE trace: a root span minted where the
request enters, child spans per attempt, and span *links* where
fan-in makes strict parentage a lie (a coalesced batch serves N
requests: the batch span links to every request span instead of
pretending one of them is its parent).

Wire propagation: a :class:`TraceContext` rides buffer meta as
``meta["trace"] = {"trace_id", "span_id"}`` — the query protocol's DATA
frames already carry meta as JSON (core/serialize.py), so the context
crosses every process boundary the tensors do, for free.

Cost discipline (the same contract as ``utils/trace.ACTIVE``): the hot
paths check ONE module-global, :data:`TRACING`, and do nothing else when
it is False. Spans use ``time.monotonic()`` so fabric/scheduler/fusion
timestamps (already monotonic) pass straight through.

Program spans (:func:`span`) are the exception to the gate: the serving
plane's pass-level spans are recorded whenever a ``DecodeScheduler``
runs, as ``ServingMetrics`` and the flight recorder are, because their
rate is the device's (a pass is tens of milliseconds) and because a
profiler session is started from outside the program and cannot flip a
flag in it. Each enters a ``jax.profiler.TraceAnnotation("nns:<name>")``,
so it lies in the profiler's host plane on the device trace's time base
whenever a session runs, and lands in the same bounded ring on
``time.monotonic``. Budget: a few microseconds a span with no session
(``tests/test_serving_spans.py`` holds it), no id string, no flight
event.

The compile account (:func:`compile_account`) is the program's own record
of what jax spent before a program could run: one ``jax.monitoring``
listener, registered with the first program span (or by whoever reads the
account first), hears every trace, lowering and backend-compile duration
and every persistent-cache hit and miss, charges it to the innermost
program span open on the thread that paid it, and keeps it in a bounded
list with running totals. A backend compile that followed a cache hit on
its thread is a *load*, any other is *fresh*. The spans of start-up
(``setup.*``, ``program.first_call``) are kept in a list of their own
(:func:`startup_spans`), which a window's pass spans cannot push out.

Export: :func:`export_chrome_trace` writes chrome://tracing / Perfetto
JSON (``X`` complete events); trace_id/span_id/parent_span_id/links ride
each event's ``args`` so tooling (and tests) can reconstruct the tree.
That export is on this process's monotonic clock; what shares a time base
with the device XPlanes of ``utils.trace.jax_trace`` is the program spans'
annotations, inside the profiler's own trace.
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..analysis import sanitizer as _san
from . import flight

# module-global fast path: instrumented call sites check this and only
# this when tracing is off (tests/test_serving_spans.py: its spans stay gated)
TRACING = False

# per-process id prefix so traces from different processes (a remote
# replica, a subprocess service) can never collide
_uniq = f"{os.getpid():x}{int.from_bytes(os.urandom(3), 'big'):06x}"
_trace_seq = itertools.count(1)
_span_seq = itertools.count(1)

# finished spans, bounded (deque append/iteration is thread-safe under
# the GIL; oldest spans fall off — export is for recent activity, the
# flight recorder keeps the tail even when tracing is later disabled).
# Sized for the always-on program spans: a steady pass leaves six, nine with
# a prefill launch, one more a retirement and five a finished request. The
# benchmark's cells leave 2,200–25,100 a run (PERF.md section 6, PR 36); a
# 48 s window kept full at the fastest full-batch pass measured (7.5 ms,
# eight spans with a launch every seventh pass) would leave 51,000, and
# passes of 3.6 ms (one read of a 1.3B model's weights a step) fill the ring
# in 34 s. Start-up's spans do not depend on it (``_startup``)
MAX_FINISHED = 65536
_finished: "collections.deque[Span]" = collections.deque(maxlen=MAX_FINISHED)
_finished_seq = itertools.count(1)
# the published total must never go BACKWARDS (Prometheus reads it as a
# counter; a regression renders as a reset → phantom rate spike), so the
# take-a-seq + publish pair is serialized by a tiny lock
_count_lock = threading.Lock()
_finished_total = 0                  # guarded-by: _count_lock (reads racy-ok)
_t0 = time.monotonic()


def _new_trace_id() -> str:
    return f"{_uniq}-{next(_trace_seq):x}"


def _new_span_id() -> str:
    return f"s{next(_span_seq):x}"


class TraceContext:
    """The propagatable half of a span: (trace_id, span_id)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def to_meta(self) -> dict:
        """Wire form for ``buffer.meta['trace']`` (plain JSON-able dict)."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @staticmethod
    def from_meta(obj) -> Optional["TraceContext"]:
        """Parse a wire/meta value back into a context; None for anything
        that is not one (meta is client-supplied data — never raise)."""
        if isinstance(obj, TraceContext):
            return obj
        if isinstance(obj, dict):
            t, s = obj.get("trace_id"), obj.get("span_id")
            if isinstance(t, str) and isinstance(s, str) and t and s:
                return TraceContext(t, s)
        return None

    def __repr__(self):
        return f"TraceContext({self.trace_id}/{self.span_id})"


class Span:
    """One timed operation inside a trace. Created via
    :func:`start_span` (live, call :meth:`end`) or :func:`record_span`
    (post-hoc, already finished)."""

    __slots__ = ("name", "kind", "trace_id", "span_id", "parent_id",
                 "start_s", "dur_s", "status", "attrs", "links", "tid",
                 "_done")

    def __init__(self, name: str, kind: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], start_s: float,
                 attrs: Optional[dict],
                 links: Sequence[Tuple[str, str]]):
        self.name = name
        self.kind = kind
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_s = start_s
        self.dur_s = 0.0
        self.status = "open"
        self.attrs = attrs or {}
        self.links: List[Tuple[str, str]] = list(links)
        self.tid = threading.get_ident()
        self._done = False

    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id)

    def add_link(self, ctx: Optional[TraceContext]) -> None:
        if ctx is not None:
            self.links.append((ctx.trace_id, ctx.span_id))

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def end(self, status: str = "ok") -> TraceContext:
        """Finish the span (idempotent) and record it."""
        if not self._done:
            self._done = True
            self.dur_s = max(0.0, time.monotonic() - self.start_s)
            self.status = status
            _record_finished(self)
        return self.context()

    def to_dict(self) -> dict:
        return {
            "name": self.name, "kind": self.kind,
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_span_id": self.parent_id,
            "start_s": self.start_s, "dur_s": self.dur_s,
            "status": self.status, "attrs": dict(self.attrs),
            "links": [{"trace_id": t, "span_id": s}
                      for t, s in self.links],
        }

    def __repr__(self):
        return (f"Span<{self.kind}:{self.name} {self.trace_id}/"
                f"{self.span_id} {self.status}>")


def _append_finished(span) -> None:
    """Into the ring, and into the published total."""
    global _finished_total
    with _count_lock:
        _finished_total = next(_finished_seq)
    _finished.append(span)


def _record_finished(span: Span) -> None:
    if _san.LEAK:
        # both terminal paths (Span.end and record_span's post-hoc
        # emission) funnel here: the span leaves the leak ledger
        _san.note_release("span", span.span_id)
    _append_finished(span)
    # spans land in the always-on flight recorder too, so a postmortem
    # dump shows the last requests even after tracing is switched off
    flight.record("span", f"{span.kind}:{span.name}",
                  {"trace": span.trace_id, "span": span.span_id,
                   "status": span.status,
                   "dur_ms": round(span.dur_s * 1e3, 3)})


def _coerce_parent(parent) -> Optional[TraceContext]:
    if parent is None:
        return None
    if isinstance(parent, (Span, ProgramSpan)):
        return parent.context()
    return TraceContext.from_meta(parent)


def start_span(name: str, kind: str = "span", parent=None,
               links: Sequence[TraceContext] = (),
               attrs: Optional[dict] = None,
               trace_id: Optional[str] = None) -> Span:
    """Open a live span. ``parent`` may be a :class:`TraceContext`, a
    :class:`Span`, or a meta dict; no parent (and no ``trace_id``) mints
    a fresh trace."""
    pctx = _coerce_parent(parent)
    tid = trace_id or (pctx.trace_id if pctx is not None
                       else _new_trace_id())
    span = Span(name, kind, tid, _new_span_id(),
                pctx.span_id if pctx is not None else None,
                time.monotonic(), attrs,
                [(c.trace_id, c.span_id) for c in links if c is not None])
    if _san.LEAK:
        _san.note_acquire("span", span.span_id,
                          detail=f"{kind}:{name}")
    return span


def record_span(name: str, kind: str = "span", parent=None,
                trace_id: Optional[str] = None,
                links: Sequence[TraceContext] = (),
                attrs: Optional[dict] = None,
                start_s: Optional[float] = None, dur_s: float = 0.0,
                status: str = "ok") -> TraceContext:
    """One-shot emission of an already-finished span (batch/fused
    dispatch paths measure first, report after). Returns the new span's
    context."""
    span = start_span(name, kind=kind, parent=parent, links=links,
                      attrs=attrs, trace_id=trace_id)
    if start_s is not None:
        span.start_s = start_s
    span._done = True
    span.dur_s = max(0.0, dur_s)
    span.status = status
    _record_finished(span)
    return span.context()


# -- program spans -----------------------------------------------------------

# the name a program span has in the profiler's host plane: the prefix
# tells the program's spans from anyone else's annotations in a trace
ANNOTATION_PREFIX = "nns:"
_annotation_names: Dict[str, str] = {}   # span name -> prefixed, built once
_TraceAnnotation = None                  # jax.profiler's, loaded at first use
# per thread. .top: innermost open span; for the compile account,
# .cache_hit: a persistent-cache hit was heard since this thread's last
# backend compile, .heard: its latest durations that none has held yet, and
# .compiled: (backend compiles, their seconds) this thread has paid
_open = threading.local()

# spans of start-up, by name: also kept in ``_startup``, out of the ring's
# reach (an engine leaves two ``setup.*`` and one ``program.first_call`` a
# program; the bound is for a process that builds engines all day)
STARTUP_NAMES = ("setup.", "program.first_call")
MAX_STARTUP = 512
_startup: "collections.deque[ProgramSpan]" = collections.deque(
    maxlen=MAX_STARTUP)


class ProgramSpan:
    """What :func:`span` returns: the context manager and the record in one
    object, so a span costs one allocation. It reads like a :class:`Span`
    (``trace_id``, ``span_id``, ``parent_id``, ``to_dict()``) for the
    exporters, but keeps its parent as an object and makes the id strings
    only when an exporter asks."""

    __slots__ = ("name", "parent", "start_s", "dur_s", "status", "attrs",
                 "tid", "_seq", "_prev", "_annotation")
    kind = "program"
    links = ()

    def __init__(self, name: str, parent, attrs: dict):
        self.name = name
        self.parent = parent
        self.start_s = self.dur_s = 0.0
        self.status = "ok"
        self.attrs = attrs
        self.tid = threading.get_ident()
        self._seq = next(_span_seq)
        self._prev = self._annotation = None

    def __enter__(self) -> "ProgramSpan":
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation as _TraceAnnotation
            _listen_to_jax()
        self._prev = getattr(_open, "top", None)
        if self.parent is None:
            self.parent = self._prev
        _open.top = self
        label = _annotation_names.get(self.name)
        if label is None:
            label = _annotation_names[self.name] = \
                ANNOTATION_PREFIX + self.name
        self._annotation = _TraceAnnotation(label)
        self._annotation.__enter__()
        self.start_s = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur_s = time.monotonic() - self.start_s
        self._annotation.__exit__(exc_type, exc, tb)
        _open.top = self._prev
        self._prev = self._annotation = None
        if exc_type is not None:
            self.status = "error:" + exc_type.__name__
        # the ring only: no leak ledger (a ``with`` cannot leak) and no
        # flight event (a pass's spans would flush that ring's 512 events
        # in seconds)
        _append_finished(self)
        if self.name.startswith(STARTUP_NAMES):
            _startup.append(self)
        return False

    def record(self, start_s: float, end_s: float) -> "ProgramSpan":
        """Write the span post hoc from two ``time.monotonic`` stamps
        (a request's phases are known only when it is done). It is not in
        the profiler's trace: an annotation cannot be made in the past."""
        self.start_s = start_s
        self.dur_s = max(0.0, end_s - start_s)
        _append_finished(self)
        return self

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s

    @property
    def span_id(self) -> str:
        return f"p{self._seq:x}"

    @property
    def parent_id(self) -> Optional[str]:
        return None if self.parent is None else self.parent.span_id

    @property
    def trace_id(self) -> str:
        if self.parent is not None:
            return self.parent.trace_id
        return f"{_uniq}-p{self._seq:x}"

    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id)

    def to_dict(self) -> dict:
        return {
            "name": self.name, "kind": self.kind,
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_span_id": self.parent_id,
            "start_s": self.start_s, "dur_s": self.dur_s,
            "status": self.status, "attrs": dict(self.attrs), "links": [],
        }

    def __repr__(self):
        return f"ProgramSpan<{self.name} {self.dur_s * 1e3:.3f} ms>"


def span(name: str, parent=None, **attrs) -> ProgramSpan:
    """A span of the program's own work, recorded whatever
    :data:`TRACING` says (see the module's header for why and at what
    cost)::

        with obs_context.span("engine.step.pull", live=3) as sp:
            ...
        sp.dur_s            # seconds on time.monotonic

    The parent is the span open on the same thread, unless ``parent``
    names one: a :class:`ProgramSpan`, a :class:`Span`, a
    :class:`TraceContext` or its meta dict (so a request's tree hangs
    under the caller's trace). ``attrs`` may be added to until the span is
    read (``sp.attrs["retired"] = 2``). A span whose times are known only
    afterwards is written with :meth:`ProgramSpan.record` instead of
    ``with``."""
    if parent is not None and not isinstance(parent, ProgramSpan):
        parent = _coerce_parent(parent)
    return ProgramSpan(name, parent, attrs)


# -- the compile account -----------------------------------------------------

# jax's event -> the attribute it adds to on the span that paid, and the
# name it has in the account
_JAX_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_JAX_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_COUNTED = frozenset(_JAX_COUNTS.values())

CompileEvent = collections.namedtuple(
    "CompileEvent", "t event seconds own hit span fun")
CompileEvent.__doc__ = """One thing jax reported: when it ended
(``time.monotonic``), which (``trace_s``, ``lower_s``, ``compile_s``,
``cache_hits``, ``cache_misses``), its seconds as jax gave them (0.0 for the
two counts) and its ``own`` seconds (less the events that ended inside it on
its thread: tracing a function holds the tracing of every jitted function it
calls, and whatever it computes on the way), ``hit`` (a ``compile_s`` that
was a load from the persistent cache, or the hit itself: True; a fresh
compile or a miss: False; None for tracing and lowering), the name of the
program span charged (None: no span was open on that thread) and jax's name
for the function."""
# the durations of one thread nest or follow one another; a listener hears
# an end a few microseconds late, which is all the slack nesting needs
_NESTING_SLACK_S = 2e-5
# a thread's latest durations that nothing has held yet: one traced program
# calls thousands of jitted functions, all of them inside its own tracing
_HEARD_KEPT = 16384

# a program's start is a tracing event for every jitted function it calls
# (a thousand and more), then one lowering, one compile and the cache's
# word; the totals run on when the list has dropped its oldest
MAX_COMPILE_EVENTS = 32768
_compile_events: "collections.deque[CompileEvent]" = collections.deque(
    maxlen=MAX_COMPILE_EVENTS)
_account_lock = threading.Lock()
_NO_TOTALS = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
              "trace_own_s": 0.0, "lower_own_s": 0.0, "load_s": 0.0,
              "compiles": 0, "loads": 0, "cache_hits": 0,
              "cache_misses": 0, "unspanned": 0}
_totals = dict(_NO_TOTALS)                # guarded-by: _account_lock
_events_heard = 0                         # guarded-by: _account_lock
_listening = False                        # guarded-by: _account_lock


def _listen_to_jax() -> None:
    """Register the account's two listeners with jax.monitoring, once a
    process (jax has no way to take one back)."""
    global _listening
    with _account_lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_heard_seconds)
    jax.monitoring.register_event_listener(_heard_count)


def _heard_seconds(event, duration, **kw) -> None:
    key = _JAX_SECONDS.get(event)
    if key is not None:
        _charge(key, float(duration), kw.get("fun_name"))


def _heard_count(event, **_kw) -> None:
    key = _JAX_COUNTS.get(event)
    if key is not None:
        _charge(key, 1, None)


def _tally(totals: dict, ev: CompileEvent) -> None:
    totals[ev.event] += 1 if ev.event in _COUNTED else ev.seconds
    if ev.event == "compile_s":
        totals["compiles"] += 1
        if ev.hit:
            totals["loads"] += 1
            totals["load_s"] += ev.seconds
    elif ev.event == "trace_s":
        totals["trace_own_s"] += ev.own
    elif ev.event == "lower_s":
        totals["lower_own_s"] += ev.own
    if ev.span is None:
        totals["unspanned"] += 1


def _charge(key: str, amount, fun) -> None:
    """One event into the account, and onto the innermost program span
    open on this thread (jax compiles on the thread that called).
    ``amount``: the seconds of a duration, 1 for a count. A span is
    charged a duration's own seconds, so that what it carries adds up to
    no more than it lasted."""
    global _events_heard
    now = time.monotonic()
    top = getattr(_open, "top", None)
    hit, seconds, own = None, 0.0, 0.0
    if key == "cache_hits":
        _open.cache_hit = hit = True
    elif key == "cache_misses":
        hit = False
    else:
        seconds = own = amount
        if key == "compile_s":
            hit = getattr(_open, "cache_hit", False)
            _open.cache_hit = False
            paid = getattr(_open, "compiled", (0, 0.0))
            _open.compiled = (paid[0] + 1, paid[1] + seconds)
        # what ended inside this duration has reported its seconds already
        heard = getattr(_open, "heard", None)
        if heard is None:
            heard = _open.heard = collections.deque(maxlen=_HEARD_KEPT)
        began = now - seconds
        while heard and heard[-1][0] >= began - _NESTING_SLACK_S:
            own -= heard.pop()[1]
        amount = own = max(own, 0.0)
        if seconds:
            heard.append((began, seconds))
    ev = CompileEvent(now, key, seconds, own, hit,
                      None if top is None else top.name, fun)
    with _account_lock:
        if top is not None:
            attrs = top.attrs
            attrs[key] = attrs.get(key, 0) + amount
            if key == "compile_s":
                attrs["compiles"] = attrs.get("compiles", 0) + 1
        _tally(_totals, ev)
        _events_heard += 1
        _compile_events.append(ev)


def compile_running() -> Tuple[int, float]:
    """``(backend compiles, their seconds)`` that the calling thread has
    paid so far, loads and fresh ones alike (jax compiles on the thread that
    called the program): what the decode loop reads before and after a pass,
    so a pass counts its own engine's compiles and no other thread's."""
    return getattr(_open, "compiled", (0, 0.0))


def compile_account(since: Optional[float] = None,
                    until: Optional[float] = None) -> dict:
    """What jax reported since the account began to listen (the first
    program span of the process, ``utils.hw_accel.enable_compilation_cache``
    or the first call of this function, whichever came first)::

        {"totals": {"trace_s", "lower_s", "compile_s", "trace_own_s",
                    "lower_own_s", "load_s", "fresh_s", "compiles", "loads",
                    "fresh", "cache_hits", "cache_misses", "unspanned"},
         "events": [CompileEvent, ...],      # oldest first, bounded
         "dropped": 0}

    ``trace_s`` and ``lower_s`` are jax's durations summed as any listener
    would sum them; ``trace_own_s`` and ``lower_own_s`` count no second
    twice (:class:`CompileEvent`), so they and ``compile_s`` add up to wall
    time. ``load_s`` / ``loads`` are the backend compiles that a
    persistent-cache hit preceded on their thread, ``fresh_s`` / ``fresh``
    the others; ``unspanned`` counts the events whose thread had no program
    span open (kept with ``span=None``). With ``since`` or ``until``
    (``time.monotonic``) both are of the events kept with ``since <= t <
    until``: "before the window opened", "inside it". ``dropped`` counts
    the events the bounded list has let go that such sums lack (all older
    than the oldest kept): 0 without an interval, where the totals are the
    running ones, and 0 where ``since`` is no older than the oldest kept."""
    _listen_to_jax()
    with _account_lock:
        totals = dict(_totals)
        events = list(_compile_events)
        dropped = _events_heard - len(events)
    if since is None and until is None:
        dropped = 0
    else:
        if since is not None and events and events[0].t <= since:
            dropped = 0  # the interval begins among the events kept
        events = [e for e in events
                  if (since is None or e.t >= since)
                  and (until is None or e.t < until)]
        totals = dict(_NO_TOTALS)
        for ev in events:
            _tally(totals, ev)
    totals["fresh_s"] = totals["compile_s"] - totals["load_s"]
    totals["fresh"] = totals["compiles"] - totals["loads"]
    return {"totals": totals, "events": events, "dropped": dropped}


def startup_spans() -> List[ProgramSpan]:
    """The ``setup.*`` and ``program.first_call`` spans of this process,
    oldest first: kept apart from the ring, so they can be read after any
    number of passes."""
    return list(_startup)


# -- control -----------------------------------------------------------------

def enable_tracing() -> None:
    global TRACING
    TRACING = True


def disable_tracing() -> None:
    global TRACING
    TRACING = False


def reset() -> None:
    """Drop recorded spans (tests / fresh export windows). The compile
    account runs on: its totals are counters."""
    _finished.clear()
    _startup.clear()


def finished_spans() -> List[Span]:
    """Snapshot of the recent finished spans, oldest first."""
    return list(_finished)


def spans_for_trace(trace_id: str) -> List[Span]:
    return [s for s in _finished if s.trace_id == trace_id]


def mono_to_wall_offset() -> float:
    """``time.time() - time.monotonic()`` right now: the per-process
    clock offset that converts span start times (monotonic) to wall
    clock. Exported alongside spans so a DIFFERENT process (the fleet
    scraper) can place them on one shared timeline — monotonic epochs
    are process-private, wall clock is not."""
    return time.time() - time.monotonic()


def export_spans(trace_id: Optional[str] = None,
                 last: Optional[int] = None) -> dict:
    """Serializable span export for cross-process stitching (the
    ``GET /spans`` route — service/api.py). Each span dict additionally
    carries ``start_wall_s`` (wall-clock start, one offset applied to
    the whole batch) so the fleet view can interleave spans from many
    processes; ``pid`` identifies the exporting process in the joined
    Perfetto document."""
    offset = mono_to_wall_offset()
    spans = (spans_for_trace(trace_id) if trace_id is not None
             else finished_spans())
    if last is not None:
        spans = spans[-last:]
    out = []
    for s in spans:
        d = s.to_dict()
        d["start_wall_s"] = s.start_s + offset
        d["tid"] = s.tid
        out.append(d)
    return {"pid": os.getpid(), "tracing": TRACING,
            "mono_to_wall": offset, "spans": out}


def stats() -> dict:
    return {"finished_total": _finished_total, "retained": len(_finished),
            "tracing": TRACING}


# -- export ------------------------------------------------------------------

def export_chrome_trace(path: Optional[str] = None) -> dict:
    """Serialize the recent spans as chrome://tracing / Perfetto JSON.
    Returns the trace dict; also writes it to ``path`` when given. Each
    event's ``args`` carries trace_id / span_id / parent_span_id / links
    so the request tree survives the format."""
    events = []
    for s in finished_spans():
        events.append({
            "name": s.name,
            "cat": s.kind,
            "ph": "X",
            "ts": (s.start_s - _t0) * 1e6,
            "dur": s.dur_s * 1e6,
            "pid": os.getpid(),
            "tid": s.tid,
            "args": {
                "trace_id": s.trace_id,
                "span_id": s.span_id,
                "parent_span_id": s.parent_id,
                "status": s.status,
                "links": [{"trace_id": t, "span_id": sid}
                          for t, sid in s.links],
                **s.attrs,
            },
        })
    doc = {"traceEvents": events}
    if path:
        import json

        with open(path, "w") as fh:
            json.dump(doc, fh)
    return doc
