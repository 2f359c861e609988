"""Request-scoped tracing (Dapper-style trace/span propagation).

A :class:`SpanContext` is minted at the edge (``serving.Client`` — or
any caller via :func:`maybe_trace`/:func:`new_trace`), carried in the
wire frames next to the existing ``rid`` as a ``"trace"`` dict, and
threaded through admission -> queue -> pad/compile/execute and the
decode slot bank. Every recorded span lands in the profiler's unified
span table (``paddle_tpu.profiler``), so ``tools/timeline.py`` emits ONE
Chrome/Perfetto trace interleaving server stages with training/executor
spans — the Dapper property that makes tail debugging tractable.

Sampling (``FLAGS_trace_sample_rate``) happens ONCE at the edge; an
untraced request pays a single ``random()`` draw client-side and one
``None`` attribute read per server stage — near-zero off-path cost.
Traced spans record even while the profiler is inactive (they are the
always-on sampled stream); ``profiler.reset_profiler()`` clears them and
the ``_MAX_SPANS`` bound + drop counter cap memory.

Loop spans (:class:`loop_span`) are the other always-on stream: what
the decode loop, the generator and ``Executor.run`` did, phase by
phase, for every round and step and not a sampled few. Each is also a
``jax.profiler.TraceAnnotation`` named ``pt/<name>``, so any profiler
trace taken of the process shows the same spans on the host plane, on
the device events' own clock. :func:`loop_spans` reads the ring.
"""
import itertools
import random
import threading
import time
import uuid
from contextlib import contextmanager

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .. import profiler as _prof
from ..flags import flag as _flag
from .metrics import default_registry

_tls = threading.local()

_TRACES_SAMPLED = default_registry().counter(
    "telemetry_traces_sampled_total",
    "trace contexts minted at the client edge (FLAGS_trace_sample_rate)")

default_registry().register_collector(
    lambda: [{"name": "telemetry_spans_dropped_total",
              "kind": "counter",
              "help": "spans lost to the profiler span-table cap "
                      "(process-lifetime total; reset_profiler only "
                      "zeroes the session count, keeping this "
                      "monotonic)",
              "labels": (),
              "samples": [((), _prof.spans_dropped_total())]}],
    families=[{"name": "telemetry_spans_dropped_total",
               "kind": "counter",
               "help": "spans lost to the profiler span-table cap "
                       "(process-lifetime, monotonic)",
               "labels": ()}])


class SpanContext:
    """(trace_id, span_id, parent_id) triple. ``span_id`` names THIS
    span; children are minted with :meth:`child`."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id, span_id=None, parent_id=""):
        self.trace_id = trace_id
        self.span_id = span_id if span_id is not None else _new_id()
        self.parent_id = parent_id

    def child(self):
        return SpanContext(self.trace_id, _new_id(), self.span_id)

    def __repr__(self):
        return (f"SpanContext({self.trace_id}/{self.span_id}"
                f"<-{self.parent_id or 'root'})")


def _new_id():
    return uuid.uuid4().hex[:16]


def new_trace():
    """Unconditionally mint a root span context (the explicit API —
    sampling is the caller's business)."""
    _TRACES_SAMPLED.inc()
    return SpanContext(_new_id())


def maybe_trace():
    """The edge sampler: the ambient context's child if one is active,
    else a fresh root with probability ``FLAGS_trace_sample_rate``,
    else None. One random() draw on the untraced path."""
    ctx = current()
    if ctx is not None:
        return ctx.child()
    if random.random() < _flag("trace_sample_rate"):
        return new_trace()
    return None


def current():
    """The ambient span context of this thread (None when untraced)."""
    return getattr(_tls, "ctx", None)


@contextmanager
def ambient(ctx):
    """Install ``ctx`` as this thread's ambient context for the block
    (``Request._init_lifecycle`` picks it up so spans recorded by the
    batcher threads parent correctly). ``ctx=None`` is a no-op."""
    if ctx is None:
        yield None
        return
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev


def record_span(name, start_s, end_s, ctx, attrs=None):
    """Record a completed span AS ``ctx`` (trace/span/parent ids ride
    into the profiler span table). No-op when ``ctx`` is None."""
    if ctx is None:
        return
    _prof.record_span(name, start_s, end_s,
                      trace=(ctx.trace_id, ctx.span_id, ctx.parent_id),
                      attrs=attrs)


def record_child(name, start_s, end_s, parent, attrs=None):
    """Record a completed span as a fresh CHILD of ``parent``; returns
    the child context (None when untraced)."""
    if parent is None:
        return None
    ctx = parent.child()
    record_span(name, start_s, end_s, ctx, attrs)
    return ctx


@contextmanager
def span(name, parent=None):
    """Span context manager: times the block and records it as a child
    of ``parent`` (default: the ambient context), installing the child
    as ambient inside the block so nested spans chain."""
    parent = parent if parent is not None else current()
    if parent is None:
        yield None
        return
    ctx = parent.child()
    t0 = time.perf_counter()
    with ambient(ctx):
        try:
            yield ctx
        finally:
            record_span(name, t0, time.perf_counter(), ctx)


# -- loop spans: always on, every round and step --------------------------

_loop_seq = itertools.count(1)
PT_PREFIX = "pt/"


def loop_root(trace_id):
    """The parent of a loop's outermost spans: it names the trace
    (``loop:<batcher id>``, ``exe:<executor id>``) and no span, so a
    span opened under it is a root of that trace."""
    return SpanContext(trace_id, "")


_NO_LOOP = loop_root("loop:-")      # a phase run outside any loop


def request_root(ctx):
    """What a request's own spans hang under when the decode loop
    records them for EVERY request: the client's context where it sent
    one, else a trace id minted here and no parent span."""
    if ctx is not None:
        return ctx
    return loop_root("req:%x" % next(_loop_seq))


class loop_span:
    """``with loop_span(name, parent=None, **attrs) as sp:`` times the
    block on ``time.perf_counter()`` and, on exit, appends ``(name,
    start_s, end_s, tid, trace_id, span_id, parent_id, attrs)`` to the
    profiler's span table whether or not a profiler session or a
    sampled request is active (at the cap the oldest row rotates out
    and the drop is counted). The block is also a
    ``jax.profiler.TraceAnnotation("pt/" + name)`` — a
    ``StepTraceAnnotation`` where ``attrs`` hold a ``step_num`` — which
    costs half a microsecond while nobody traces and otherwise puts the
    span into the trace on the device events' clock.

    ``parent`` is a ``SpanContext`` or another ``loop_span``; left out,
    it is the span this thread is inside. Give it where work hops
    threads (``run_with_watchdog``'s worker has no enclosing span).
    ``sp.attrs`` may be filled inside the block with what is only known
    at its end; ``sp.t0``/``sp.t1`` are the clock reads, for consumers
    that need the same interval; ``sp.dropped = True`` records
    nothing."""

    __slots__ = ("name", "ctx", "attrs", "t0", "t1", "dropped", "_ann",
                 "_prev")

    def __init__(self, name, parent=None, **attrs):
        parent = getattr(parent, "ctx", parent)
        if parent is None:
            parent = current_loop() or _NO_LOOP
        self.name = name
        self.ctx = SpanContext(parent.trace_id, "%x" % next(_loop_seq),
                               parent.span_id)
        self.attrs = attrs
        self.t0 = self.t1 = None
        self.dropped = False

    def __enter__(self):
        self._prev = getattr(_tls, "loop", None)
        _tls.loop = self.ctx
        step = self.attrs.get("step_num")
        self._ann = (TraceAnnotation(PT_PREFIX + self.name)
                     if step is None else StepTraceAnnotation(
                         PT_PREFIX + self.name, step_num=step))
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _tls.loop = self._prev
        if not self.dropped:
            ctx = self.ctx
            _prof.record_span(
                self.name, self.t0, self.t1,
                trace=(ctx.trace_id, ctx.span_id, ctx.parent_id),
                attrs=self.attrs)
        return False


def current_loop():
    """The context of the loop span this thread is inside (None outside
    any): what to hand, as ``parent``, to work that hops threads."""
    return getattr(_tls, "loop", None)


def loop_spans(since_s, until_s):
    """The span table's rows that carry ids (loop spans, request spans,
    sampled traces) and overlap ``[since_s, until_s]`` on the
    ``perf_counter`` clock, oldest first, each ``(name, start_s, end_s,
    tid, trace_id, span_id, parent_id, attrs)``. What the benchmark's
    readers read, and what ``tools/timeline.py`` renders once dumped
    (``{"spans": loop_spans(...)}``)."""
    return _prof.spans_between(since_s, until_s)


# -- wire representation (inside the typed wire value universe) ----------

def to_wire(ctx):
    """``{"tid", "sid"}`` dict for the wire frame (None passthrough)."""
    if ctx is None:
        return None
    return {"tid": ctx.trace_id, "sid": ctx.span_id}


def from_wire(d):
    """SpanContext from a wire ``"trace"`` dict (None / malformed ->
    None; a hostile frame must never raise here)."""
    if not isinstance(d, dict):
        return None
    tid, sid = d.get("tid"), d.get("sid")
    if not (isinstance(tid, str) and isinstance(sid, str)):
        return None
    return SpanContext(tid[:64], sid[:64])
