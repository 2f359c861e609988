"""Readers of the program's own loop spans.

The program keeps, always on, a ring of spans of what its decode loop, its
generator and ``Executor.run`` did (``paddle_tpu.observability.tracing``:
``loop_span``, read back with ``loop_spans(since_s, until_s)``). A row is
``(name, start_s, end_s, tid, trace_id, span_id, parent_id, attrs)`` on the
``time.perf_counter()`` clock, the one ``Slice.t0``/``t1`` are read on. A
metric's file under ``layer_metrics/`` says what to read:

    span        the name of the spans the metric is about
    having      keep only those whose attrs hold this key
    minus       names of descendants whose time is taken out (self time)
    attr, per   two attrs of the span, for a ratio
    percentile  which percentile of the durations
    outside     names of spans whose time does not count (idle_under_span)
    last        where the driver's facts give no ``slice`` (the train
                driver's do not): the fact that counts the traced steps,
                the last the program ran before the readers run

Every reader keeps to the traced slice, and returns None where the program
has no such ring (a commit before the spans: ``loop_spans`` is missing),
where the slice holds no such span and, for ``idle_under_span``, where the
trace holds no chip's plane: the harness then leaves the metric out.

``idle_under_span`` needs the spans on the device trace's clock.
``trace_reduce.read_events`` keeps no host event outside ``bench/``, so the
``pt/`` annotations that the program writes into the trace itself are not
in ``events``; the rows are placed instead by one anchor the harness
records on both clocks: the ``bench/slice`` event's start in ``events``
against ``facts["slice"][0]`` (``Slice.__enter__`` reads the clock on the
line after it opens that annotation).
"""
import numpy as np

from . import trace_reduce

NAME, START, END, TID, TRACE, SPAN, PARENT, ATTRS = range(8)


def spans_of(facts):
    """The rows that touch the traced slice (every row where the facts
    name none), oldest first; None where the program keeps no ring.
    ``facts["spans"]`` stands in for the program's ring in the tests."""
    if "spans" in facts:
        return facts["spans"]
    try:
        from paddle_tpu.observability.tracing import loop_spans
    except ImportError:
        return None
    since, until = facts.get("slice") or (0.0, float("inf"))
    return loop_spans(since, until)


def picked(facts, spec, whole=False):
    """The spans the metric is about: named ``span``, with the ``having``
    attr where the file asks for one, ended inside the slice (``whole``:
    begun inside it too, so that all they caused is among the rows);
    where the facts give no slice, the last ``facts[last]`` of them.
    Empty where there is nothing to read."""
    named = [r for r in spans_of(facts) or []
             if r[NAME] == spec["span"]
             and ("having" not in spec or spec["having"] in r[ATTRS])]
    if facts.get("slice"):
        since, until = facts["slice"]
        return [r for r in named if since <= r[END] <= until
                and (r[START] >= since or not whole)]
    count = facts.get(spec.get("last"))
    return named[-count:] if count else []


def descendants_seconds(rows, root, names):
    """Seconds of the spans under ``root`` (children, their children, ...)
    whose name is one of ``names``."""
    children = {}
    for r in rows:
        if r[TRACE] == root[TRACE]:
            children.setdefault(r[PARENT], []).append(r)
    total, todo = 0.0, [root]
    while todo:
        for child in children.get(todo.pop()[SPAN], ()):
            if child[NAME] in names:
                total += child[END] - child[START]
            todo.append(child)
    return total


def self_ms(facts, events, spec):
    """Mean, in ms, of a span's length less its ``minus`` descendants."""
    mine = picked(facts, spec, whole=True)
    if not mine:
        return None
    rows = spans_of(facts)
    return 1e3 * float(np.mean([
        r[END] - r[START] - descendants_seconds(rows, r, spec["minus"])
        for r in mine]))


def share_of_slice(facts, events, spec):
    """Seconds of the spans inside the slice over the slice's, in %."""
    rows = spans_of(facts)
    if not rows or not facts.get("slice"):
        return None
    since, until = facts["slice"]
    inside = [min(r[END], until) - max(r[START], since)
              for r in rows if r[NAME] == spec["span"]]
    inside = [s for s in inside if s > 0]
    if not inside:
        return None
    return 100.0 * sum(inside) / (until - since)


def duration_percentile_ms(facts, events, spec):
    mine = picked(facts, spec)
    if not mine:
        return None
    return 1e3 * float(np.percentile(
        [r[END] - r[START] for r in mine], spec["percentile"]))


def attr_ratio_percent(facts, events, spec):
    """Mean over the spans of ``attr`` over ``per``, in %."""
    mine = [r for r in picked(facts, spec) if r[ATTRS].get(spec["per"])]
    if not mine:
        return None
    return 100.0 * float(np.mean([
        r[ATTRS][spec["attr"]] / r[ATTRS][spec["per"]] for r in mine]))


# ------------------------------------------------- intervals, in nanoseconds

def merged(intervals):
    """Sorted, disjoint ``(start, end)`` covering the same instants."""
    out = []
    for start, end, *_ in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        elif end > start:
            out.append([start, end])
    return [tuple(i) for i in out]


def common(a, b):
    """The instants in both of two merged lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        start, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if end > start:
            out.append((start, end))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def outside(a, b, lo, hi):
    """The instants of merged ``a`` within ``[lo, hi]`` and in none of
    merged ``b``."""
    gaps, reach = [], lo
    for start, end in b:
        if start > reach:
            gaps.append((reach, min(start, hi)))
        reach = max(reach, end)
    if reach < hi:
        gaps.append((reach, hi))
    return common(a, [g for g in gaps if g[1] > g[0]])


def idle_under_span(facts, events, spec):
    """Share of the slice, in %, in which the first chip ran nothing while
    the program was inside a ``span`` and outside every ``outside`` one."""
    chips = trace_reduce.device_ops(events)
    mark = [(s, e) for s, e, name in trace_reduce.host_spans(events)
            if name == "slice"]
    rows = spans_of(facts)
    if not chips or not mark or not rows or not facts.get("slice"):
        return None
    lo, hi = mark[0]

    def on_trace_clock(names):
        return merged((lo + round(1e9 * (r[START] - facts["slice"][0])),
                       lo + round(1e9 * (r[END] - facts["slice"][0])))
                      for r in rows if r[NAME] in names)

    under = on_trace_clock([spec["span"]])
    if not under:
        return None
    idle = outside([(lo, hi)], merged(chips[sorted(chips)[0]]), lo, hi)
    cover = outside(under, on_trace_clock(spec.get("outside", [])), lo, hi)
    return 100.0 * sum(e - s for s, e in common(idle, cover)) / (hi - lo)
