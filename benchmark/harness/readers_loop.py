"""Readers for a stack of layers that is run several times over the same
weights, with a KV cache of its own for every (pass, layer) pair.

They read what ``readers.py``'s read (the driver's facts, the traced
slice's events) and the program's loop spans as ``readers_spans.py`` does.
Since the PR that added these, an ``engine/step`` span that sent a decode
step and a ``generator/prefill`` span carry ``ut_steps`` (the passes over
the weights their executable made a token) and ``cache_layers``. A program
that has no such attrs, as the parent of that PR has not, gives
``loop_hbm_roofline`` nothing to read: None, and the line leaves the metric
out. A family with more cache layers than weight layers gives the readers
``stack_weight_bytes(sz)``, ``head_weight_bytes(sz)`` and a
``kv_bytes_per_position(sz, kv_bytes)`` that counts every cache layer.
"""
from . import readers_spans, trace_reduce
from .readers import decode_positions_read, of_a_chip
from .readers_moe import seconds_matching_any

NAME, END, ATTRS = readers_spans.NAME, readers_spans.END, readers_spans.ATTRS
RAN_PASSES = ("engine/step", "generator/prefill")


def pass_spans(facts):
    """The spans that ended in the slice and say how many passes over the
    weights their executable made."""
    rows = readers_spans.spans_of(facts) or []
    since, until = facts.get("slice") or (0.0, float("inf"))
    return [r for r in rows if r[NAME] in RAN_PASSES
            and "ut_steps" in r[ATTRS] and since <= r[END] <= until]


@of_a_chip
def loop_hbm_roofline(facts, events, spec):
    """Bandwidth-bound: the least bytes the slice's executables had to
    move (each decode step sent and each prefill: its passes x the
    stack's weights, and the head once; the keys and values of the
    positions live rows attended, in every cache layer, from the request
    records) at the peak HBM bytes/s, over the device's busy seconds."""
    busy = trace_reduce.busy_seconds(events)
    spans = pass_spans(facts)
    span = facts.get("slice")
    if not busy or not spans or not span:
        return None
    fam, sz = facts["family"], facts["sizes"]
    weights = sum(r[ATTRS]["ut_steps"] * fam.stack_weight_bytes(sz)
                  + fam.head_weight_bytes(sz) for r in spans)
    cache = decode_positions_read(facts["slice_records"], *span) \
        * fam.kv_bytes_per_position(sz, facts["kv_bytes"])
    return 100.0 * (weights + cache) / facts["peaks"]["hbm_bytes_per_s"] \
        / busy


def loop_cache_device_share(facts, events, spec):
    """Device time of the cache layers' reads and appends (the events
    ``match_any`` names) over the device's busy time, in %."""
    seconds = seconds_matching_any(events, spec["match_any"])
    busy = trace_reduce.busy_seconds(events)
    if not seconds or not busy:
        return None
    return 100.0 * seconds / busy
