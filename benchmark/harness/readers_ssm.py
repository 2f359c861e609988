"""Readers for a configuration whose rows keep a fixed-size recurrent
state a slot (state-space layers) beside keys and values a position.

They read what ``readers.py``'s read (the driver's facts, the traced
slice's events) and the program's loop spans as ``readers_spans.py`` does.
Since the PR that added these, over a pool with a state group an
``engine/step`` span that sent a decode step carries ``state_rows`` (the
live rows whose state the step advanced) and ``state_layers``, and a
``generator/prefill`` span ``prompt_tokens`` (the real tokens of the
prompts it ingested), ``scan_tokens`` (rows x the bucket's length) and
``state_layers``. A program that has no such attrs, as the parent of that
PR has not, gives every reader here nothing to read: None, and the line
leaves the metric out; a share is never 0 for want of a reading.

The family gives ``selective_scan_work(sz, tokens, rows)`` (operations,
bytes), ``state_bytes_per_row(sz)``, ``stack_weight_bytes(sz)``,
``head_weight_bytes(sz)`` and ``kv_bytes_per_position(sz, kv_bytes)``. A
device event's name is its HLO instruction's text (``trace_reduce``'s
docstring): the scan is found by its Pallas call's name, the decode
step's state update and convolution by the shapes only the slot bank has
(``match_any``, as ``readers_moe``'s).
"""
from . import readers_spans, trace_reduce
from .readers import decode_positions_read, of_a_chip
from .readers_moe import seconds_matching_any

NAME, END, ATTRS = readers_spans.NAME, readers_spans.END, readers_spans.ATTRS


def spans_with(facts, name, attr):
    """The ``name`` spans that ended in the slice and carry ``attr``."""
    rows = readers_spans.spans_of(facts) or []
    since, until = facts.get("slice") or (0.0, float("inf"))
    return [r for r in rows if r[NAME] == name and attr in r[ATTRS]
            and since <= r[END] <= until]


@of_a_chip
def selective_scan_roofline(facts, events, spec):
    """Summed over the slice's prefills, the larger of the time the
    recurrence's operations need at the peak FLOP/s and the time its
    bytes need at the peak HBM bytes/s, for the real tokens of the
    prompts each ingested, over the device time of the scan kernel's
    events. The work comes from the program's spans and the family's
    shapes, whatever implements the recurrence."""
    seconds = seconds_matching_any(events, [spec["match"]])
    prefills = spans_with(facts, "generator/prefill", "prompt_tokens")
    if not seconds or not prefills:
        return None
    fam, sz, peaks = facts["family"], facts["sizes"], facts["peaks"]
    least = 0.0
    for r in prefills:
        flops, nbytes = fam.selective_scan_work(
            sz, r[ATTRS]["prompt_tokens"], r[ATTRS]["rows"])
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def ssm_device_share(facts, events, spec):
    """Device time of the state-space mechanism's operations (the events
    ``match_any`` names) over the device's busy time, in %."""
    seconds = seconds_matching_any(events, spec["match_any"])
    busy = trace_reduce.busy_seconds(events)
    if not seconds or not busy:
        return None
    return 100.0 * seconds / busy


@of_a_chip
def state_hbm_roofline(facts, events, spec):
    """Bandwidth-bound: the least bytes the slice's executables had to
    move (each decode step sent: the weights once and its live rows'
    state read and written; each prefill: the weights once; the keys and
    values of the positions live rows attended, from the request
    records) at the peak HBM bytes/s, over the device's busy seconds."""
    busy = trace_reduce.busy_seconds(events)
    steps = spans_with(facts, "engine/step", "state_rows")
    prefills = spans_with(facts, "generator/prefill", "state_layers")
    span = facts.get("slice")
    if not busy or not steps or not span:
        return None
    fam, sz = facts["family"], facts["sizes"]
    weights = fam.stack_weight_bytes(sz) + fam.head_weight_bytes(sz)
    state = 2 * fam.state_bytes_per_row(sz) * sum(
        r[ATTRS]["state_rows"] for r in steps)
    cache = decode_positions_read(facts["slice_records"], *span) \
        * fam.kv_bytes_per_position(sz, facts["kv_bytes"])
    moved = weights * (len(steps) + len(prefills)) + state + cache
    return 100.0 * moved / facts["peaks"]["hbm_bytes_per_s"] / busy
