"""Readers for a routed-expert, window-and-full-attention configuration,
and the prefill-only share of the flash forward.

They read what ``readers.py``'s read (the driver's facts, the traced
slice's events) and the program's loop spans as ``readers_spans.py`` does.
The routing counters are attrs of the program's own spans: an
``engine/step`` span (a decode step) and a ``generator/prefill`` span (an
admission's prefill) carry ``moe_tokens`` (assignments, summed over the
layers), ``moe_experts_hit`` and ``moe_load_max`` of the executable they
ran. A program that has no such attrs, as the parent of the PR that added
these has not, gives every reader here nothing to read: None, and the
line leaves the metric out.

A device event's name is its HLO instruction's text (``trace_reduce``'s
docstring): a Pallas call carries the kernel's own name
(``%moe_experts_swiglu.7``), an XLA fusion nothing of the ``moe/`` scope it
was traced under. So the expert products are found by the kernel's name,
and what else the expert layer runs by the shapes only it has (a metric's
``match_any``: lists of strings, an event counts if its name holds every
string of one list).
"""
import numpy as np

from . import readers_spans, trace_reduce
from .readers import of_a_chip

NAME, START, END, ATTRS = (readers_spans.NAME, readers_spans.START,
                           readers_spans.END, readers_spans.ATTRS)


def seconds_matching_any(events, match_any):
    """Device seconds of the operations whose text holds every string of
    one of ``match_any``'s lists, averaged over the chips; None where
    nothing matches."""
    chips = trace_reduce.device_ops(events)
    total = sum(end - start for ops in chips.values()
                for start, end, name in ops
                if any(all(n in name for n in needles)
                       for needles in match_any))
    return total / len(chips) / 1e9 if total else None


def routed_spans(facts):
    """The spans of the slice that carry routing counters."""
    rows = readers_spans.spans_of(facts) or []
    since, until = facts.get("slice") or (0.0, float("inf"))
    return [r for r in rows if "moe_tokens" in r[ATTRS]
            and since <= r[END] <= until]


@of_a_chip
def moe_experts_roofline(facts, events, spec):
    """Summed over the slice's expert-layer calls, the larger of the time
    their products need at the peak FLOP/s and the time their bytes need
    at the peak HBM bytes/s (each touched expert's weights once, each
    routed row in and out), over the device time of the expert kernel's
    events. The work comes from the program's routing counters, whatever
    implements the products."""
    seconds = seconds_matching_any(events, [spec["match"]])
    spans = routed_spans(facts)
    if not seconds or not spans:
        return None
    fam, sz, peaks = facts["family"], facts["sizes"], facts["peaks"]
    least = 0.0
    for r in spans:
        flops, nbytes = fam.expert_layer_work(
            sz, r[ATTRS]["moe_tokens"], r[ATTRS]["moe_experts_hit"])
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def moe_device_share(facts, events, spec):
    """Device time of the expert layer's operations (``match_any``) over
    the device's busy time, in %."""
    seconds = seconds_matching_any(events, spec["match_any"])
    busy = trace_reduce.busy_seconds(events)
    if not seconds or not busy:
        return None
    return 100.0 * seconds / busy


def moe_load_max_over_mean(facts, events, spec):
    """Mean over the slice's decode steps of the fullest expert's load
    over the mean expert's: ``moe_load_max x num_experts / moe_tokens``,
    both summed over the layers. 1 is an even spread."""
    steps = [r for r in routed_spans(facts)
             if r[NAME] == spec["span"] and r[ATTRS]["moe_tokens"]]
    if not steps:
        return None
    experts = facts["sizes"].num_experts
    return sum(r[ATTRS]["moe_load_max"] * experts / r[ATTRS]["moe_tokens"]
               for r in steps) / len(steps)


def decode_layer_positions_read(facts, t0, t1):
    """As ``readers.decode_positions_read``, with each step's positions
    counted layer by layer: the context in a full layer, the window's
    last in a window layer (the family's ``decode_positions``)."""
    fam, sz = facts["family"], facts["sizes"]
    total = 0
    for r in facts["slice_records"]:
        steps = r["new_tokens"] - 1
        if steps < 1 or not r["ok"]:
            continue
        each = (r["t_reply"] - r["t_send"]) / (steps + 1)
        for j in range(1, steps + 1):
            if t0 <= r["t_send"] + (j + 0.5) * each <= t1:
                total += fam.decode_positions(sz, r["prompt_len"] + j)
    return total


@of_a_chip
def paged_attention_roofline_windowed(facts, events, spec):
    """Bandwidth-bound, as ``readers.paged_attention_roofline``, for a
    model whose layers keep different spans: the least time to read the
    keys and values each layer's kernel call had to read (from the
    request records), over the device time of the paged kernel's
    events."""
    seconds = seconds_matching_any(events, [spec["match"]])
    span = facts.get("slice")
    if not seconds or not span:
        return None
    positions = decode_layer_positions_read(facts, *span)
    if not positions:
        return None
    nbytes = positions * facts["family"].kv_bytes_per_layer_position(
        facts["sizes"], facts["kv_bytes"])
    return 100.0 * nbytes / facts["peaks"]["hbm_bytes_per_s"] / seconds


@of_a_chip
def flash_prefill_roofline(facts, events, spec):
    """Compute-bound: the causal (window-limited where the layer is)
    attention products of the prompts whose requests were sent in the
    traced slice, at their own lengths, at the peak FLOP/s, over the
    device time of the flash forward's events. In a closed loop a
    request's prefill follows its send by a round, so the few prefills
    that straddle the slice's edges stand for one another."""
    seconds = seconds_matching_any(events, [spec["match"]])
    span = facts.get("slice")
    if not seconds or not span:
        return None
    fam, sz = facts["family"], facts["sizes"]
    flops = sum(fam.causal_attention_flops(sz, 1, r["prompt_len"], False)
                for r in facts["slice_records"]
                if r["ok"] and span[0] <= r["t_send"] <= span[1])
    if not flops:
        return None
    return 100.0 * flops / facts["peaks"]["flops_per_s"] / seconds


def reply_ms_per_token_percentile(facts, events, spec):
    """The ``percentile`` of client-side milliseconds from send to reply
    over the reply's new tokens, over the window's replies; a failed
    request counts as the whole window a token (as the driver's own
    ``serve_ms_per_token_p95``, for a cell that does not report that)."""
    records = facts.get("records")
    if not records:
        return None
    per_token = [1e3 * (r["t_reply"] - r["t_send"]) / r["new_tokens"]
                 if r["ok"] else 1e3 * facts["window_s"] for r in records]
    return float(np.percentile(per_token, spec["percentile"]))
