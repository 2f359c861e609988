"""One small reader for each per-layer metric.

A reader is ``reader(facts, events, spec)``: ``facts`` is what the cell's
driver counted (window length, tokens, counter deltas, request records, the
family and its sizes, the chip's peaks), ``events`` the traced slice as
``trace_reduce.read_events`` gives it, ``spec`` the metric's own file under
``layer_metrics/``. It returns the number, or None where it finds nothing
to read, and the harness then leaves the metric out of the line. A share of
a roofline or of a peak is never reported as 0 for want of a reading.

A later PR adds a metric by adding a ``layer_metrics/<name>.json``; where
none of these readers fits, it adds ``readers_<something>.py`` beside this
file and names ``<module>:<function>`` in ``reader``.
"""
import functools

from . import trace_reduce


def of_a_chip(reader):
    """A share of a chip's peak: nothing to read where the run has no
    chip's peaks, as in the CPU rehearsal."""
    @functools.wraps(reader)
    def guarded(facts, events, spec):
        if not facts.get("peaks"):
            return None
        return reader(facts, events, spec)
    return guarded


def device_idle_share(facts, events, spec):
    busy = trace_reduce.busy_seconds(events)
    window = trace_reduce.window_seconds(events)
    if not busy or not window:
        return None
    return 100.0 * (1.0 - busy / window)


def counter_delta(facts, events, spec):
    """A counter of the program, after the window less before it."""
    return facts.get(spec["fact"])


def window_ms_per_count(facts, events, spec):
    count = facts.get(spec["fact"])
    if not count:
        return None
    return 1e3 * facts["window_s"] / count


def ratio_of_counts(facts, events, spec):
    under = facts.get(spec["per"])
    if not under:
        return None
    return facts[spec["fact"]] / under


@of_a_chip
def train_mfu(facts, events, spec):
    """The operations the forward and backward passes need for a token
    (recomputed ones not counted) x tokens a second, over the peak."""
    per_token = facts["family"].train_flops_per_token(
        facts["sizes"], facts["seq"])
    return 100.0 * per_token * facts["tokens_per_s"] \
        / facts["peaks"]["flops_per_s"]


@of_a_chip
def serve_mfu(facts, events, spec):
    """The operations the window's replies needed, prompt and new tokens,
    a second of the window, over the peak."""
    fam, sz = facts["family"], facts["sizes"]
    flops = sum(fam.serve_flops(sz, r["prompt_len"], r["new_tokens"])
                for r in facts["records"] if r["ok"])
    if not flops:
        return None
    return 100.0 * flops / facts["window_s"] / facts["peaks"]["flops_per_s"]


@of_a_chip
def flash_attention_roofline(facts, events, spec):
    """Compute-bound: the least time the chip needs for the causal
    attention products of the traced steps, forward and backward, over the
    device time of the flash kernel's events."""
    seconds = trace_reduce.seconds_matching(events, spec["match"])
    steps = facts.get("traced_steps")
    if not seconds or not steps:
        return None
    flops = steps * facts["family"].causal_attention_flops(
        facts["sizes"], facts["batch"], facts["seq"], backward=True)
    return 100.0 * flops / facts["peaks"]["flops_per_s"] / seconds


def decode_positions_read(records, t0, t1):
    """Cache positions that live rows had to read in ``[t0, t1]``: reply
    ``r`` makes ``new_tokens - 1`` decode steps (prefill gives the first
    token), spread evenly from send to reply, and step ``j`` reads the
    prompt and the ``j`` tokens before it."""
    total = 0
    for r in records:
        steps = r["new_tokens"] - 1
        if steps < 1 or not r["ok"]:
            continue
        each = (r["t_reply"] - r["t_send"]) / (steps + 1)
        for j in range(1, steps + 1):
            if t0 <= r["t_send"] + (j + 0.5) * each <= t1:
                total += r["prompt_len"] + j
    return total


@of_a_chip
def paged_attention_roofline(facts, events, spec):
    """Bandwidth-bound: the least time the chip needs to read the keys and
    values of the live rows' contexts in the traced slice, over the device
    time of the paged kernel's events. The bytes come from the harness's
    request records, never from the kernel's grid or the table's width."""
    seconds = trace_reduce.seconds_matching(events, spec["match"])
    span = facts.get("slice")
    if not seconds or not span:
        return None
    positions = decode_positions_read(facts["slice_records"], *span)
    if not positions:
        return None
    nbytes = positions * facts["family"].kv_bytes_per_position(
        facts["sizes"], facts["kv_bytes"])
    return 100.0 * nbytes / facts["peaks"]["hbm_bytes_per_s"] / seconds


def resolve(name):
    """``name`` or ``module:function`` to the reader."""
    import importlib
    if ":" in name:
        module, func = name.split(":")
        return getattr(importlib.import_module(
            f"benchmark.harness.{module}"), func)
    return globals()[name]
