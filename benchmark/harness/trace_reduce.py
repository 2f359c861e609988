"""From a profiler trace to a list of events, and from the list to times.

``read_events`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into
``[plane, line, name, start_ns, dur_ns]`` rows, read with
``jax.profiler.ProfileData`` and nothing else. Every number the benchmark
takes from a trace is worked out from that list by the functions below, so
that it can be checked on a recorded list with no chip
(``tests/benchmark_tests/fixtures``).

What the v5e's trace looks like (looked at by hand, PR 24): one plane
``/device:TPU:<n>`` a chip, whose line ``XLA Ops`` holds one event for each
operation the chip ran, named by the whole text of its HLO instruction. A
Pallas call is ``%pallas.12 = (...) custom-call(...),
custom_call_target="tpu_custom_call", ...``: the ``<op>/<impl>`` scope that
``_dispatch.resolved`` opens is not in the text, so a kernel is found by
``tpu_custom_call`` and its operands' shapes. ``XLA Modules`` and ``Steps``
on the same plane span whole executables and ``Async XLA Ops`` the copies
that overlap them; counting those would count a second twice. The host's
threads are lines of the plane ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` puts the harness's own spans, on the same
clock.
"""
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path):
    """``[plane, line, name, start_ns, dur_ns]`` for every event of the
    chips' planes and for the harness's own spans on the host's plane (the
    host's other events run to millions and no reader wants them)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    events.append([plane.name, line.name, ev.name,
                                   int(ev.start_ns), int(ev.duration_ns)])
    return events


def device_ops(events):
    """chip's plane name -> its operations, as ``(start, end, name)`` in
    nanoseconds, by start."""
    chips = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE) and line == OPS_LINE:
            chips.setdefault(plane, []).append((start, start + dur, name))
    return {plane: sorted(ops) for plane, ops in chips.items()}


def host_spans(events):
    """The harness's own spans, ``(start, end, name)`` without the
    prefix, by start."""
    return sorted((start, start + dur, name[len(SPAN_PREFIX):])
                  for plane, _line, name, start, dur in events
                  if plane == HOST_PLANE and name.startswith(SPAN_PREFIX))


def union_ns(intervals):
    """Length of the union of ``(start, end, ...)`` intervals."""
    total, reach = 0, None
    for start, end, *_ in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def busy_seconds(events):
    """Seconds in which an operation ran on the device, averaged over the
    chips that ran any; None where no chip's plane is in the trace."""
    chips = device_ops(events)
    if not chips:
        return None
    return sum(union_ns(ops) for ops in chips.values()) / len(chips) / 1e9


def window_seconds(events, spans=("slice",)):
    """Length of the traced slice: the harness's ``bench/slice`` span where
    it is in the trace, and otherwise first operation to last."""
    marks = [(s, e) for s, e, name in host_spans(events) if name in spans]
    if marks:
        return (max(e for _, e in marks) - min(s for s, _ in marks)) / 1e9
    ops = [op for chip in device_ops(events).values() for op in chip]
    if not ops:
        return None
    return (max(e for _, e, _ in ops) - min(s for s, _, _ in ops)) / 1e9


def time_by_name(events):
    """operation name -> seconds on the device, summed over the chips."""
    out = {}
    for ops in device_ops(events).values():
        for start, end, name in ops:
            out[name] = out.get(name, 0.0) + (end - start) / 1e9
    return out


def seconds_matching(events, needles):
    """Device seconds of the operations whose text holds every one of
    ``needles``, averaged over the chips; None where nothing matches."""
    chips = device_ops(events)
    total = sum(end - start for ops in chips.values()
                for start, end, name in ops
                if all(n in name for n in needles))
    return total / len(chips) / 1e9 if total else None


def op_kind(name):
    """An operation's text without what tells one instance from the next:
    the numbers after ``%names`` and the layouts in braces. The same
    operation in each of 24 layers is then one kind."""
    name = re.sub(r"(%[A-Za-z_][\w\-]*?)\.\d+", r"\1", name)
    while True:
        bare = re.sub(r"\{[^{}]*\}", "", name)
        if bare == name:
            return name
        name = bare


def top_device_ops(events, n=10, width=160):
    """The kinds of operation that took most device time, ``[[kind,
    seconds], ...]``, seconds summed over the chips."""
    kinds = {}
    for name, seconds in time_by_name(events).items():
        kind = op_kind(name)[:width]
        kinds[kind] = kinds.get(kind, 0.0) + seconds
    ranked = sorted(kinds.items(), key=lambda kv: -kv[1])
    return [[kind, seconds] for kind, seconds in ranked[:n]]


def idle_gaps(events, n=10):
    """The longest stretches in which the first chip ran nothing, each
    named by the harness's span that covers most of it (``unattributed``
    where none does): ``[[name, seconds], ...]`` summed by name."""
    chips = device_ops(events)
    if not chips:
        return []
    ops = chips[sorted(chips)[0]]
    spans = host_spans(events)
    gaps, reach = [], None
    for start, end, _ in ops:
        if reach is not None and start > reach:
            gaps.append((reach, start))
        reach = end if reach is None else max(reach, end)
    by_name = {}
    for g0, g1 in gaps:
        best, cover = "unattributed", 0
        for s0, s1, name in spans:
            if s0 >= g1:
                break
            if name == "slice":
                continue
            overlap = min(g1, s1) - max(g0, s0)
            if overlap > cover:
                best, cover = name, overlap
        by_name[best] = by_name.get(best, 0.0) + (g1 - g0) / 1e9
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    return [[name, seconds] for name, seconds in ranked[:n]]


def summarise(path):
    """What a trace holds, for looking at one by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            totals = {}
            for ev in evs:
                t = totals.setdefault(ev.name, [0, 0, ev])
                t[0] += 1
                t[1] += ev.duration_ns
            top = sorted(totals.items(), key=lambda kv: -kv[1][1])[:12]
            for name, (count, ns, ev) in top:
                stats = {k: str(v)[:120] for k, v in list(ev.stats)[:8]}
                print(f"    {ns / 1e6:10.3f} ms x{count:<6d} {name[:100]!r}"
                      f" {stats}")


if __name__ == "__main__":
    import sys
    summarise(find_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1])
              else sys.argv[1])
