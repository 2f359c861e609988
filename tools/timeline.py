#!/usr/bin/env python
"""Convert a profiler span dump into a Chrome tracing JSON (reference
tools/timeline.py — its --profile_path proto becomes the spans JSON
that paddle_tpu.profiler.stop_profiler(profile_path=...) writes; load
the output in chrome://tracing or Perfetto).

Spans come in two shapes, unified in one span table:

    [name, start_s, end_s, tid]                          profiler event
    [name, start_s, end_s, tid, trace_id, span_id,
     parent_id]                                          traced request
    [name, start_s, end_s, tid, trace_id, span_id,
     parent_id, attrs]                                   loop span

Loop spans (``tracing.loop_span``: what the decode loop, the generator
and ``Executor.run`` did, every round and step, always on) carry the
counts at their boundary as ``attrs``, shown in the event's ``args``.
Without a profiler session, dump the ring's last minutes with
``json.dump({"spans": tracing.loop_spans(since_s, until_s)}, f)``.

Traced spans (observability.tracing, wire-propagated request tracing)
carry their ids in the event ``args`` and are linked parent -> child
with Chrome flow events, so ONE request renders as one connected trace
interleaved with the host-side profiler spans around it.

Usage:
    python tools/timeline.py --profile_path /tmp/profile \\
        --timeline_path /tmp/timeline.json
"""
import argparse
import json


def to_chrome_trace(spans, counters=()):
    """spans: [(name, start_s, end_s, tid[, trace_id, span_id,
    parent_id])] -> Chrome trace dict (complete events, microsecond
    timebase, normalized to t0; flow events link traced parent/child
    spans). ``counters`` ([(name, t_s, value)] — e.g. the memory
    profiler's hbm_live_bytes live-set track) render as Chrome counter
    ("C") events, so Perfetto shows the byte timeline under the op
    spans."""
    if not spans and not counters:
        return {"traceEvents": []}
    t0 = min([s[1] for s in spans] + [c[1] for c in counters])
    if not spans:
        return {"traceEvents": [
            {"name": c[0], "ph": "C", "ts": (c[1] - t0) * 1e6,
             "pid": 0, "args": {"value": c[2]}} for c in counters]}
    events = []
    tids = {}
    # span_id -> (end_ts, tid) of traced spans, for flow binding
    by_span_id = {}
    traced = []
    for s in spans:
        name, start, end, tid = s[0], s[1], s[2], s[3]
        tids.setdefault(tid, len(tids))
        ev = {
            "name": name,
            "ph": "X",                       # complete event
            "ts": (start - t0) * 1e6,
            "dur": max((end - start) * 1e6, 0.001),
            "pid": 0,
            "tid": tids[tid],
            "cat": "host",
        }
        if len(s) >= 7:
            trace_id, span_id, parent_id = s[4], s[5], s[6]
            ev["cat"] = "request"
            ev["args"] = {"trace_id": trace_id, "span_id": span_id,
                          "parent_span_id": parent_id}
            if len(s) >= 8:
                ev["args"].update(s[7])
            by_span_id[span_id] = (ev["ts"], ev["dur"], tids[tid])
            traced.append(ev)
        events.append(ev)
    # flow events: one arrow per traced child from its parent span
    flows = []
    for ev in traced:
        parent = ev["args"]["parent_span_id"]
        src = by_span_id.get(parent)
        if not src:
            continue
        fid = f"{ev['args']['trace_id']}/{ev['args']['span_id']}"
        src_ts, src_dur, src_tid = src
        flows.append({"name": "trace", "ph": "s", "cat": "request",
                      "id": fid, "pid": 0, "tid": src_tid,
                      "ts": src_ts})
        flows.append({"name": "trace", "ph": "f", "bp": "e",
                      "cat": "request", "id": fid, "pid": 0,
                      "tid": ev["tid"], "ts": ev["ts"]})
    counter_events = [
        {"name": c[0], "ph": "C", "ts": (c[1] - t0) * 1e6, "pid": 0,
         "args": {"value": c[2]}} for c in counters]
    meta = [{"name": "process_name", "ph": "M", "pid": 0,
             "args": {"name": "paddle_tpu host"}}]
    meta += [{"name": "thread_name", "ph": "M", "pid": 0, "tid": i,
              "args": {"name": f"thread {i}"}} for i in tids.values()]
    return {"traceEvents": meta + events + flows + counter_events,
            "displayTimeUnit": "ms"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile_path", required=True,
                    help="spans JSON written by profiler.stop_profiler")
    ap.add_argument("--timeline_path", required=True,
                    help="output Chrome trace JSON")
    args = ap.parse_args()
    with open(args.profile_path) as f:
        doc = json.load(f)
    spans = doc["spans"]
    counters = doc.get("counters", [])
    with open(args.timeline_path, "w") as f:
        json.dump(to_chrome_trace(spans, counters=counters), f)
    dropped = doc.get("dropped", 0)
    drop_note = f"; {dropped} spans were dropped at record time" \
        if dropped else ""
    counter_note = f", {len(counters)} counter samples" if counters \
        else ""
    print(f"wrote {args.timeline_path} ({len(spans)} spans"
          f"{counter_note}{drop_note}) "
          f"— open in chrome://tracing or Perfetto")


if __name__ == "__main__":
    main()
