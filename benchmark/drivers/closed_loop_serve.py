"""Callers that wait: ``callers`` threads, each a ``Client`` on the
loopback socket of an ``InferenceServer`` started in this process, each
sending its next ``generate`` when the last reply arrives. Greedy, no
shared prefix.

Every seed sends the same multiset of (prompt length, new tokens) pairs,
drawn once from the traffic file's ``shape_seed``, in another order and with
other token ids. Set-up makes the weights, warms every executable the
traffic can reach by driving the engine directly (each prefill row bucket x
length bucket, each scatter shape, the decode step, the greedy pick), starts
the server and the callers, and opens the window once every caller has had
a reply: the ramp is set-up the traffic needs.

``serve_tokens_per_s`` is the new tokens of every reply that arrived inside
the window over the window's seconds; ``serve_ms_per_token_p95`` the 95th
percentile, over those replies, of client-side seconds from send to reply
over the reply's new tokens, a failed request counting as the whole window
a token.
"""
import gc
import threading
import time

import numpy as np

from ..harness import compare
from ..harness.tracing import Slice
from .train_steps import composite_sites, memory_peak

KV_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}


def log_uniform(rng, lo, hi, n):
    return np.clip(np.rint(np.exp(rng.uniform(
        np.log(lo), np.log(hi), n))), lo, hi).astype(np.int64)


def make_requests(traffic, vocab, seed):
    """The seed's requests: ``(prompt ids, new tokens)``, the traffic's own
    sizes in the seed's order."""
    shapes = np.random.default_rng(traffic["shape_seed"])
    n = traffic["n_requests"]
    prompt_len = log_uniform(shapes, traffic["prompt_min"],
                             traffic["prompt_max"], n)
    new_tokens = log_uniform(shapes, traffic["new_min"],
                             traffic["new_max"], n)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    return [(rng.integers(1, vocab, int(prompt_len[i]), dtype=np.int32),
             int(new_tokens[i])) for i in order]


def warm(server, traffic):
    """Every shape the traffic can make, through the engine's own calls,
    so that arrival timing cannot leave one out: each prefill of 1 to
    ``warm_rows_max`` requests admitted together in each length bucket
    (with its greedy pick and its scatter into the pool), and the decode
    step. The callers start ``caller_start_gap_s`` apart, so that no more
    than a few requests are ever admitted together. Returns the seconds
    each admission took, by length bucket."""
    from paddle_tpu.models.generation import length_bucket
    from paddle_tpu.serving.batching import GenerationRequest
    eng = server.gen_engine
    gen, slots = eng.gen, eng.slots
    buckets = sorted({length_bucket(n, gen.bucket_min) for n in range(
        traffic["prompt_min"], traffic["prompt_max"] + 1)})
    seconds = {}
    for length in buckets:
        for rows in range(1, min(slots, traffic["warm_rows_max"]) + 1):
            reqs = [GenerationRequest(np.ones(length, np.int32),
                                      max_new_tokens=traffic["new_min"])
                    for _ in range(rows)]
            t0 = time.perf_counter()
            eng.admit(reqs, list(range(rows)))
            for slot in range(rows):
                eng.release_slot(slot)
            seconds.setdefault(length, []).append(
                round(time.perf_counter() - t0, 2))
    zeros = np.zeros(slots, np.int32)
    eng.step(zeros, zeros, np.zeros(slots, np.float32), zeros)
    if eng.pool.blocks_in_use():
        raise RuntimeError("warm-up left blocks in use")
    return seconds


class Callers:
    def __init__(self, endpoint, requests, n, start_gap_s):
        self.endpoint, self.requests = endpoint, requests
        self.start_gap_s = start_gap_s
        self.records, self.first_reply = [], [False] * n
        self.stop = threading.Event()
        self._next, self._lock = 0, threading.Lock()
        self.threads = [threading.Thread(target=self._call, args=(i,),
                                         daemon=True) for i in range(n)]

    def start(self):
        for t in self.threads:
            t.start()
            time.sleep(self.start_gap_s)

    def _take(self):
        with self._lock:
            k = self._next
            self._next += 1
        return self.requests[k % len(self.requests)]

    def _call(self, who):
        from paddle_tpu.serving import Client
        with Client(self.endpoint) as client:
            while not self.stop.is_set():
                prompt, new_tokens = self._take()
                rec = {"prompt": prompt, "prompt_len": int(prompt.size),
                       "new_tokens": new_tokens, "tokens": None,
                       "t_send": time.perf_counter()}
                try:
                    rec["tokens"] = client.generate(
                        prompt, max_new_tokens=new_tokens)
                    rec["ok"] = True
                except Exception as exc:  # noqa: BLE001 — counted as failed
                    rec["ok"], rec["error"] = False, repr(exc)[:300]
                rec["t_reply"] = time.perf_counter()
                if self.stop.is_set() and not rec["ok"]:
                    return          # the shutdown's own error, not traffic's
                self.records.append(rec)
                self.first_reply[who] = True


def sample_replies(records, seed, n):
    """``n`` of the window's replies drawn from the seed, the longest
    among them."""
    good = [r for r in records if r["ok"]]
    if not good:
        return []
    longest = max(range(len(good)), key=lambda i: (
        good[i]["prompt_len"] + good[i]["new_tokens"]))
    rest = [i for i in range(len(good)) if i != longest]
    picks = np.random.default_rng(seed).permutation(rest)[:n - 1]
    return [good[longest]] + [good[i] for i in picks]


def most_sent_together(records, within_s=0.1):
    """The most requests sent within ``within_s`` of one another: the
    callers whose replies one decode step ended, whom the server admits
    as one prefill."""
    sent = sorted(r["t_send"] for r in records)
    most, lo = 0, 0
    for hi, t in enumerate(sent):
        while t - sent[lo] > within_s:
            lo += 1
        most = max(most, hi - lo + 1)
    return most


def malformed(records, vocab):
    return sum(1 for r in records if r["ok"] and not (
        r["tokens"].shape == (r["new_tokens"],)
        and np.all((r["tokens"] >= 0) & (r["tokens"] < vocab))))


def sizes_of(run):
    """The configuration's sizes, the traffic's parameters and the
    ``serve`` group, at the rehearsal's sizes in a rehearsal."""
    cell = run.cell
    traffic, serve = dict(cell.traffic), dict(cell.config["serve"])
    if run.rehearsal:
        traffic.update(traffic["rehearsal"])
        serve.update(cell.config["rehearsal"]["serve"])
    if not serve["kv_paged"]:
        raise ValueError("this driver serves over the paged pool")
    return cell.family.Sizes(cell.config, run.rehearsal), traffic, serve


def run(run):
    from paddle_tpu.serving import InferenceServer
    fam = run.cell.family
    sz, traffic, serve = sizes_of(run)

    phases, t_phase = {}, run.t0

    def phase(name):
        nonlocal t_phase
        now = time.perf_counter()
        phases[name], t_phase = now - t_phase, now

    gen = fam.build_generator(sz, serve, run.seed)
    phase("weights_s")
    server = InferenceServer(generator=gen, kv_paged=True,
                             decode_slots=serve["decode_slots"])
    phases["warm_admit_s"] = warm(server, traffic)
    phase("warm_s")
    server.start()
    callers = Callers(server.endpoint,
                      make_requests(traffic, sz.vocab_size, run.seed),
                      traffic["callers"], traffic["caller_start_gap_s"])
    callers.start()
    try:
        while not all(callers.first_reply):
            if not all(t.is_alive() for t in callers.threads):
                raise RuntimeError("a caller died during the ramp")
            time.sleep(0.05)

        # ---- the window
        phase("ramp_s")
        before = server.stats()
        t_open = time.perf_counter()
        setup_s = t_open - run.t0
        time.sleep(run.seconds)
        t_close = time.perf_counter()
        after = server.stats()

        traced = None
        if run.trace:
            with Slice(run.root, run.keep_xplane) as traced:
                time.sleep(traffic["traced_seconds"])
            # the replies under way in the slice have to land, for the
            # records to hold all that its rows read; no caller sends
            # another, so the bank drains
            callers.stop.set()
            for t in callers.threads:
                t.join(timeout=120)
    finally:
        callers.stop.set()
        stopper = threading.Thread(target=server.stop, daemon=True)
        stopper.start()
        stopper.join(timeout=120)
        for t in callers.threads:
            t.join(timeout=30)
    # the window's numbers are taken by now: a slow shutdown is noted and
    # does not void them (every thread is a daemon and ends with the run)
    hung = stopper.is_alive() or any(t.is_alive() for t in callers.threads)

    window_s = t_close - t_open
    records = list(callers.records)
    inside = [r for r in records if t_open <= r["t_reply"] <= t_close]
    good = [r for r in inside if r["ok"]]
    tokens_per_s = sum(r["new_tokens"] for r in good) / window_s
    per_token = [1e3 * (r["t_reply"] - r["t_send"]) / r["new_tokens"]
                 if r["ok"] else 1e3 * window_s for r in inside]
    p95 = float(np.percentile(per_token, 95)) if per_token else None

    peak = memory_peak()
    composite = composite_sites(run, ("flash_attention", "paged_attention"))
    picked = sample_replies(inside, run.seed, traffic["sample_replies"])
    rows = [(r["prompt"], r["tokens"]) for r in picked]
    bad = malformed(inside, sz.vocab_size)
    del server, gen
    gc.collect()
    # reading a trace holds the interpreter for seconds: only now, with
    # the server's threads stopped
    events = traced.read() if traced else None

    # ---- the reference runs once over each sampled prompt and its reply
    t_ref = time.perf_counter()
    gaps = fam.reference_served_gaps(
        sz, run.seed, rows, traffic["prompt_max"] + traffic["new_max"])
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    reference_s = time.perf_counter() - t_ref
    limits = traffic["limits"]
    checks = [
        compare.check("served_logit_gap", widest,
                      limits["served_logit_gap"]),
        compare.check("malformed_replies", bad, 0),
        compare.check("attention_composite_sites", composite, 0)]

    def delta(key):
        return after[key] - before[key]

    facts = {"family": fam, "sizes": sz, "peaks": run.peaks,
             "window_s": window_s, "records": inside,
             "kv_bytes": KV_BYTES[serve["kv_cache_dtype"]],
             "decode_steps": delta("decode_steps"),
             "decode_rows": delta("decode_rows"),
             "generator_recompiles": delta("compiles"),
             "slice": (traced.t0, traced.t1) if traced else None,
             "slice_records": records}
    return {"attempted": len(inside), "failed": len(inside) - len(good),
            "end_to_end": {"serve_tokens_per_s": tokens_per_s,
                           "serve_ms_per_token_p95": p95,
                           "setup_s": setup_s},
            "facts": facts, "events": events,
            "checks": checks, "memory_peak_bytes": peak,
            "notes": {"setup_phases": phases, "reference_s": reference_s,
                      "trace_read_s": traced.read_s if traced else None,
                      "compared_tokens": sum(g.size for g in gaps),
                      "replies_in_window": len(inside),
                      "most_sent_together": most_sent_together(
                          inside, 0.5 * window_s / max(
                              1, after["decode_steps"]
                              - before["decode_steps"])),
                      "brownout_level": after.get("brownout_level"),
                      "shutdown_hung": hung,
                      "errors": [r["error"] for r in inside
                                 if not r["ok"]][:3]}}


def calibrate(run, seeds):
    """One line a seed: the widest gap of the served tokens (the lower
    reading) and of the tokens that the reference puts first in the
    precision below the configuration's (the control), on the same sampled
    replies of a short window at the cell's own load. One server serves
    every seed, its weights swapped between them."""
    from paddle_tpu.serving import InferenceServer
    fam = run.cell.family
    sz, traffic, serve = sizes_of(run)
    gen = fam.build_generator(sz, serve, seeds[0])
    server = InferenceServer(generator=gen, kv_paged=True,
                             decode_slots=serve["decode_slots"])
    warm(server, traffic)
    server.start()
    pad_to = traffic["prompt_max"] + traffic["new_max"]
    try:
        for seed in seeds:
            params = fam.init_params(sz, seed)
            server.gen_engine.apply_params(
                {name: params[name] for name in gen._params})
            del params
            callers = Callers(server.endpoint, make_requests(
                traffic, sz.vocab_size, seed), traffic["callers"],
                traffic["caller_start_gap_s"])
            callers.start()
            while not all(callers.first_reply):
                time.sleep(0.05)
            t_open = time.perf_counter()
            time.sleep(run.seconds)
            t_close = time.perf_counter()
            callers.stop.set()
            for t in callers.threads:
                t.join(timeout=120)
            inside = [r for r in callers.records
                      if t_open <= r["t_reply"] <= t_close]
            rows = [(r["prompt"], r["tokens"]) for r in sample_replies(
                inside, seed, traffic["sample_replies"])]
            out = {"seed": seed, "replies": len(inside),
                   "failed": sum(1 for r in inside if not r["ok"]),
                   "compared_tokens": sum(t.size for _, t in rows)}
            for mode in ["highest"] + traffic["controls"]:
                gaps = fam.reference_served_gaps(sz, seed, rows, pad_to,
                                                 mode=mode)
                flat = np.concatenate(gaps)
                out["program" if mode == "highest" else mode] = {
                    "served_logit_gap": float(flat.max()),
                    "p99": float(np.percentile(flat, 99)),
                    "nonzero_share": float(np.mean(flat > 0))}
            yield out
    finally:
        server.stop()
