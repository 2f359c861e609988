"""The decode loop one step ahead of the host (serving/batching.py
``DecodeBatcher._step``, serving/engine.py ``dispatch_step`` /
``collect_step``, the decode executable that picks its own token):
replies are what ``GPTGenerator.generate`` gives each request alone, and
what the two-call loop (decode step, then a separate pick, each waited
for) drew from the same key chain; a row that ends while its next step is
in flight delivers nothing more and its slot serves the next request; the
counter says how often the loop ran ahead; a fault or a stall at the
chaos point fails the live rows typed; a swap and a restart wait for or
drop the step in flight. CPU, tiny GPT; nothing here is a timing."""
import itertools
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import resilience, serving
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.resilience import FaultInjected, WatchdogTimeout
from paddle_tpu.serving.batching import (DecodeBatcher, GenerationRequest,
                                         RequestQueue, SwapHandle)
from paddle_tpu.serving.metrics import ServingStats


@pytest.fixture(scope="module")
def tiny_gen():
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    return cfg, GPTGenerator(cfg, scope, max_len=64, bucket_min=8)


def _prompts(cfg, lens, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


_POOL_SEQ = itertools.count()      # every pool its own gauge series


def _bank(gen, slots, **kw):
    """An engine and a batcher over it whose rounds the test drives by
    hand (no loop thread), with their stats."""
    stats = ServingStats()
    engine = serving.GenerationEngine(
        gen, slots=slots, stats=stats,
        pool_name=f"ahead{next(_POOL_SEQ)}",
        kv_block_size=kw.pop("kv_block_size", None),
        prefix_cache=kw.pop("prefix_cache", None))
    batcher = DecodeBatcher(RequestQueue(max_depth=64), engine,
                            stats=stats, **kw)
    return engine, batcher, stats




def _rounds(batcher, until, limit=400):
    """Drive ``batcher._round`` on this thread until ``until()``."""
    for _ in range(limit):
        if until():
            return
        assert batcher._round(batcher._epoch, {})
    raise AssertionError("the loop did not get there")


def _serve(batcher, reqs, timeout=120, one_admission=False):
    """The loop thread over ``reqs``; ``one_admission`` queues them all
    before its first round."""
    if not one_admission:
        batcher.start()
    try:
        for r in reqs:
            batcher.queue.put(r)
        if one_admission:
            batcher.start()
        return [r.wait(timeout=timeout)[0] for r in reqs]
    finally:
        batcher.stop()


# ------------------------------------------------- (a) what generate gives

PICKS = {
    # which pick program the bank's vectors call for, and why its tokens
    # can be compared with generate() alone: argmax needs no key; top_k 1
    # leaves the full sampler one token to draw
    "greedy": dict(temperature=0.0, top_k=0),
    "full_sampler_top1": dict(temperature=0.7, top_k=1),
}


@pytest.mark.parametrize("pick", sorted(PICKS) + ["mixed"])
def test_eight_callers_get_what_generate_gives_each_alone(tiny_gen, pick):
    cfg, gen = tiny_gen
    lens = (5, 9, 12, 7, 4, 15, 6, 10)
    prompts = _prompts(cfg, lens, seed=11)
    news = [6 + 3 * (i % 4) for i in range(len(lens))]
    knobs = [PICKS[pick] if pick != "mixed"
             else PICKS[sorted(PICKS)[i % 2]] for i in range(len(lens))]
    want = [gen.generate([p], max_new_tokens=n, seed=0, **k)[0]
            for p, n, k in zip(prompts, news, knobs)]
    server = serving.InferenceServer(generator=gen, decode_slots=3)
    server.start(serve_network=False)
    got = [None] * len(lens)

    def call(i):
        for _ in range(2):      # twice: the slots are reused many times
            got[i] = server.submit_generate(
                prompts[i], max_new_tokens=news[i],
                **knobs[i]).wait(timeout=300)[0]

    try:
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(lens))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        stats = server.stats()
    finally:
        server.stop()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert stats["requests_failed"] == 0
    assert stats["decode_steps_ahead"] > 0
    assert stats["decode_free_slots"] == 3


@pytest.mark.parametrize("knobs", [
    dict(temperature=0.9, top_k=0), dict(temperature=1.1, top_k=5)],
    ids=["sort_free_sampler", "full_sampler"])
def test_a_seeded_sampled_request_draws_what_generate_draws(tiny_gen,
                                                            knobs):
    """One slot, one request, the engine's seed: the bank's shapes and
    its key chain are ``generate(seed=0)``'s, so the decode executable
    that picks inside draws what decode-then-pick drew."""
    cfg, gen = tiny_gen
    prompt = _prompts(cfg, (9,), seed=23)[0]
    want = gen.generate([prompt], max_new_tokens=12, seed=0, **knobs)[0]
    _, batcher, stats = _bank(gen, slots=1)
    got, = _serve(batcher, [GenerationRequest(prompt, max_new_tokens=12,
                                              **knobs)])
    np.testing.assert_array_equal(got, want)
    assert len(set(got.tolist())) > 1
    assert stats.counter("decode_steps_ahead") >= 9


def _two_call_loop(gen, reqs, slots, seed=0):
    """What the loop drew before the pick moved into the step: every
    request admitted together (the batcher hands out the highest slot
    first), then a decode call and a separate pick call a step, each
    waited for, on one key chain; a finished row's sampling knobs are
    zeroed before the next step."""
    import jax
    pool = gen.new_pool(slots, name=f"twocall{next(_POOL_SEQ)}")
    key = jax.random.PRNGKey(seed)
    slot_of = [slots - 1 - i for i in range(len(reqs))]
    tokens, pos_ids, last = gen._pack_prompts([r.prompt for r in reqs])
    temp = np.zeros(tokens.shape[0], np.float32)
    topk = np.zeros(tokens.shape[0], np.int32)
    for i, r in enumerate(reqs):
        temp[i], topk[i] = r.temperature, r.top_k
        pool.alloc(slot_of[i], int(r.prompt.size))
    logits, row_caches, key = gen._run_prefill(tokens, pos_ids, last, key,
                                               kv_dtype=pool.dtype)
    first, key = gen._run_sample(logits, temp, topk, key)
    pool.scatter_prefill(slot_of, row_caches, tokens.shape[1],
                         lengths=[int(r.prompt.size) for r in reqs])
    tok = np.zeros(slots, np.int32)
    pos = np.zeros(slots, np.int32)
    temp, topk = np.zeros(slots, np.float32), np.zeros(slots, np.int32)
    outs, live = [[] for _ in reqs], {}

    def deliver(i, t):
        r = reqs[i]
        if r.eos_id is not None and t == r.eos_id:
            return False
        outs[i].append(t)
        return len(outs[i]) < r.max_new_tokens

    for i, r in enumerate(reqs):
        s = slot_of[i]
        tok[s], pos[s] = int(np.asarray(first)[i]), r.prompt.size
        temp[s], topk[s] = r.temperature, r.top_k
        if deliver(i, int(tok[s])):
            live[s] = i
        else:
            temp[s], topk[s] = 0.0, 0
            pool.free_slot(s)
    while live:
        for s in live:
            pool.ensure(s, int(pos[s]))
        logits, key = gen._run_decode_paged(tok, pos, pool, key)
        picked, key = gen._run_sample(logits, temp, topk, key)
        picked = np.asarray(picked)
        for s, i in list(live.items()):
            pos[s] += 1
            tok[s] = picked[s]
            if not deliver(i, int(picked[s])):
                del live[s]
                temp[s], topk[s] = 0.0, 0
                pool.free_slot(s)
    return [np.asarray(o, np.int32) for o in outs]


def test_a_bank_of_seeded_rows_draws_what_the_two_call_loop_drew(tiny_gen):
    """Greedy, sort-free and top-k rows in one bank, ending at different
    steps (two on an ``eos_id``, found one step late): every row's
    tokens are the ones the decode call and the separate pick call drew
    from the same key chain, whichever pick program a step ran."""
    cfg, gen = tiny_gen
    prompts = _prompts(cfg, (6, 11, 8, 5, 13), seed=31)
    knobs = [dict(temperature=0.0, top_k=0), dict(temperature=0.8, top_k=0),
             dict(temperature=1.2, top_k=6), dict(temperature=1.0, top_k=0),
             dict(temperature=0.6, top_k=3)]
    news = [14, 9, 12, 5, 11]

    def requests(eos=None):
        return [GenerationRequest(p, max_new_tokens=n, eos_id=(
            eos[i] if eos else None), **k) for i, (p, n, k)
            in enumerate(zip(prompts, news, knobs))]

    free_run = _two_call_loop(gen, requests(), slots=6)
    # rows 1 and 2 end early, on a token they are known to draw
    eos = {1: int(free_run[1][4]), 2: int(free_run[2][6])}
    eos = [eos.get(i) for i in range(len(prompts))]
    want = _two_call_loop(gen, requests(eos), slots=6)
    assert len(want[1]) <= 4 and len(want[2]) <= 6
    _, batcher, stats = _bank(gen, slots=6)
    got = _serve(batcher, requests(eos), one_admission=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert stats.counter("decode_steps_ahead") >= 10


def test_step_warms_the_executable_the_loop_runs(tiny_gen):
    """``step(tokens, pos, temperature, top_k)`` is dispatch + collect of
    the loop's own executable and signature: after it, rounds compile
    nothing for decode."""
    cfg, gen = tiny_gen
    engine, batcher, stats = _bank(gen, slots=2)
    zeros = np.zeros(2, np.int32)
    out = engine.step(zeros, zeros, np.zeros(2, np.float32), zeros)
    assert out.dtype == np.int32 and out.shape == (2,)
    req = GenerationRequest(_prompts(cfg, (7,))[0], max_new_tokens=6)
    batcher.queue.put(req)
    _rounds(batcher, lambda: batcher._flight is not None)
    compiles = stats.counter("compiles")
    _rounds(batcher, req.done)
    assert stats.counter("compiles") == compiles
    np.testing.assert_array_equal(
        req.wait(1)[0], gen.generate([req.prompt], max_new_tokens=6)[0])


# ----------------------------------- (b), (c) a row ends one step behind

@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["plain", "prefix_cache"])
def test_a_row_that_ends_on_eos_with_its_next_step_in_flight(tiny_gen,
                                                             prefix_cache):
    """One slot, three queued requests, each ending on an ``eos_id`` in
    mid-generation: nothing past EOS is delivered, the step that was in
    flight is computed and dropped (one row a request), and the slot,
    reused at once, serves the next request what it would get alone.
    With the prefix cache on the three share a prompt prefix, so the
    second and third adopt the first's blocks and copy before they
    write."""
    cfg, gen = tiny_gen
    # three whole blocks of 4 shared, and a tail inside the fourth
    shared = _prompts(cfg, (12,), seed=41)[0]
    # a startup-initialised tiny GPT mostly repeats itself: take prompts
    # whose greedy reply turns to a new token after at least two, and
    # let that token be the request's EOS
    prompts, eos = [], []
    for seed in range(200):
        p = np.concatenate([shared, _prompts(cfg, (1 + seed % 3,), seed)[0]])
        full = gen.generate([p], max_new_tokens=16)[0].tolist()
        turns = [k for k in range(2, 15) if full[k] not in full[:k]]
        if turns:
            prompts.append(p)
            eos.append(full[turns[0]])
        if len(prompts) == 3:
            break
    want = [gen.generate([p], max_new_tokens=16, eos_id=e)[0]
            for p, e in zip(prompts, eos)]
    assert len(want) == 3 and all(2 <= len(w) < 15 for w in want)
    engine, batcher, stats = _bank(gen, slots=1, kv_block_size=4,
                                   prefix_cache=prefix_cache)
    reqs = [GenerationRequest(p, max_new_tokens=16, eos_id=e)
            for p, e in zip(prompts, eos)]
    for r in reqs:
        batcher.queue.put(r)
    _rounds(batcher, lambda: all(r.done() for r in reqs)
            and batcher._flight is None)
    got = [r.wait(1)[0] for r in reqs]
    for g, w, e in zip(got, want, eos):
        np.testing.assert_array_equal(g, w)
        assert e not in g.tolist()
    # a request's decode steps: its tokens after the first, the step
    # that drew EOS, and the one in flight behind it that was dropped
    useful = sum(len(w) - 1 + 1 for w in want)
    assert stats.counter("decode_rows") == useful + len(reqs)
    assert stats.counter("tokens_generated") == sum(len(w) for w in want)
    assert engine.pool.blocks_in_use() == 0
    assert sorted(batcher._free) == [0]
    if prefix_cache:
        assert sum(e["hits"] for e in engine.pool._prefix.values()) >= 2


ENDINGS = ["deadline", "abandoned", "budget"]


@pytest.mark.parametrize("ending", ENDINGS)
def test_a_row_that_ends_while_its_step_is_in_flight_is_dropped(tiny_gen,
                                                               ending):
    """Two rows; one ends between a step's send and its reading (its
    deadline passes; its waiter gives up), or is known to end by its own
    ``max_new_tokens`` before the step is read: the other row's tokens
    are untouched, the slot comes back, and the late row gets nothing
    from the step that was in flight."""
    cfg, gen = tiny_gen
    prompts = _prompts(cfg, (8, 6), seed=47)
    want = gen.generate([prompts[0]], max_new_tokens=10)[0]
    engine, batcher, stats = _bank(gen, slots=2)
    keeper = GenerationRequest(prompts[0], max_new_tokens=10)
    other = GenerationRequest(
        prompts[1], max_new_tokens=3 if ending == "budget" else 30,
        deadline_ms=60_000.0 if ending == "deadline" else None)
    batcher.queue.put(keeper)
    batcher.queue.put(other)
    _rounds(batcher, lambda: batcher._flight is not None
            and len(other.out_tokens) == 2)
    assert other.slot in batcher._flight.rows
    had = list(other.out_tokens)
    if ending == "deadline":
        other.deadline_at = time.monotonic() - 1.0
    elif ending == "abandoned":
        other.set_error(serving.RequestCancelledError("gave up"))
    _rounds(batcher, lambda: other.slot not in batcher._active)
    if ending == "budget":
        # due from the step in flight: it took no part in the next one
        np.testing.assert_array_equal(
            other.wait(1)[0],
            gen.generate([prompts[1]], max_new_tokens=3)[0])
        # read so far: the two steps that drew its second and third
        # token, with the keeper's row in each; the keeper's next step
        # flies alone
        assert stats.counter("decode_rows") == 2 + 2
        assert list(batcher._flight.rows) == [keeper.slot]
        assert stats.counter("decode_steps_ahead") >= 1
    else:
        assert other.out_tokens == had
        err = serving.DeadlineExceededError if ending == "deadline" \
            else serving.RequestCancelledError
        with pytest.raises(err):
            other.wait(1)
    _rounds(batcher, keeper.done)
    np.testing.assert_array_equal(keeper.wait(1)[0], want)
    assert sorted(batcher._free) == [0, 1]
    assert engine.pool.blocks_in_use() == 0
    assert batcher._flight is None


# --------------------------------------------- (d) how often it runs ahead

def test_steady_load_runs_ahead_and_drafting_rounds_never(tiny_gen):
    cfg, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12, 7, 4, 15, 6, 10), seed=53)
    server = serving.InferenceServer(generator=gen, decode_slots=4)
    server.start(serve_network=False)

    replies = []

    def call(i):
        for _ in range(3):
            replies.append(server.submit_generate(
                prompts[i], max_new_tokens=40).wait(300)[0])

    try:
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        stats = server.stats()
    finally:
        server.stop()
    assert len(replies) == 24 and stats["requests_failed"] == 0
    assert stats["decode_steps_ahead"] / stats["decode_steps"] > 0.9

    engine, batcher, stats = _bank(gen, slots=2, spec_k=2)
    reqs = [GenerationRequest(p, max_new_tokens=12) for p in prompts[:3]]
    got = _serve(batcher, reqs)
    for g, p in zip(got, prompts):
        np.testing.assert_array_equal(
            g, gen.generate([p], max_new_tokens=12)[0])
    assert stats.counter("decode_steps") > 0
    assert stats.counter("decode_steps_ahead") == 0


def test_a_round_with_a_chunked_prefill_reads_its_step_in_place(tiny_gen):
    """While a prompt is ingested chunk by chunk the rounds read their
    step in place, and the first step after is not ahead either."""
    cfg, gen = tiny_gen
    prompts = _prompts(cfg, (6, 20), seed=59)
    want = [gen.generate([p], max_new_tokens=14)[0] for p in prompts]
    flags = fluid.get_flags(["FLAGS_prefill_chunk_tokens"])
    fluid.set_flags({"prefill_chunk_tokens": 4})
    try:
        engine, batcher, stats = _bank(gen, slots=2)
        first = GenerationRequest(prompts[0], max_new_tokens=14)
        batcher.queue.put(first)
        _rounds(batcher, lambda: len(first.out_tokens) >= 3)
        ahead = stats.counter("decode_steps_ahead")
        second = GenerationRequest(prompts[1], max_new_tokens=14)
        batcher.queue.put(second)
        _rounds(batcher, lambda: bool(batcher._prefilling))
        while batcher._prefilling:
            assert batcher._round(batcher._epoch, {})
            assert batcher._flight is None      # read in place
        assert stats.counter("decode_steps_ahead") == ahead
        _rounds(batcher, lambda: first.done() and second.done())
        assert stats.counter("decode_steps_ahead") > ahead
    finally:
        fluid.set_flags(flags)
    for r, w in zip((first, second), want):
        np.testing.assert_array_equal(r.wait(1)[0], w)


# --------------------------------------- (e) a fault, a stall, at the wait

@pytest.mark.parametrize("what", ["fault", "stall"])
def test_a_failed_wait_fails_the_live_rows_and_the_next_is_served(
        tiny_gen, what):
    """The chaos point stands where the loop waits for the chip. A fault
    there, or a stall past the loop's budget (WatchdogTimeout, from a
    clock and no thread), fails the rows of the step that was waited for
    and, the pool having gone with it, the step sent behind it; a
    request still queued is untouched and served right after."""
    cfg, gen = tiny_gen
    prompts = _prompts(cfg, (7, 9, 5), seed=61)
    want = gen.generate([prompts[2]], max_new_tokens=8)[0]
    engine, batcher, stats = _bank(gen, slots=2, watchdog_s=0.25)
    live = [GenerationRequest(p, max_new_tokens=30) for p in prompts[:2]]
    for r in live:
        batcher.queue.put(r)
    _rounds(batcher, lambda: batcher._flight is not None
            and all(len(r.out_tokens) >= 3 for r in live))
    queued = GenerationRequest(prompts[2], max_new_tokens=8)
    batcher.queue.put(queued)       # no slot is free: it stays queued
    threads = threading.active_count()
    chaos = dict(exc=FaultInjected) if what == "fault" \
        else dict(delay=0.6)
    with resilience.chaos("serving.decode_step", p=1.0, times=1, **chaos):
        assert batcher._round(batcher._epoch, {})
    assert threading.active_count() == threads      # no thread a step
    err = FaultInjected if what == "fault" else WatchdogTimeout
    for r in live:
        with pytest.raises(err):
            r.wait(1)
    assert engine.bank_lost and batcher._flight is None
    assert not batcher._active and sorted(batcher._free) == [0, 1]
    assert stats.counter("engine_failures") == 1
    assert stats.counter("watchdog_timeouts") == (what == "stall")
    assert stats.counter("requests_failed") == 2
    assert not queued.done()
    _rounds(batcher, queued.done)
    np.testing.assert_array_equal(queued.wait(1)[0], want)
    assert not engine.bank_lost and batcher.consecutive_failures == 0
    assert engine.pool.blocks_in_use() == 0


def test_a_row_admitted_behind_a_failed_step_fails_with_the_bank(tiny_gen):
    """A row that joined after the failed step was sent was not in it:
    it fails typed all the same, its cache having gone with the pool."""
    cfg, gen = tiny_gen
    prompts = _prompts(cfg, (7, 9), seed=67)
    engine, batcher, stats = _bank(gen, slots=2, watchdog_s=0)
    first = GenerationRequest(prompts[0], max_new_tokens=30)
    batcher.queue.put(first)
    _rounds(batcher, lambda: batcher._flight is not None)
    late = GenerationRequest(prompts[1], max_new_tokens=30)
    batcher.queue.put(late)
    with resilience.chaos("serving.decode_step", p=1.0, times=1):
        assert batcher._round(batcher._epoch, {})   # admits, sends, waits
    with pytest.raises(FaultInjected):
        first.wait(1)
    with pytest.raises(serving.ServingError, match="slot bank lost"):
        late.wait(1)
    assert sorted(batcher._free) == [0, 1] and batcher._flight is None
    assert engine.pool.blocks_in_use() == 0


# ------------------------- (f) a swap and a restart, with a step in flight

def test_a_pending_swap_waits_for_the_step_in_flight(tiny_gen):
    cfg, gen = tiny_gen
    prompt = _prompts(cfg, (8,), seed=71)[0]
    want = gen.generate([prompt], max_new_tokens=9)[0]
    engine, batcher, stats = _bank(gen, slots=2)
    req = GenerationRequest(prompt, max_new_tokens=9)
    batcher.queue.put(req)
    _rounds(batcher, lambda: batcher._flight is not None)
    seen = []
    batcher._swap = SwapHandle(lambda: seen.append(
        (batcher._flight, dict(batcher._active), req.done())))
    behind = GenerationRequest(prompt, max_new_tokens=9)
    batcher.queue.put(behind)       # admission is paused meanwhile
    _rounds(batcher, lambda: bool(seen))
    assert seen == [(None, {}, True)]
    assert batcher._swap is None and not behind.done()
    np.testing.assert_array_equal(req.wait(1)[0], want)
    _rounds(batcher, behind.done)
    np.testing.assert_array_equal(behind.wait(1)[0], want)


def test_a_row_deadline_with_a_swap_pending_still_lands_the_flight(
        tiny_gen):
    """Every row of the step in flight ends before it is read: the loop
    reads it (nobody is left to deliver to) and only then swaps."""
    cfg, gen = tiny_gen
    engine, batcher, stats = _bank(gen, slots=1)
    req = GenerationRequest(_prompts(cfg, (8,), seed=73)[0],
                            max_new_tokens=30, deadline_ms=60_000.0)
    batcher.queue.put(req)
    _rounds(batcher, lambda: batcher._flight is not None)
    applied = []
    batcher._swap = SwapHandle(lambda: applied.append(batcher._flight))
    req.deadline_at = time.monotonic() - 1.0
    _rounds(batcher, lambda: bool(applied))
    assert applied == [None] and not batcher._active
    with pytest.raises(serving.DeadlineExceededError):
        req.wait(1)
    assert engine.pool.blocks_in_use() == 0


def test_restart_drops_the_step_in_flight(tiny_gen):
    cfg, gen = tiny_gen
    prompts = _prompts(cfg, (8, 6), seed=79)
    want = gen.generate([prompts[1]], max_new_tokens=7)[0]
    engine, batcher, stats = _bank(gen, slots=2)
    lost = GenerationRequest(prompts[0], max_new_tokens=30)
    batcher.queue.put(lost)
    _rounds(batcher, lambda: batcher._flight is not None)
    batcher.restart("test")
    try:
        with pytest.raises(serving.ServingError, match="restarted"):
            lost.wait(1)
        assert batcher._flight is None or batcher._active
        after = GenerationRequest(prompts[1], max_new_tokens=7)
        batcher.queue.put(after)
        np.testing.assert_array_equal(after.wait(120)[0], want)
    finally:
        batcher.stop()
    assert sorted(batcher._free) == [0, 1]
    assert engine.pool.blocks_in_use() == 0


def test_stop_with_a_step_in_flight_fails_its_rows_typed(tiny_gen):
    cfg, gen = tiny_gen
    engine, batcher, stats = _bank(gen, slots=2)
    req = GenerationRequest(_prompts(cfg, (8,), seed=83)[0],
                            max_new_tokens=40)
    batcher.start()
    # a short stall a step: 40 steps of a tiny model would be over before
    # this thread looked twice
    with resilience.chaos("serving.decode_step", p=1.0, delay=0.01):
        try:
            batcher.queue.put(req)
            deadline = time.monotonic() + 120
            while len(req.out_tokens) < 3 and time.monotonic() < deadline:
                time.sleep(0.005)
        finally:
            batcher.stop()
    with pytest.raises(serving.ServerShutdownError):
        req.wait(1)
    assert batcher._flight is None and not batcher._active
    assert engine.pool.blocks_in_use() == 0
