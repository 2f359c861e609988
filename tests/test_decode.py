"""KV-cached autoregressive decoding (models/gpt.py cache graphs +
models/generation.GPTGenerator + the serving decode batching): greedy
prefill+decode must be token-for-token identical to naive full-forward
argmax generation, prefill logits must match the full forward at
tolerance, the cache must honor its shape/position invariants, sampling
must be seed-deterministic, and the serving decode bank must reuse
slots as rows finish."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator, length_bucket

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_gen():
    """One initialized tiny-GPT parameter scope + generator per module
    (param init dominates; every test reuses the compiled executables
    through the generator's cache)."""
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    gen = GPTGenerator(cfg, scope, max_len=48, bucket_min=8)
    return cfg, scope, gen


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def test_greedy_parity_kv_vs_full_recompute(tiny_gen):
    """Greedy generate() (prefill + cached decode steps) must be
    token-for-token identical to naive full-forward argmax generation,
    across ragged prompt lengths in one batch."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12))
    kv = gen.generate(prompts, max_new_tokens=14, seed=0)
    naive = gen.generate_naive(prompts, max_new_tokens=14, seed=0)
    for a, b in zip(kv, naive):
        np.testing.assert_array_equal(a, b)
        assert a.dtype == np.int32 and a.shape == (14,)


def test_greedy_first_token_matches_executor_forward(tiny_gen):
    """The first generated token equals argmax of the full-sequence
    eval program run through the plain Executor — ties the fast path to
    the framework's reference forward, not just to generate_naive."""
    cfg, scope, gen = tiny_gen
    prompts = _prompts(cfg, (7,), seed=11)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = gpt.gpt_logits(cfg)
    exe = fluid.Executor()
    s = int(prompts[0].size)
    feed = {"tokens": prompts[0][None, :],
            "pos_ids": np.arange(s, dtype=np.int32)[None, :],
            "last_pos": np.array([s - 1], np.int32)}
    with fluid.scope_guard(scope):
        logits, = exe.run(main, feed=feed, fetch_list=[out["logits"]])
    want = int(np.argmax(np.asarray(logits)[0]))
    got = gen.generate(prompts, max_new_tokens=1, seed=0)
    assert int(got[0][0]) == want


def test_prefill_logits_parity_across_buckets(tiny_gen):
    """Bucketed prefill (which also hands back every layer's keys and
    values, at the bucket's length) must produce the same next-token
    logits as the cache-free full forward at the same bucket, and
    padding to a LARGER bucket must not change them beyond tolerance
    (padded keys are causally masked)."""
    cfg, _, gen = tiny_gen
    import jax
    key = jax.random.PRNGKey(0)
    prompt = _prompts(cfg, (9,), seed=5)[0]
    for bucket in (16, 32):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :prompt.size] = prompt
        pos_ids = np.arange(bucket, dtype=np.int32)[None, :]
        last = np.array([prompt.size - 1], np.int32)
        pf_logits, caches, _ = gen._run_prefill(toks, pos_ids, last, key)
        full_logits, _ = gen._run_logits(toks, pos_ids, last, key)
        np.testing.assert_allclose(np.asarray(pf_logits),
                                   np.asarray(full_logits),
                                   rtol=1e-5, atol=1e-6)
        d_head = cfg.hidden_size // cfg.num_heads
        for i in range(cfg.num_layers):
            assert caches[f"cache_k_{i}"].shape == \
                (1, cfg.num_heads, bucket, d_head)


# ---------------------------------------------------------------------------
# cache invariants
# ---------------------------------------------------------------------------

def test_kv_cache_write_position_invariants(tiny_gen):
    """A paged decode step (``decode_paged_fp32``) must change each
    row's keys and values ONLY at that row's own position — the block
    its table names for it, at the offset inside — and nowhere else in
    the pool; prefill row caches are [B, H, bucket, D]."""
    cfg, _, gen = tiny_gen
    import jax
    key = jax.random.PRNGKey(1)
    prompts = _prompts(cfg, (5, 9), seed=7)
    bucket = 16
    toks = np.zeros((2, bucket), np.int32)
    for r, p in enumerate(prompts):
        toks[r, :p.size] = p
    pos_ids = np.broadcast_to(np.arange(bucket, dtype=np.int32),
                              (2, bucket)).copy()
    last = np.array([4, 8], np.int32)
    _, caches, key = gen._run_prefill(toks, pos_ids, last, key)
    d_head = cfg.hidden_size // cfg.num_heads
    for a in caches.values():
        assert a.shape == (2, cfg.num_heads, bucket, d_head)

    pool = gen.new_pool(2, dtype="fp32", block_size=4, name="invariants")
    pos = np.array([5, 9], np.int32)          # per-row write positions
    tok = np.array([3, 4], np.int32)
    for r, p in enumerate(prompts):
        pool.alloc(r, int(p.size))
    pool.scatter_prefill([0, 1], caches, bucket,
                         lengths=[int(p.size) for p in prompts])
    for r in range(2):
        pool.ensure(r, int(pos[r]))
    names = list(pool.arrays())
    before = {n: pool.logical(n) for n in names}
    gen._run_decode_paged(tok, pos, pool, key)
    assert len(names) == 2 * cfg.num_layers
    for n in names:
        b = pool.logical(n)                   # [blocks, H, block, D]
        assert b.shape == before[n].shape
        changed = np.any(before[n] != b, axis=(1, 3))   # [blocks, block]
        want = np.zeros_like(changed)
        for r, p in enumerate(pos):
            want[pool.tables[r, p // 4], p % 4] = True
        np.testing.assert_array_equal(changed, want, err_msg=n)


def test_generate_rejects_overlong_prompt(tiny_gen):
    cfg, _, gen = tiny_gen
    with pytest.raises(ValueError):
        gen.generate(_prompts(cfg, (40,)), max_new_tokens=20)
    with pytest.raises(ValueError):
        gen.generate([np.zeros((0,), np.int32)], max_new_tokens=4)


@pytest.mark.parametrize("n,lo,want", [
    (1, 1, 1), (3, 1, 4), (17, 16, 32), (1024, 16, 1024),
    (1025, 16, 2048), (2048, 16, 2048), (2049, 16, 3072),
    (3073, 16, 4096), (4097, 16, 6144), (6144, 16, 6144),
    (6145, 16, 8192), (8193, 16, 12288), (5, 3000, 3000)])
def test_length_bucket_ladder(n, lo, want):
    """Powers of two, and from 2048 up their midpoints too: a prompt of
    4,097 tokens pads to 6,144 and not to 8,192, and no bucket under
    2,048 (every GPT shape the tests and the benchmark warm) moved."""
    assert length_bucket(n, lo) == want


def test_generate_accepts_bare_prompt(tiny_gen):
    """A bare 1-D array (or flat list of ints) is ONE prompt — the shape
    the serving Client takes — not a batch of one-token prompts."""
    cfg, _, gen = tiny_gen
    p = _prompts(cfg, (6,))[0]
    want = gen.generate([p], max_new_tokens=5, seed=0)
    for bare in (p, p.tolist()):
        got = gen.generate(bare, max_new_tokens=5, seed=0)
        assert len(got) == 1
        np.testing.assert_array_equal(got[0], want[0])


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_fixed_seed_determinism(tiny_gen):
    """Same seed -> bitwise-identical token sequences (the sample op
    draws from the framework RNG stream, advanced by the same
    split-chain as the executor); different seed -> different draw."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (6, 10))
    a = gen.generate(prompts, max_new_tokens=12, temperature=1.0,
                     top_k=8, seed=42)
    b = gen.generate(prompts, max_new_tokens=12, temperature=1.0,
                     top_k=8, seed=42)
    c = gen.generate(prompts, max_new_tokens=12, temperature=1.0,
                     top_k=8, seed=43)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))
    assert all(t < cfg.vocab_size for out in a for t in out)
    # temperature-only config takes the sort-free sampler variant and is
    # just as reproducible
    t1 = gen.generate(prompts, max_new_tokens=6, temperature=1.0, seed=7)
    t2 = gen.generate(prompts, max_new_tokens=6, temperature=1.0, seed=7)
    for x, y in zip(t1, t2):
        np.testing.assert_array_equal(x, y)


def test_top_k_one_equals_greedy(tiny_gen):
    """top_k=1 collapses sampling to argmax whatever the temperature —
    the sampler's filtering and the greedy branch agree."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (6, 10))
    g = gen.generate(prompts, max_new_tokens=8, temperature=0.0, seed=0)
    k1 = gen.generate(prompts, max_new_tokens=8, temperature=4.0,
                      top_k=1, seed=99)
    for x, y in zip(g, k1):
        np.testing.assert_array_equal(x, y)


def test_eos_stops_generation(tiny_gen):
    """eos_id truncates the output at (and excluding) the first
    occurrence, per row."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (6, 10))
    ref = gen.generate(prompts, max_new_tokens=10, seed=0)
    eos = int(ref[0][0])       # row 0 stops immediately with this eos
    out = gen.generate(prompts, max_new_tokens=10, seed=0, eos_id=eos)
    for r in range(2):
        full = ref[r]
        hits = np.nonzero(full == eos)[0]
        want = full[:hits[0]] if hits.size else full
        np.testing.assert_array_equal(out[r], want)


# ---------------------------------------------------------------------------
# serving decode bank
# ---------------------------------------------------------------------------

def test_decode_batcher_slot_reuse(tiny_gen):
    """More concurrent generation requests than decode slots: every
    request completes with the greedy reference output (rows join/leave
    the running batch between steps), slots are reused, and the stats
    surface the generation pipeline."""
    import threading
    from paddle_tpu import serving

    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12, 7, 4), seed=17)
    ref = gen.generate(prompts, max_new_tokens=9, seed=0)

    server = serving.InferenceServer(generator=gen, decode_slots=2)
    server.start(serve_network=False)
    try:
        reqs = [server.submit_generate(p, max_new_tokens=9)
                for p in prompts]
        outs = [r.wait(timeout=120)[0] for r in reqs]
        for got, want in zip(outs, ref):
            np.testing.assert_array_equal(got, want)
        st = server.stats()
        assert st["generate_requests"] == 5
        assert st["tokens_generated"] == 5 * 9
        assert st["decode_steps"] > 0
        assert 0.0 < st["decode_occupancy"] <= 1.0
        assert st["decode_free_slots"] == 2          # all slots returned
        assert st["prefill_count"] >= 1 and st["decode_count"] >= 1
        # the picks run inside the admission's and the decode step's
        # executables: no pick of its own, every admission one call
        assert st["sample_count"] == 0
        assert st["admissions"] == st["admissions_fused"] >= 1
        assert st["tokens_per_s"] > 0
    finally:
        server.stop()
    # a late request after stop is refused, not hung
    with pytest.raises(serving.ServerOverloadedError):
        server.submit_generate(prompts[0], max_new_tokens=4)


def test_generate_over_the_wire(tiny_gen):
    """Network path: Client.generate speaks the wire protocol and
    returns the greedy reference tokens; eos and deadline errors map to
    typed exceptions."""
    from paddle_tpu import serving

    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (6, 11), seed=23)
    ref = gen.generate(prompts, max_new_tokens=7, seed=0)
    server = serving.InferenceServer(generator=gen, decode_slots=4)
    server.start()
    try:
        with serving.Client(server.endpoint) as c:
            out = c.generate(prompts[0], max_new_tokens=7)
            np.testing.assert_array_equal(out, ref[0])
            # infer against a generation-only server is a clean error
            with pytest.raises(RuntimeError):
                c.infer({"x": np.zeros((1, 2), np.float32)})
    finally:
        server.stop()


def test_token_level_deadline_frees_slot(tiny_gen):
    """A row whose deadline lapses MID-GENERATION fails with a
    token-level DeadlineExceededError between decode steps and frees
    its slot (driven synchronously — no batcher thread — so the expiry
    point is deterministic)."""
    import time
    from paddle_tpu import serving
    from paddle_tpu.serving.batching import (DecodeBatcher,
                                             GenerationRequest,
                                             RequestQueue)

    cfg, _, gen = tiny_gen
    engine = serving.GenerationEngine(gen, slots=1)
    batcher = DecodeBatcher(RequestQueue(max_depth=8), engine)
    prompt = _prompts(cfg, (6,), seed=29)[0]
    req = GenerationRequest(prompt, max_new_tokens=40, deadline_ms=200.0)
    batcher.queue.put(req)
    batcher._admit()                 # prefill -> slot 0, first token out
    assert req.slot == 0 and not req.done()
    assert len(req.out_tokens) == 1
    time.sleep(0.25)                 # let the token budget lapse
    batcher._check_deadlines(time.monotonic())
    assert req.done()
    with pytest.raises(serving.DeadlineExceededError) as ei:
        req.wait(timeout=0.1)
    assert "token-level" in str(ei.value)
    assert batcher._free == [0]      # the slot is reusable


# ---------------------------------------------------------------------------
# what the prefill hands the pool
# ---------------------------------------------------------------------------

# what a prefill hands a pool of each dtype, and an element's bytes in
# the pool
ROW_DTYPES = {"bf16": "bfloat16", "fp32": "float32", "int8": "float32"}
POOL_BYTES = {"bf16": 2, "fp32": 4, "int8": 1}


def _forward_kv(cfg, scope, tokens, pos_ids, last):
    """Every layer's float32 k and v ``[B, H, S, D]`` as the cache-free
    full forward (``gpt_logits`` through the plain Executor) hands them
    to its attention: ``[(k, v)]`` a layer."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    attn = [op for op in main.global_block().ops
            if op.type == "flash_attention"]
    assert len(attn) == cfg.num_layers
    names = [op.input(slot)[0] for op in attn for slot in ("K", "V")]
    with fluid.scope_guard(scope):
        got = fluid.Executor().run(
            main, feed={"tokens": tokens, "pos_ids": pos_ids,
                        "last_pos": last}, fetch_list=names)
    got = [np.asarray(a) for a in got]
    assert all(a.dtype == np.float32 for a in got)
    return list(zip(got[0::2], got[1::2]))


@pytest.mark.parametrize("kv", ["bf16", "fp32", "int8"])
def test_prefill_hands_back_bucket_long_rows_in_the_pools_dtype(tiny_gen,
                                                                kv):
    """``cache_k_i``/``cache_v_i`` are ``[bb, H, bucket, D]`` (not
    ``max_len`` long) in the dtype the pool stores; an int8 pool, which
    quantizes in the scatter, is handed float32 rows by the fp32
    pool's program."""
    cfg, _, gen = tiny_gen
    import jax
    prompts = _prompts(cfg, (5, 9, 3), seed=41)
    tokens, pos_ids, last = gen._pack_prompts(prompts)
    assert tokens.shape == (4, 16) and gen.max_len == 48
    kind = gen.arch.prefill_kind(kv)
    assert kind == {"bf16": "prefill_bf16", "fp32": "prefill_fp32",
                    "int8": "prefill_fp32"}[kv]
    _, caches, _ = gen._run_prefill(tokens, pos_ids, last,
                                    jax.random.PRNGKey(0), kv_dtype=kv)
    d_head = cfg.hidden_size // cfg.num_heads
    assert sorted(caches) == sorted(
        f"cache_{c}_{i}" for c in "kv" for i in range(cfg.num_layers))
    for a in caches.values():
        assert a.shape == (4, cfg.num_heads, 16, d_head)
        assert str(a.dtype) == ROW_DTYPES[kv]
    assert "prefill" not in gen._progs       # no dtype-blind kind left


@pytest.mark.parametrize("kv", ["bf16", "fp32", "int8"])
def test_pool_after_scatter_is_the_forwards_kv_bit_for_bit(tiny_gen, kv):
    """After prefill and ``scatter_prefill`` every allocated block
    holds, bit for bit, the full forward's float32 k and v cast (bf16),
    kept (fp32) or quantized (int8) the pool's way; prompts shorter
    than their bucket: the padding's blocks land in the trash block and
    no other block is written."""
    cfg, scope, gen = tiny_gen
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import quantize_kv
    prompts = _prompts(cfg, (5, 9), seed=43)
    lens = [int(p.size) for p in prompts]
    tokens, pos_ids, last = gen._pack_prompts(prompts)
    bucket, bs = tokens.shape[1], 4
    assert bucket == 16
    want = _forward_kv(cfg, scope, tokens, pos_ids, last)
    pool = gen.new_pool(2, dtype=kv, block_size=bs, name=f"bits_{kv}")
    for r, n in enumerate(lens):
        pool.alloc(r, n)
    _, caches, _ = gen._run_prefill(tokens, pos_ids, last,
                                    jax.random.PRNGKey(0), kv_dtype=kv)
    pool.scatter_prefill([0, 1], caches, bucket, lengths=lens)
    held = {r: pool.blocks_for_tokens(n) for r, n in enumerate(lens)}
    assert held == {0: 2, 1: 3}               # of the bucket's 4 blocks
    live = sorted(int(b) for r, n in held.items()
                  for b in pool.tables[r, :n])
    assert 0 not in live and len(set(live)) == 5
    for i, pair in enumerate(want):
        for c, full in zip("kv", pair):
            name = f"cache_p{c}_{i}"
            for r, n in held.items():
                ids = pool.tables[r, :n]
                # [H, n * bs, D] -> [n, H, bs, D]
                ref = full[r, :, :n * bs].reshape(
                    cfg.num_heads, n, bs, -1).transpose(1, 0, 2, 3)
                got = pool.logical(name, ids)
                if kv == "int8":
                    q, sc = jax.jit(quantize_kv)(jnp.asarray(ref))
                    np.testing.assert_array_equal(got, np.asarray(q))
                    np.testing.assert_array_equal(
                        pool.logical(f"cache_p{c}s_{i}", ids),
                        np.asarray(sc))
                else:
                    ref = np.asarray(jnp.asarray(ref).astype(got.dtype))
                    assert got.dtype == ref.dtype
                    np.testing.assert_array_equal(
                        got.view(np.uint8), ref.view(np.uint8))
            # nothing but the rows' own blocks and the trash was written
            rest = [b for b in range(1, pool.num_blocks) if b not in live]
            assert not np.any(pool.logical(name, rest))


def _generate_by_the_old_hand_over(gen, prompts, new_tokens, kv):
    """Greedy tokens of the hand-over this repo had before PR 37: the
    prefill's float32 k and v written at position 0 of zero-filled
    float32 ``[bb, H, max_len, D]`` row caches, which the scatter slices,
    casts or quantizes; then the paged decode step."""
    import jax
    import jax.numpy as jnp
    lens = [int(p.size) for p in prompts]
    tokens, pos_ids, last = gen._pack_prompts(prompts)
    bb, s = tokens.shape
    pool = gen.new_pool(bb, dtype=kv, name=f"old_{kv}")
    for r, n in enumerate(lens):
        pool.alloc(r, n)
    logits, rows, key = gen._run_prefill(
        tokens, pos_ids, last, jax.random.PRNGKey(0), kv_dtype="fp32")
    dense = {n: jnp.zeros(a.shape[:2] + (gen.max_len, a.shape[3]),
                          jnp.float32).at[:, :, :s].set(a)
             for n, a in rows.items()}
    pool.scatter_prefill(list(range(len(prompts))), dense, s, lengths=lens)
    zeros = np.zeros((bb,), np.float32), np.zeros((bb,), np.int32)
    pos = np.zeros((bb,), np.int32)
    pos[:len(lens)] = lens
    outs = []
    for _ in range(new_tokens):
        tok, key = gen._run_sample(logits, *zeros, key)
        outs.append(np.asarray(tok)[:len(lens)])
        for r in range(len(lens)):
            pool.ensure(r, int(pos[r]))
        logits, key = gen._run_decode_paged(tok, pos, pool, key)
        pos[:len(lens)] += 1
    return list(np.stack(outs, axis=1))


@pytest.mark.parametrize("kv", ["bf16", "fp32", "int8"])
def test_greedy_tokens_are_the_old_hand_overs(tiny_gen, kv):
    """``generate`` over a bf16, fp32 or int8 pool returns the tokens
    the float32 ``max_len``-long row caches gave; over fp32 those are
    ``generate_naive``'s."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12), seed=47)
    got = gen.generate(prompts, max_new_tokens=11, seed=0, kv_dtype=kv)
    want = _generate_by_the_old_hand_over(gen, prompts, 11, kv)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    if kv == "fp32":
        for a, b in zip(got, gen.generate_naive(prompts, max_new_tokens=11,
                                                seed=0)):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kv", ["bf16", "fp32", "int8"])
def test_two_requests_admitted_together_through_the_server(tiny_gen, kv):
    """Two requests waiting when the loop starts share one prefill: the
    replies are offline ``generate``'s, and the ``generator/prefill``
    span says what its call wrote into the pool: ``cache_bytes``, the
    bucket's rows x K and V x layers x the bucket's length x (hidden x
    the pool's element bytes, and an int8 pool's float32 scale a
    head)."""
    import time
    from paddle_tpu import flags, serving
    from paddle_tpu.observability import tracing

    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (6, 11), seed=53)
    ref = gen.generate(prompts, max_new_tokens=7, seed=0, kv_dtype=kv)
    before = flags.flag("kv_cache_dtype")
    flags.set_flags({"FLAGS_kv_cache_dtype": kv})
    try:
        server = serving.InferenceServer(generator=gen, decode_slots=2)
    finally:
        flags.set_flags({"FLAGS_kv_cache_dtype": before})
    assert server.gen_engine.pool.dtype == kv
    reqs = [server.submit_generate(p, max_new_tokens=7) for p in prompts]
    t0 = time.perf_counter()
    server.start(serve_network=False)
    try:
        outs = [r.wait(timeout=120)[0] for r in reqs]
    finally:
        server.stop()
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)
    prefills = [r[7] for r in tracing.loop_spans(t0, time.perf_counter())
                if r[0] == "generator/prefill"]
    assert [a["rows"] for a in prefills] == [2]
    assert prefills[0]["cache_bytes"] == \
        2 * 2 * cfg.num_layers * 16 * (
            cfg.hidden_size * POOL_BYTES[kv]
            + (4 * cfg.num_heads if kv == "int8" else 0))


# ---------------------------------------------------------------------------
# bench smoke
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_decode_smoke():
    """bench.py --config decode CPU smoke: completes, reports tokens/s
    for seq {128, 256}, and the KV path beats full recompute by the
    acceptance margin (>= 3x at seq 256)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--config",
         "decode"], capture_output=True, text=True, timeout=300,
        env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["unit"] == "tokens/sec"
    assert set(rec["seq"]) == {"128", "256"}
    assert rec["value"] > 0
    assert rec["seq"]["256"]["speedup_vs_full_recompute"] >= 3.0, rec
    # paged KV-pool rows: fp32 is bitwise-parity-gated inside the
    # bench; the quantized rows must be present with tokens/s
    assert set(rec["paged"]) == {"fp32", "bf16", "int8"}
    for row in rec["paged"].values():
        assert row["tokens_per_sec"] > 0
    assert rec["paged"]["fp32"]["greedy_match_vs_dense"] == 1.0
    # fixed-HBM concurrency acceptance: paged admits >= 2x dense slots
    # at max_len=2048 (also asserted inside bench_decode itself)
    fh = rec["fixed_hbm_concurrency"]
    assert fh["max_len"] == 2048
    assert fh["fp32"]["x_vs_dense"] >= 2.0, fh
    assert fh["int8"]["slots"] >= fh["fp32"]["slots"], fh
