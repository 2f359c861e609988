"""The hybrid block (``models/jamba.py``: Mamba-1 layers whose state
lives a slot in the pool beside the paged keys and values of the
attention layers) against the plain reference
(``benchmark/families/jamba.py``, which imports nothing of
``paddle_tpu``) on seeded weights at the rehearsal size: full-sequence
logits through ``Executor``, prefill then paged decode through the pool,
the state and the convolution's tail of a padded prompt, a prefill
against single-token steps, rows admitted and freed beside rows that
decode, an idle slot over 1,000 steps, faults that each have to fail a
tolerance, ``InferenceServer`` end to end, and the typed refusals."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.flags import set_flags
from paddle_tpu.kernels import _dispatch
from paddle_tpu.kernels import selective_scan as scan_kernel
from paddle_tpu.models import generation, jamba
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.serving.kvpool import adopt_decode_fetches, decode_feed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "jamba2-3b.json")) as fh:
    CONFIG = json.load(fh)

from benchmark.families import jamba as fam  # noqa: E402

SZ = fam.Sizes(CONFIG, rehearsal=True)
SEED = 36
MAMBA = SZ.mamba_layers

# float32 weights: program and reference differ by summation order and by
# XLA:CPU's default float32 product against precision=highest: measured
# 2e-7 to 5e-7 of logits of magnitude 1.5 through prefill and 24 decode
# steps. The faults below move a logit by 4e-3 (bfloat16 matrices) to 1
# (an inner norm left out), so 2e-5 holds the one and fails the others by
# a hundred times and more.
# bfloat16 weights: the same numbers, but every product rounds its
# activations to 8 mantissa bits (relative 4e-3) and the Mamba layers'
# outputs are ten times the embedding's scale: measured 1.2e-2 of logit
# through prefill and 24 decode steps over a bfloat16 cache; 3e-2 holds
# it and still fails an inner norm or the state's padding fault (3e-1 and
# more).
TOL = {"float32": 2e-5, "bfloat16": 3e-2}


def _tokens(rows, seq, seed=0):
    return np.random.default_rng(seed).integers(
        1, SZ.vocab_size, (rows, seq)).astype(np.int32)


def _cast(params, dtype):
    """The family rounds the matrices to bfloat16 once; float32 holds the
    same numbers exactly."""
    return {n: (a if a.dtype == jnp.float32 else a.astype(dtype))
            for n, a in params.items()}


def _generator(dtype="float32", max_len=64, bucket_min=None, **cfg_over):
    cfg = fam.program_config(SZ)
    cfg.dtype = dtype
    for key, value in cfg_over.items():
        setattr(cfg, key, value)
    params = fam.init_params(SZ, SEED)
    gen = GPTGenerator(cfg, fluid.Scope(), max_len=max_len,
                       bucket_min=bucket_min)
    gen.bind_params(_cast(params, dtype))
    return cfg, gen, params


def _executor_logits(cfg, params, toks, last):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = jamba.jamba_logits(cfg)
    exe, scope = fluid.Executor(), fluid.Scope()
    rows, seq = toks.shape
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (rows, seq)).copy()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for p in main.all_parameters():
            scope.set(p.name, np.asarray(_cast(params, cfg.dtype)[p.name]))
        return exe.run(
            main, feed={"tokens": toks, "pos_ids": pos, "last_pos": last},
            fetch_list=[out["logits"]])[0]


@pytest.fixture
def blocks_of_4():
    set_flags({"kv_block_size": 4})
    yield
    set_flags({"kv_block_size": 16})


@pytest.fixture
def chunks_of_8(monkeypatch):
    """The scan kernel's chunk at 8 tokens, so that a test's lengths lie
    at, under and over a chunk's edge."""
    monkeypatch.setattr(scan_kernel, "_CHUNK", 8)


def test_config_derives_its_layer_types_and_matches_the_source():
    cfg = jamba.JambaConfig()
    for key, value in CONFIG.items():
        if hasattr(cfg, key) and key not in ("name",):
            assert getattr(cfg, key) == value, key
    kinds = cfg.layers_block_type
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("mamba") == 26 and cfg.head_dim == 128 \
        and cfg.mamba_inner == 5120
    groups = cfg.serving().kv_groups()
    assert groups[0] == {"name": "full", "window": None, "layers": [0, 1]}
    assert groups[1]["layers"] == list(range(26)) and groups[1]["state"]
    assert groups[1]["arrays"] == {"c": ((15360,), "float32"),
                                   "s": ((16, 5120), "float32")}
    # a row's state: 389,120 B a layer, 10,117,120 B a row
    assert sum(np.prod(s) * 4 for s, _ in groups[1]["arrays"].values()) \
        == 389120
    for bad in ({"num_experts": 16}, {"mamba_proj_bias": True},
                {"tie_word_embeddings": False}, {"hidden_act": "gelu"}):
        with pytest.raises(ValueError):
            jamba.JambaConfig(**bad)


def test_full_sequence_logits_through_executor_match_reference():
    cfg = fam.program_config(SZ)
    cfg.dtype = "float32"
    params = fam.init_params(SZ, SEED)
    toks, last = _tokens(2, 40), np.array([39, 21], np.int32)
    logits = _executor_logits(cfg, params, toks, last)
    ref = np.asarray(fam.reference_logits(SZ, params, jnp.asarray(toks)))
    for r in range(2):
        np.testing.assert_allclose(logits[r], ref[r, last[r]], rtol=0,
                                   atol=TOL["float32"])
    assert np.abs(ref).max() > 0.5       # logits of magnitude 1


def _prefill_then_decode(gen, params, kv_dtype, steps=24, lens=(20, 13)):
    """Two prompts, then ``steps`` decode steps through the pool with
    blocks of 4: every step's logits against the reference's full forward
    pass over the same tokens. Returns the widest difference and the
    pool."""
    toks = _tokens(2, max(lens) + 1 + steps, seed=3)
    ref = np.asarray(fam.reference_logits(SZ, params, jnp.asarray(toks)))
    pool = gen.new_pool(2, dtype=kv_dtype, name="test")
    key = jax.random.PRNGKey(0)
    packed, pos_ids, last = gen._pack_prompts(
        [toks[r, :lens[r]] for r in range(2)])
    for r in range(2):
        pool.alloc(r, lens[r])
    logits, caches, key = gen._run_prefill(packed, pos_ids, last, key,
                                           kv_dtype=kv_dtype)
    pool.scatter_prefill([0, 1], caches, packed.shape[1], lengths=lens)
    pos, worst = np.asarray(lens, np.int32), 0.0
    for _ in range(steps):
        for r in range(2):
            worst = max(worst, float(np.abs(
                np.asarray(logits)[r] - ref[r, pos[r] - 1]).max()))
            pool.ensure(r, int(pos[r]))
        tok = np.array([toks[r, pos[r]] for r in range(2)], np.int32)
        fetches, key = gen._invoke(f"decode_paged_{kv_dtype}", "decode",
                                   decode_feed(pool, tok, pos), key)
        logits = adopt_decode_fetches(pool, fetches)
        pos = pos + 1
    return worst, pool


@pytest.mark.parametrize("dtype,kv_dtype,impl", [
    ("float32", "fp32", "xla"), ("float32", "fp32", "interpret"),
    ("bfloat16", "bf16", "xla")])
def test_prefill_then_paged_decode_matches_the_reference_forward(
        dtype, kv_dtype, impl, monkeypatch, blocks_of_4, chunks_of_8):
    """Prompts of 20 and 13 tokens in a bucket of 32 (the second ends
    inside a chunk of the scan), contexts to 44 positions past eleven
    blocks of 4 in the 2 attention layers, 4 Mamba layers' states carried
    a slot."""
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: impl)
    _, gen, params = _generator(dtype)
    worst, pool = _prefill_then_decode(gen, params, kv_dtype)
    assert worst <= TOL[dtype], worst
    assert pool.blocks_in_use_by_group() == {"full": 11 + 10}
    assert (pool.num_layers, pool.num_arrays, pool.state_layers) == (2, 2, 4)


def _end_of_bucket(monkeypatch):
    real = jamba.mamba_layer
    monkeypatch.setattr(
        jamba, "mamba_layer", lambda cfg, x, idx, length=None, state=None:
        real(cfg, x, idx, length=None, state=state))


def _no_norm(skip):
    real = jamba._norm
    return lambda cfg, x, name: x if skip(name) else real(cfg, x, name)


FAULTS = {
    "state_and_tail_taken_at_the_buckets_end": _end_of_bucket,
    "bf16_where_the_config_says_float32":
        lambda mp: {"dtype": "bfloat16"},
    "step_norm_left_out": lambda mp: mp.setattr(
        jamba, "_norm", _no_norm(lambda n: n.endswith("dt_norm"))),
    "input_map_norm_left_out": lambda mp: mp.setattr(
        jamba, "_norm", _no_norm(lambda n: n.endswith("b_norm"))),
    "output_map_norm_left_out": lambda mp: mp.setattr(
        jamba, "_norm", _no_norm(lambda n: n.endswith("c_norm"))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_tolerance(fault, monkeypatch, blocks_of_4):
    over = FAULTS[fault](monkeypatch) or {}
    _, gen, params = _generator(**over)
    worst, _ = _prefill_then_decode(gen, params, "fp32", steps=6)
    assert worst > 10 * TOL["float32"], worst


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("n", [1, 2, 7, 8, 9, 23])
def test_a_padded_prompt_gives_the_unpadded_prompts_state_tail_and_logits(
        n, impl, monkeypatch, chunks_of_8):
    """n tokens in a bucket of 32: n at, one under and one over a chunk's
    edge (8), n shorter than the convolution's tail (3), against the same
    program fed the n tokens alone."""
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: impl)
    _, gen, _ = _generator(bucket_min=32)
    prompt = _tokens(1, n, seed=n)[0]
    key = jax.random.PRNGKey(0)
    packed, pos_ids, last = gen._pack_prompts([prompt])
    assert packed.shape == (1, 32) and last[0] == n - 1
    logits, caches, _ = gen._run_prefill(packed, pos_ids, last, key,
                                         kv_dtype="fp32")
    # (a fed sequence of one token is the embedding look-up's [B, 1]
    # ids convention: two columns at the least)
    w = max(n, 2)
    alone, caches_alone, _ = gen._run_prefill(
        packed[:, :w], pos_ids[:, :w], last, key, kv_dtype="fp32")
    np.testing.assert_allclose(np.asarray(logits), np.asarray(alone),
                               rtol=0, atol=TOL["float32"])
    for m in range(MAMBA):
        for tag in ("c", "s"):
            name = f"cache_s{tag}_{m}"
            np.testing.assert_allclose(
                np.asarray(caches[name]), np.asarray(caches_alone[name]),
                rtol=0, atol=TOL["float32"], err_msg=name)
    inner = SZ.inner
    tail = np.asarray(caches["cache_sc_0"]).reshape(3, inner)
    # before the row's first token the tail holds zeros
    assert not tail[:max(3 - n, 0)].any() and tail[max(3 - n, 0):].any()


def _step(gen, pool, tok, pos, key):
    fetches, key = gen._invoke("decode_paged_fp32", "decode",
                               decode_feed(pool, tok, pos), key)
    return np.asarray(adopt_decode_fetches(pool, fetches)), key


def _admit(gen, pool, slot, prompt, key):
    packed, pos_ids, last = gen._pack_prompts([prompt])
    pool.free_slot(slot)
    pool.alloc(slot, prompt.size)
    logits, caches, key = gen._run_prefill(packed, pos_ids, last, key,
                                           kv_dtype="fp32")
    pool.scatter_prefill([slot], caches, packed.shape[1],
                         lengths=[prompt.size])
    return np.asarray(logits)[0], key


def test_a_prefill_of_n_tokens_equals_n_single_token_decode_steps(
        blocks_of_4):
    """The same 11 tokens through one prefill and through a prefill of
    the first and ten decode steps: the same states, tails and next
    logits."""
    _, gen, _ = _generator()
    prompt = _tokens(1, 11, seed=5)[0]
    key = jax.random.PRNGKey(0)
    whole = gen.new_pool(1, dtype="fp32", name="whole")
    want, key = _admit(gen, whole, 0, prompt, key)
    stepped = gen.new_pool(1, dtype="fp32", name="stepped")
    _, key = _admit(gen, stepped, 0, prompt[:1], key)
    for t in range(1, 11):
        stepped.ensure(0, t)
        got, key = _step(gen, stepped, prompt[t:t + 1],
                         np.array([t], np.int32), key)
    np.testing.assert_allclose(got[0], want, rtol=0, atol=TOL["float32"])
    for name in whole.state_arrays:
        np.testing.assert_allclose(
            np.asarray(stepped.arrays()[name]),
            np.asarray(whole.arrays()[name]), rtol=0, atol=TOL["float32"],
            err_msg=name)


def test_rows_come_and_go_beside_rows_that_decode(blocks_of_4):
    """A row admitted while another decodes leaves the other's logits
    bit-identical and gets the logits it gets alone; a slot freed and
    admitted again shows nothing of its last holder."""
    _, gen, _ = _generator()
    a, b, c = (_tokens(1, n, seed=n)[0] for n in (9, 14, 6))
    feed_a, feed_b = _tokens(1, 12, seed=77)[0], _tokens(1, 12, seed=78)[0]

    def run(with_b, b_slot_held_before=False):
        pool = gen.new_pool(2, dtype="fp32", name="bank")
        key = jax.random.PRNGKey(0)
        out = {"a": [], "b": []}
        if b_slot_held_before:
            # slot 1 held another row, which decoded and left
            _, key = _admit(gen, pool, 1, c, key)
            for t in range(3):
                pool.ensure(1, c.size + t)
                _, key = _step(gen, pool, np.array([0, 5 + t], np.int32),
                               np.array([0, c.size + t], np.int32), key)
            pool.free_slot(1)
        first_a, key = _admit(gen, pool, 0, a, key)
        out["a"].append(first_a)
        pos = np.array([a.size, 0], np.int32)
        tok = np.zeros(2, np.int32)
        live_b = False
        for t in range(8):
            if with_b and t == 3:
                first_b, key = _admit(gen, pool, 1, b, key)
                out["b"].append(first_b)
                pos[1], live_b = b.size, True
            pool.ensure(0, int(pos[0]))
            tok[0] = feed_a[t]
            if live_b:
                pool.ensure(1, int(pos[1]))
                tok[1] = feed_b[t]
            logits, key = _step(gen, pool, tok, pos, key)
            out["a"].append(logits[0])
            pos[0] += 1
            if live_b:
                out["b"].append(logits[1])
                pos[1] += 1
        return out, pool

    alone, _ = run(with_b=False)
    both, pool = run(with_b=True)
    assert len(both["b"]) == 6
    for got, want in zip(both["a"], alone["a"]):
        np.testing.assert_array_equal(got, want)
    reused, _ = run(with_b=True, b_slot_held_before=True)
    for got, want in zip(reused["b"], both["b"]):
        np.testing.assert_array_equal(got, want)
    # b alone in a bank of its own: the logits it got beside a
    solo = gen.new_pool(2, dtype="fp32", name="solo")
    key = jax.random.PRNGKey(0)
    first_b, key = _admit(gen, solo, 1, b, key)
    np.testing.assert_allclose(first_b, both["b"][0], rtol=0, atol=1e-6)
    pos, tok = np.array([0, b.size], np.int32), np.zeros(2, np.int32)
    for t in range(3, 8):
        solo.ensure(1, int(pos[1]))
        tok[1] = feed_b[t]
        logits, key = _step(gen, solo, tok, pos, key)
        np.testing.assert_allclose(logits[1], both["b"][t - 2], rtol=0,
                                   atol=1e-6)
        pos[1] += 1
    # freeing leaves the state where it lies; the blocks go back
    pool.free_slot(0)
    pool.free_slot(1)
    assert pool.blocks_in_use() == 0
    assert np.asarray(pool.arrays()["cache_ss_0"]).any()


def test_an_idle_slots_state_stays_finite_over_a_thousand_steps():
    """Slot 1 is never admitted: the bank's steps advance its state all
    the same, from stale tokens, and it neither grows nor dies into
    something that is not a number."""
    _, gen, _ = _generator()
    pool = gen.new_pool(2, dtype="fp32", name="idle")
    key = jax.random.PRNGKey(0)
    _, key = _admit(gen, pool, 0, _tokens(1, 5, seed=1)[0], key)
    rng = np.random.default_rng(0)
    pos = np.array([5, 0], np.int32)
    for t in range(1000):
        pool.ensure(0, int(pos[0]))
        tok = rng.integers(1, SZ.vocab_size, 2).astype(np.int32)
        logits, key = _step(gen, pool, tok, pos, key)
        pos[0] = 5 + (t % 50)           # slot 0 stays inside its 64
    assert np.isfinite(logits).all()
    for name in pool.state_arrays:
        held = np.asarray(pool.arrays()[name])
        assert np.isfinite(held).all(), name
        assert np.abs(held[1]).max() < 1e3, name


def test_inference_server_serves_it_and_its_spans_say_what_ran():
    """Through ``InferenceServer``'s own entry points: greedy replies
    are, teacher-forced through the reference, its own first choice at
    every position; the counters and spans this block adds move; the
    pool drains."""
    from paddle_tpu.observability import tracing
    _, gen, _ = _generator()
    prompts = [_tokens(1, n, seed=n)[0] for n in (21, 9, 30, 14)]
    server = serving.InferenceServer(generator=gen, decode_slots=2)
    t0 = time.perf_counter()
    server.start(serve_network=False)
    try:
        reqs = [server.submit_generate(p, max_new_tokens=12)
                for p in prompts]
        outs = [r.wait(timeout=300)[0] for r in reqs]
        stats = server.stats()
    finally:
        server.stop()
    gaps = fam.reference_served_gaps(SZ, SEED, list(zip(prompts, outs)), 48)
    assert max(float(g.max()) for g in gaps) <= 2 * TOL["float32"]
    assert stats["kv_cache_layers"] == 2
    assert stats["kvpool_state_layers"] == MAMBA
    assert stats["kvpool_state_bytes_per_slot"] == MAMBA * 4 * SZ.inner * (
        SZ.mamba_d_state + SZ.mamba_d_conv - 1)
    assert stats["kvpool_blocks_in_use"] == 0
    assert stats["kvpool_prefix_entries"] == 0
    rows = tracing.loop_spans(t0, time.perf_counter())
    sent = [r[7] for r in rows if r[0] == "engine/step"
            and "state_rows" in r[7]]
    prefills = [r[7] for r in rows if r[0] == "generator/prefill"]
    assert not [r for r in rows if r[0] == "pool/scatter"]
    assert sent and all(1 <= a["state_rows"] <= 2
                        and a["state_layers"] == MAMBA for a in sent)
    assert prefills and all(a["state_layers"] == MAMBA for a in prefills)
    assert sum(a["prompt_tokens"] for a in prefills) == 21 + 9 + 30 + 14
    assert all(a["scan_tokens"] >= a["prompt_tokens"] for a in prefills)
    assert all(a["cache_layers"] == 2 and a["fused"] == 1
               for a in prefills)
    assert stats["state_slot_writes"] == 4
    assert stats["scan_tokens"] == sum(a["scan_tokens"] for a in prefills)
    assert stats["admissions_fused"] == stats["admissions"] == len(prefills)
    assert set(stats["pool_relayouts"]) >= {
        "decode_paged_fp32+sample_greedy", "prefill_fp32+sample_greedy"}


def test_the_paths_it_is_not_built_for_refuse_by_name():
    cfg, gen, _ = _generator()
    prompt = _tokens(1, 6)[0]
    with pytest.raises(generation.UnsupportedPathError,
                       match="speculative verify"):
        gen.generate([prompt], max_new_tokens=2, spec_k=2)
    with pytest.raises(generation.UnsupportedPathError, match="tp > 1"):
        GPTGenerator(cfg, fluid.Scope(), max_len=32, tp=2)
    with pytest.raises(generation.UnsupportedPathError, match="int8 KV pool"):
        gen.new_pool(2, dtype="int8")
    # a prefix cache is asked for and declined; migration refuses
    engine = serving.GenerationEngine(gen, slots=2, prefix_cache=True)
    assert not engine.pool.prefix_enabled
    with pytest.raises(serving.batching.BadRequestError,
                       match="state group"):
        engine.export_slot(0)
    with pytest.raises(serving.batching.BadRequestError,
                       match="state group"):
        engine.pool.import_slot(0, {})
    with pytest.raises(generation.UnsupportedPathError,
                       match="chunked prefill"):
        set_flags({"prefill_chunk_tokens": 4})
        try:
            assert engine.incremental_prefill_enabled()
            state = engine.start_prefill(
                serving.batching.GenerationRequest(prompt,
                                                   max_new_tokens=2), 0)
            engine.prefill_chunk(state)
        finally:
            set_flags({"prefill_chunk_tokens": 0})
            engine.release_slot(0)
    with pytest.raises(generation.UnsupportedPathError,
                       match="speculative verify"):
        serving.batching.DecodeBatcher(
            serving.batching.RequestQueue(), engine, spec_k=2)
    # offline generation runs through the same pool
    out = gen.generate([prompt], max_new_tokens=3)
    assert out[0].shape == (3,)
