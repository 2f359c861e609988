"""The looped block (``models/ouro.py``: a stack of layers run
``total_ut_steps`` times over shared weights, a KV cache a (pass, layer)
pair) against the plain reference (``benchmark/families/ouro.py``, which
imports nothing of ``paddle_tpu``) on seeded weights at the rehearsal
size: full-sequence logits through ``Executor``, prefill then paged
decode through the pool past a block's length, ``InferenceServer`` end
to end, faults that each have to fail a tolerance, the pool's cache
layers apart from weight layers, and the typed refusals."""
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
from paddle_tpu.models import generation, ouro
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.serving.kvpool import adopt_decode_fetches, decode_feed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "ouro-2.6b.json")) as fh:
    CONFIG = json.load(fh)

from benchmark.families import ouro as fam  # noqa: E402

SZ = fam.Sizes(CONFIG, rehearsal=True)
SEED = 33


def _sizes(**over):
    config = dict(CONFIG)
    config["rehearsal"] = dict(CONFIG["rehearsal"], **over)
    return fam.Sizes(config, rehearsal=True)


def _tokens(rows, seq, seed=0):
    return np.random.default_rng(seed).integers(
        1, SZ.vocab_size, (rows, seq)).astype(np.int32)


def _cast(params, dtype):
    """The family rounds the matrices to bfloat16 once; float32 holds the
    same numbers exactly."""
    return {n: (a if a.dtype == jnp.float32 else a.astype(dtype))
            for n, a in params.items()}


def _generator(dtype, sz=SZ, max_len=64, **cfg_over):
    cfg = fam.program_config(sz)
    cfg.dtype = dtype
    for key, value in cfg_over.items():
        setattr(cfg, key, value)
    params = fam.init_params(sz, SEED)
    gen = GPTGenerator(cfg, fluid.Scope(), max_len=max_len)
    gen.bind_params(_cast(params, dtype))
    return cfg, gen, params


def _executor_logits(cfg, params, toks, last):
    """``ouro_logits`` through ``Executor``: ``(logits [B, V], exit_probs
    [B, U], exit_pass [B])`` at each row's ``last`` position."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = ouro.ouro_logits(cfg)
    exe, scope = fluid.Executor(), fluid.Scope()
    rows, seq = toks.shape
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (rows, seq)).copy()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for p in main.all_parameters():
            scope.set(p.name, np.asarray(_cast(params, cfg.dtype)[p.name]))
        return exe.run(
            main, feed={"tokens": toks, "pos_ids": pos, "last_pos": last},
            fetch_list=[out["logits"], out["aux"]["exit_probs"],
                        out["aux"]["exit_pass"]])


# float32 weights: program and reference differ by summation order and by
# XLA:CPU's default float32 product against precision=highest: measured
# 1e-7 to 5e-7 of logits of magnitude 0.3 after 8 block applications (the
# norm after every sub-layer keeps a rounding from growing). The faults
# below move a logit by 3e-3 (bfloat16 matrices) to 6e-1 (a cache shared
# by the passes), so 2e-5 holds the one and fails the others by ten times
# and more.
# bfloat16 weights: the same numbers, but every product rounds its
# activations to 8 mantissa bits (relative 4e-3) and sums tens to
# thousands of them: measured 3.3e-3 of logit through prefill and 24
# decode steps over a bfloat16 cache; 6e-3 (twice that with the cache's
# own rounding) holds it and still fails a pass or a norm left out (3e-1
# to 5e-1).
TOL = {"float32": 2e-5, "bfloat16": 6e-3}


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_full_sequence_logits_through_executor_match_reference(passes):
    sz = _sizes(total_ut_steps=passes)
    cfg = fam.program_config(sz)
    cfg.dtype = "float32"
    params = fam.init_params(sz, SEED)
    toks, last = _tokens(2, 40), np.array([39, 21], np.int32)
    logits, probs, exit_pass = _executor_logits(cfg, params, toks, last)
    ref = np.asarray(fam.reference_logits(sz, params, jnp.asarray(toks)))
    assert probs.shape == (2, passes)
    for r in range(2):
        np.testing.assert_allclose(logits[r], ref[r, last[r]], rtol=0,
                                   atol=TOL["float32"])
        _, ref_probs = fam.reference_forward(sz, params, jnp.asarray(toks[r]))
        np.testing.assert_allclose(probs[r], np.asarray(ref_probs)[last[r]],
                                   rtol=0, atol=1e-5)
    # the exit distribution is one: every pass's share, the last taking
    # what is left; the reported pass is the first whose running sum
    # reaches 0.5
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-6)
    np.testing.assert_array_equal(
        exit_pass, 1 + (np.cumsum(probs, axis=1) < 0.5).sum(axis=1).clip(
            max=passes - 1))


def test_threshold_one_takes_the_last_pass_and_the_reference_leaves_early():
    """At ``early_exit_threshold`` 1 the logits are the last pass's (the
    program has no other path); the reference with a lower threshold
    takes an earlier pass's state for the tokens that reach it, which
    are other logits: the threshold is part of the model."""
    params = fam.init_params(SZ, SEED)
    toks = jnp.asarray(_tokens(1, 24)[0])
    last, probs = fam.reference_forward(SZ, params, toks)
    early_sz = _sizes(early_exit_threshold=0.5)
    early, _ = fam.reference_forward(early_sz, params, toks)
    one_pass, _ = fam.reference_forward(_sizes(total_ut_steps=1), params, toks)
    leaves_first = np.asarray(probs)[:, 0] >= 0.5
    assert leaves_first.any() and not leaves_first.all()
    np.testing.assert_allclose(np.asarray(early)[leaves_first],
                               np.asarray(one_pass)[leaves_first],
                               rtol=0, atol=1e-6)
    assert np.abs(np.asarray(early) - np.asarray(last))[leaves_first].max() \
        > 1e-2


def _prefill_then_decode(gen, params, kv_dtype, steps=24, sz=SZ):
    """Prompts of 20 and 13 tokens, then ``steps`` decode steps through
    the pool with blocks of 4: every step's logits against the
    reference's full forward pass over the same tokens. Returns the
    widest difference and the pool."""
    toks = _tokens(2, 21 + steps, seed=3)
    lens = [20, 13]
    ref = np.asarray(fam.reference_logits(sz, params, jnp.asarray(toks)))
    pool = gen.new_pool(2, dtype=kv_dtype, name="test")
    key = jax.random.PRNGKey(0)
    packed, pos_ids, last = gen._pack_prompts(
        [toks[0, :20], toks[1, :13]])
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


@pytest.fixture
def blocks_of_4():
    set_flags({"kv_block_size": 4})
    yield
    set_flags({"kv_block_size": 16})


@pytest.mark.parametrize("dtype,kv_dtype,impl", [
    ("float32", "fp32", "xla"), ("float32", "fp32", "interpret"),
    ("bfloat16", "bf16", "xla")])
def test_prefill_then_paged_decode_matches_the_reference_forward(
        dtype, kv_dtype, impl, monkeypatch, blocks_of_4):
    """Contexts run from 13 to 44 positions, past eleven blocks of 4, in
    8 cache layers of 2 weight layers; the append runs inside the loop
    of passes."""
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: impl)
    _, gen, params = _generator(dtype)
    worst, pool = _prefill_then_decode(gen, params, kv_dtype)
    # bf16 keys and values add their own rounding on top of TOL
    assert worst <= TOL[dtype] * (1 if kv_dtype == "fp32" else 2)
    assert pool.blocks_in_use_by_group() == {"full": 11 + 10}
    assert (pool.num_layers, pool.num_arrays, pool.passes) == (8, 2, 4)


def _no_norm(skip):
    real = ouro._norm
    return lambda cfg, x, name: x if skip(name) else real(cfg, x, name)


def _final_norm_after_the_loop_only(monkeypatch):
    real_norm, real_head = ouro._norm, ouro._head
    monkeypatch.setattr(ouro, "_norm", _no_norm(
        lambda name: name == "final_norm"))
    monkeypatch.setattr(
        ouro, "_head", lambda cfg, h, gates, last: real_head(
            cfg, real_norm(cfg, h, "final_norm"), gates, last))


FAULTS = {
    "three_passes_for_four": lambda mp: {"total_ut_steps": 3},
    "bf16_where_the_config_says_float32": lambda mp: {"dtype": "bfloat16"},
    "final_norm_between_passes_left_out": _final_norm_after_the_loop_only,
    "post_attention_norm_left_out": lambda mp: mp.setattr(
        ouro, "_norm", _no_norm(lambda n: n.endswith("attn_out_norm"))),
    "post_mlp_norm_left_out": lambda mp: mp.setattr(
        ouro, "_norm", _no_norm(lambda n: n.endswith("mlp_out_norm"))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_forward_fails_the_tolerance(fault, monkeypatch):
    cfg = fam.program_config(SZ)
    cfg.dtype = "float32"
    for key, value in (FAULTS[fault](monkeypatch) or {}).items():
        setattr(cfg, key, value)
    params = fam.init_params(SZ, SEED)
    toks, last = _tokens(2, 40), np.array([39, 21], np.int32)
    logits, _, _ = _executor_logits(cfg, params, toks, last)
    ref = np.asarray(fam.reference_logits(SZ, params, jnp.asarray(toks)))
    worst = max(np.abs(logits[r] - ref[r, last[r]]).max() for r in range(2))
    assert worst > 10 * TOL["float32"], worst


def test_passes_sharing_one_cache_fail_the_tolerance(monkeypatch,
                                                     blocks_of_4):
    """Every pass appending to and reading the first pass's blocks: a
    pass then reads, for the positions before this one, the keys the
    LAST pass left there."""
    monkeypatch.setattr(ouro, "_pass_tables", lambda tables, first: tables)
    _, gen, params = _generator("float32")
    worst, _ = _prefill_then_decode(gen, params, "fp32", steps=6)
    assert worst > 10 * TOL["float32"], worst


def test_the_pool_holds_a_cache_for_every_pass_of_every_layer(blocks_of_4):
    """Cache layers apart from weight layers: 8 cache layers in 2 arrays
    of 4 passes' blocks under one table; the scatter fills every one
    with the keys and values of ITS pass; blocks come back."""
    _, gen, _ = _generator("float32")
    pool = gen.new_pool(3, dtype="fp32", name="test_layers")
    assert sorted(pool.arrays()) == ["cache_pk_0", "cache_pk_1",
                                     "cache_pv_0", "cache_pv_1"]
    n = pool.num_blocks
    assert all(a.shape[0] == 4 * n for a in pool.arrays().values())
    prompts = [_tokens(1, m, seed=m)[0] for m in (9, 14)]
    packed, pos_ids, last = gen._pack_prompts(prompts)
    for slot, p in zip((2, 0), prompts):
        pool.alloc(slot, p.size)
    _, caches, _ = gen._run_prefill(packed, pos_ids, last,
                                    jax.random.PRNGKey(0), kv_dtype="fp32")
    assert caches["cache_k_1"].shape == (4, 2, 4, 16, 8)
    pool.scatter_prefill([2, 0], caches, packed.shape[1],
                         lengths=[9, 14])
    for layer in range(pool.num_layers):
        u, i = divmod(layer, 2)
        for kind in "kv":
            rows = np.asarray(caches[f"cache_{kind}_{i}"])[u]
            for row, (slot, p) in enumerate(zip((2, 0), prompts)):
                held = pool.holders()[slot]
                # pass u's section of weight layer i's array
                got = pool.logical(
                    f"cache_p{kind}_{i}",
                    pool.tables[slot, :held] + u * pool.num_blocks)
                got = got.transpose(1, 0, 2, 3).reshape(4, held * 4, 8)
                np.testing.assert_array_equal(got[:, :p.size],
                                              rows[row, :, :p.size])
    # two passes of one weight layer hold different keys
    assert np.abs(np.asarray(caches["cache_k_0"])[0]
                  - np.asarray(caches["cache_k_0"])[3]).max() > 1e-3
    assert pool.relayouts().keys() == {"scatter"}
    assert pool.blocks_in_use() == 3 + 4
    for slot in (0, 2):
        pool.free_slot(slot)
    assert pool.blocks_in_use() == 0
    with pytest.raises(serving.batching.BadRequestError,
                       match="4 cache layers a weight layer"):
        pool.export_slot(0)


def test_inference_server_serves_it_and_its_spans_say_what_ran():
    """Through ``InferenceServer``'s own entry points: greedy replies
    are, teacher-forced through the reference, its own first choice at
    every position; the counters and spans this block adds move; the
    pool drains."""
    from paddle_tpu.observability import tracing
    _, gen, _ = _generator("float32")
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
    assert stats["kv_cache_layers"] == 8
    assert stats["kvpool_blocks_in_use"] == 0
    rows = tracing.loop_spans(t0, time.perf_counter())
    sent = [r[7] for r in rows if r[0] == "engine/step"
            and "ut_steps" in r[7]]
    read = [r[7] for r in rows if r[0] == "engine/step"
            and "exit_pass_mean" in r[7]]
    prefills = [r[7] for r in rows if r[0] == "generator/prefill"]
    assert not [r for r in rows if r[0] == "pool/scatter"]
    assert sent and all(a["ut_steps"] == 4 and a["cache_layers"] == 8
                        for a in sent + prefills)
    assert all(a["fused"] == 1 for a in prefills)
    assert read and all(1.0 <= a["exit_pass_mean"] <= 4.0 for a in read)
    assert all(1.0 <= a["exit_pass_mean"] <= 4.0 for a in prefills)
    # four passes an executable, steps and prefills alike
    assert stats["loop_passes"] == 4 * (stats["decode_steps"]
                                        + len(prefills))
    assert stats["admissions_fused"] == stats["admissions"] == len(prefills)
    assert set(stats["pool_relayouts"]) >= {
        "decode_paged_fp32+sample_greedy", "prefill_fp32+sample_greedy"}


def test_the_paths_it_is_not_built_for_refuse_by_name():
    cfg, gen, _ = _generator("float32")
    prompt = _tokens(1, 6)[0]
    with pytest.raises(generation.UnsupportedPathError,
                       match="speculative verify"):
        gen.generate([prompt], max_new_tokens=2, spec_k=2)
    with pytest.raises(generation.UnsupportedPathError, match="tp > 1"):
        GPTGenerator(cfg, fluid.Scope(), max_len=32, tp=2)
    with pytest.raises(generation.UnsupportedPathError, match="int8 KV pool"):
        gen.new_pool(2, dtype="int8")
    with pytest.raises(generation.UnsupportedPathError,
                       match="early_exit_threshold 0.9 below 1"):
        GPTGenerator(ouro.OuroConfig.tiny(early_exit_threshold=0.9),
                     fluid.Scope(), max_len=32)
    engine = serving.GenerationEngine(gen, slots=2)
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


def test_rotary_table_and_config_defaults_are_the_sources():
    cfg = ouro.OuroConfig()
    for key in ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "intermediate_size", "total_ut_steps", "rms_norm_eps",
                "rope_theta", "early_exit_threshold",
                "max_position_embeddings", "tie_word_embeddings",
                "hidden_act"):
        assert getattr(cfg, key) == CONFIG[key], key
    np.testing.assert_allclose(ouro.rope_inv_freq(cfg),
                               fam.rope_table(fam.Sizes(CONFIG)), rtol=1e-6)
    assert cfg.cache_layers == 192
    groups = cfg.serving().kv_groups()
    assert len(groups) == 1 and groups[0]["layers"] == list(range(192)) \
        and groups[0]["passes"] == 4 and groups[0]["window"] is None
    # a token's keys and values in every cache layer: 1.5 MB
    assert cfg.serving().prefill_bytes(1, 1, 320, 2) \
        - cfg.vocab_size * 4 - cfg.intermediate_size * 14 == 1572864
