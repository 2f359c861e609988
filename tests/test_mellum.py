"""The Mellum-2-class block (``models/mellum.py``) against the plain
reference (``benchmark/families/mellum.py``, which imports nothing of
``paddle_tpu``) on seeded weights at the rehearsal size: full-sequence
logits through ``Executor``, prefill then paged decode through the
two-group pool past the window's length, ``InferenceServer`` end to end,
and the typed refusals of the paths the block is not built for."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.flags import set_flags
from paddle_tpu.kernels import _dispatch
from paddle_tpu.models import mellum
from paddle_tpu.models import generation
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.serving.kvpool import decode_feed, adopt_decode_fetches

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "mellum2-12b-a2.5b.json")) as fh:
    CONFIG = json.load(fh)

from benchmark.families import mellum as fam  # noqa: E402

SZ = fam.Sizes(CONFIG, rehearsal=True)
SEED = 11


def _generator(dtype, max_len=64):
    """The rehearsal-size model with the family's seeded weights in
    ``dtype`` (the family rounds them to bfloat16 once; float32 holds the
    same numbers exactly)."""
    cfg = fam.program_config(SZ)
    cfg.dtype = dtype
    params = {n: (a if a.dtype == jnp.float32 else a.astype(dtype))
              for n, a in fam.init_params(SZ, SEED).items()}
    gen = GPTGenerator(cfg, fluid.Scope(), max_len=max_len)
    gen.bind_params(params)
    return cfg, gen, fam.init_params(SZ, SEED)


def _tokens(rows, seq, seed=0):
    return np.random.default_rng(seed).integers(
        1, SZ.vocab_size, (rows, seq)).astype(np.int32)


# float32 weights: program and reference differ by summation order and by
# XLA:CPU's default float32 product against precision=highest, a few 1e-6
# of logits of magnitude 0.3. One routed assignment dropped or swapped
# moves a logit by 1e-3 to 1e-2, and router logits rounded to bfloat16
# (3 decimal digits of a softmax over 8) swap a near-tied pair in some
# position of every 40-token sequence, so 2e-5 fails both.
# bfloat16 weights: the same numbers, but every product rounds its
# activations to 8 mantissa bits (relative 4e-3) and sums tens to
# thousands of them: 1e-3 to 3e-3 of logit by the last layer, measured
# 7e-4; 5e-3 holds that and still fails a dropped token.
TOL = {"float32": 2e-5, "bfloat16": 5e-3}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_full_sequence_logits_through_executor_match_reference(dtype):
    cfg = fam.program_config(SZ)
    cfg.dtype = dtype
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = mellum.mellum_logits(cfg)
    exe, scope = fluid.Executor(), fluid.Scope()
    params = fam.init_params(SZ, SEED)
    toks = _tokens(2, 40)
    pos = np.broadcast_to(np.arange(40, dtype=np.int32), (2, 40)).copy()
    last = np.array([39, 21], np.int32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for name, a in params.items():
            scope.set(name, np.asarray(
                a if a.dtype == jnp.float32 else a.astype(dtype)))
        logits, counts = exe.run(
            main, feed={"tokens": toks, "pos_ids": pos, "last_pos": last},
            fetch_list=[out["logits"], out["aux"]["moe_counts"]])
    ref = np.asarray(fam.reference_logits(SZ, params, jnp.asarray(toks)))
    for r in range(2):
        np.testing.assert_allclose(logits[r], ref[r, last[r]], rtol=0,
                                   atol=TOL[dtype])
    # every real token goes to exactly k experts in every layer, padding
    # to none
    assert counts.shape == (SZ.num_hidden_layers, SZ.num_experts)
    assert counts.sum(axis=1).tolist() == [
        (40 + 22) * SZ.num_experts_per_tok] * SZ.num_hidden_layers


@pytest.mark.parametrize("dtype,kv_dtype,impl", [
    ("float32", "fp32", "xla"), ("float32", "fp32", "interpret"),
    ("bfloat16", "bf16", "xla")])
def test_prefill_then_paged_decode_matches_the_reference_forward(
        dtype, kv_dtype, impl, monkeypatch):
    """Prompts of 20 and 13 tokens, then 24 decode steps through the
    pool: contexts run to 44, five windows of 8 and past the ring of 3
    blocks of 4, so window layers recycle blocks while full layers keep
    all. Every step's logits against the reference's full forward pass
    over the same tokens."""
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: impl)
    set_flags({"kv_block_size": 4})
    try:
        cfg, gen, params = _generator(dtype)
        toks = _tokens(2, 45, seed=3)
        lens = [20, 13]
        prompts = [toks[0, :20], toks[1, :13]]
        ref = np.asarray(fam.reference_logits(SZ, params,
                                              jnp.asarray(toks)))
        # bf16 keys and values add their own rounding on top of TOL
        tol = TOL[dtype] * (1 if kv_dtype == "fp32" else 2)
        pool = gen.new_pool(2, dtype=kv_dtype, name="test")
        assert pool.window.ring == 3
        key = jax.random.PRNGKey(0)
        packed, pos_ids, last = gen._pack_prompts(prompts)
        for r in range(2):
            pool.alloc(r, lens[r])
        logits, caches, key, aux = gen._run_prefill(
            packed, pos_ids, last, key, kv_dtype=kv_dtype, want_aux=True)
        pool.scatter_prefill([0, 1], caches, packed.shape[1], lengths=lens)
        assert np.asarray(aux["moe_counts"]).sum() == \
            33 * SZ.num_experts_per_tok * SZ.num_hidden_layers
        pos = np.asarray(lens, np.int32)
        for step in range(24):
            for r in range(2):
                np.testing.assert_allclose(
                    np.asarray(logits)[r], ref[r, pos[r] - 1], rtol=0,
                    atol=tol, err_msg=f"step {step} row {r}")
                pool.ensure(r, int(pos[r]))
            tok = np.array([toks[r, pos[r]] for r in range(2)], np.int32)
            kind = f"decode_paged_{kv_dtype}"
            fetches, key = gen._invoke(kind, "decode",
                                       decode_feed(pool, tok, pos), key)
            logits = adopt_decode_fetches(pool, fetches)
            counts = np.asarray(gen.aux_of(kind, fetches)["moe_counts"])
            assert counts.sum() == 2 * SZ.num_experts_per_tok \
                * SZ.num_hidden_layers
            pos = pos + 1
        held = pool.blocks_in_use_by_group()
        assert held == {"full": 11 + 10, "window": 3 + 3}
        assert pool.window.recycled > 0
    finally:
        set_flags({"kv_block_size": 16})


def test_inference_server_serves_it_past_the_window():
    """Through ``InferenceServer``'s own entry points: greedy replies
    whose contexts pass the window are, teacher-forced through the
    reference, its own first choice at every position (gap 0 up to
    rounding: the float32 tolerance above), the routing counters move,
    the round's span carries both groups' blocks, and the pool drains."""
    from paddle_tpu.observability import tracing
    import time
    cfg, gen, params = _generator("float32")
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
    assert stats["moe_assignments"] == sum(
        p.size + 11 for p in prompts) * SZ.num_experts_per_tok \
        * SZ.num_hidden_layers
    assert 0 < stats["moe_experts_hit"] and \
        stats["moe_expert_load_max"] <= stats["moe_assignments"]
    assert stats["kvpool_blocks_in_use"] == 0
    assert stats["kvpool_window_blocks_recycled"] > 0
    rows = tracing.loop_spans(t0, time.perf_counter())
    rounds = [r[7] for r in rows if r[0] == "serving/round"
              and "blocks_in_use_window" in r[7]]
    assert rounds and all(a["blocks_in_use_window"] <= 2 * 3
                          for a in rounds)
    steps = [r[7] for r in rows if r[0] == "engine/step"
             and "moe_tokens" in r[7]]
    assert steps and all(
        a["moe_load_max"] * SZ.num_experts >= a["moe_tokens"]
        for a in steps)


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
    err = generation.UnsupportedPathError("mellum", "x")
    assert isinstance(err, NotImplementedError) and err.path == "x"


def test_yarn_table_matches_the_reference_and_the_plain_one_below_low():
    cfg = mellum.MellumConfig()          # the published rope_parameters
    cfg.rope_parameters = CONFIG["rope_parameters"]
    full = fam.Sizes(CONFIG)
    for kind in (mellum.FULL, mellum.SLIDING):
        inv, factor = mellum.rope_inv_freq(cfg, kind)
        ref_inv, ref_factor = fam.rope_table(full, kind)
        np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
        assert factor == pytest.approx(ref_factor)
    yarn, factor = mellum.rope_inv_freq(cfg, mellum.FULL)
    plain, _ = mellum.rope_inv_freq(cfg, mellum.SLIDING)
    assert factor == pytest.approx(1.2772588722239782)
    # fast dimensions keep their frequency, slow ones are stretched 16x
    np.testing.assert_allclose(yarn[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(yarn[-8:] * 16, plain[-8:], rtol=1e-6)
