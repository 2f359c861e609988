"""Speculative decoding on the paged KV bank: rejection sampling
preserves the output distribution exactly (op-level marginal check +
bitwise greedy parity spec-on vs spec-off vs full recompute, and tp=2),
multi-token block-pool appends stay COW/refcount-correct under
prefix-cache sharing (a 256-verify-step sweep with partial rejections
leaks zero blocks), and the draft depth behaves as a load knob (the
brownout ladder shrinks degraded classes' drafting while interactive
rows keep full depth; acceptance telemetry rides stats()/health() and
the flight recorder)."""
import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.flags import flag, set_flags
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import (GPTGenerator, NgramDrafter,
                                          make_drafter)
from paddle_tpu.parallel.mesh import get_mesh, set_mesh
from paddle_tpu.serving.batching import (DecodeBatcher, GenerationRequest,
                                         RequestQueue)
from paddle_tpu.serving.brownout import BrownoutController
from paddle_tpu.serving.metrics import ServingStats


@pytest.fixture(scope="module")
def tiny_gpt():
    """One initialized tiny-GPT scope + generator per module (the
    verify/spec executables compile once into the generator's cache)."""
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


@pytest.fixture
def spec_flags():
    """Flags this file mutates, always restored — plus the ambient mesh
    (GPTGenerator(tp=2) installs one globally)."""
    keys = ("decode_spec_k", "decode_spec_mode",
            "kv_prefix_cache", "prefill_chunk_tokens")
    saved = {k: flag(k) for k in keys}
    prev_mesh = get_mesh()
    yield
    set_flags({f"FLAGS_{k}": v for k, v in saved.items()})
    set_mesh(prev_mesh)


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _repetitive_prompt(n=12):
    return np.array(([5, 6, 7] * ((n + 2) // 3))[:n], np.int32)


# ---------------------------------------------------------------------------
# rejection sampling preserves the distribution (op level)
# ---------------------------------------------------------------------------

def test_spec_accept_marginal_matches_target_distribution(tiny_gpt):
    """The accept/resample op's emitted-token marginal equals the
    target softmax exactly (Leviathan-style guarantee, point-mass
    draft): accept draft d w.p. p(d), else resample from the residual
    — either way P(out = v) == p(v). Checked empirically over 20k
    independent rows sharing one op call/key."""
    _cfg, _scope, gen = tiny_gpt
    B, V = 20000, 8
    rng = np.random.default_rng(0)
    row = rng.normal(size=(V,)).astype(np.float32)
    logits = np.broadcast_to(row, (B, 2, V)).copy()   # K=1 -> S=2
    draft = np.full((B, 1), 3, np.int32)
    temp = np.ones((B,), np.float32)
    topk = np.zeros((B,), np.int32)
    nd = np.ones((B,), np.int32)
    out, acc, _ = gen._run_spec_accept(logits, draft, temp, topk, nd,
                                       jax.random.PRNGKey(7))
    out, acc = np.asarray(out), np.asarray(acc)
    p = np.exp(row - row.max())
    p /= p.sum()
    # acceptance rate of the point-mass draft is p(draft)
    assert abs(acc.mean() - p[3]) < 0.02
    # first-emitted-token marginal is the target distribution
    emp = np.bincount(out[:, 0], minlength=V) / B
    np.testing.assert_allclose(emp, p, atol=0.02)
    # fixed key -> bitwise reproducible
    out2, acc2, _ = gen._run_spec_accept(logits, draft, temp, topk, nd,
                                         jax.random.PRNGKey(7))
    np.testing.assert_array_equal(out, np.asarray(out2))
    np.testing.assert_array_equal(acc, np.asarray(acc2))


def test_spec_accept_greedy_semantics(tiny_gpt):
    """Greedy rows (temperature <= 0) accept exactly the argmax-chain
    prefix of the draft and emit the argmax correction — no randomness
    involved, which is what makes spec-on greedy bitwise equal to
    spec-off."""
    _cfg, _scope, gen = tiny_gpt
    V = 6
    logits = np.zeros((2, 3, V), np.float32)
    logits[:, 0, 2] = 5.0      # argmax after pos0 = 2
    logits[:, 1, 4] = 5.0      # argmax after draft1 = 4
    logits[:, 2, 1] = 5.0      # bonus argmax = 1
    draft = np.array([[2, 4], [2, 3]], np.int32)   # row1 wrong at step 2
    temp = np.zeros((2,), np.float32)
    topk = np.zeros((2,), np.int32)
    nd = np.full((2,), 2, np.int32)
    out, acc, _ = gen._run_spec_accept(logits, draft, temp, topk, nd,
                                       jax.random.PRNGKey(0))
    out, acc = np.asarray(out), np.asarray(acc)
    assert acc.tolist() == [2, 1]
    assert out[0, :3].tolist() == [2, 4, 1]   # all accepted + bonus
    assert out[1, :2].tolist() == [2, 4]      # 1 accepted + correction


# ---------------------------------------------------------------------------
# end-to-end parity (offline generator)
# ---------------------------------------------------------------------------

def test_spec_greedy_bitwise_parity_with_full_recompute(tiny_gpt,
                                                        spec_flags):
    """Greedy generation with speculation on is BITWISE the
    non-speculative output, and both are naive full recompute's token
    for token — for high-acceptance (repetitive) and low-acceptance
    (random) prompts alike."""
    cfg, _scope, gen = tiny_gpt
    prompts = [_repetitive_prompt(12)] + _prompts(cfg, [9, 7])
    ref = gen.generate_naive(prompts, max_new_tokens=10, seed=0)
    for k in (0, 2, 4):
        spec = gen.generate(prompts, max_new_tokens=10, seed=0, spec_k=k)
        for a, b in zip(ref, spec):
            np.testing.assert_array_equal(a, b)


def test_spec_greedy_parity_tp2(tiny_gpt, spec_flags):
    """tp=2 sharded speculative generation (conftest's virtual device
    mesh) matches the single-chip non-speculative output bitwise on the
    paged pool — the verify program shards like prefill."""
    cfg, scope, gen = tiny_gpt
    prompts = [_repetitive_prompt(11), _prompts(cfg, [8])[0]]
    ref = gen.generate(prompts, max_new_tokens=8, seed=0, spec_k=0)
    gen2 = GPTGenerator(cfg, scope, max_len=48, bucket_min=8, tp=2)
    assert gen2.mesh is not None
    spec = gen2.generate(prompts, max_new_tokens=8, seed=0, spec_k=4)
    for a, b in zip(ref, spec):
        np.testing.assert_array_equal(a, b)


def test_spec_stochastic_seeded_equivalence(tiny_gpt, spec_flags):
    """Seeded stochastic speculative sampling is reproducible call-over
    -call: the whole span's randomness comes from the one
    program-invocation key chain."""
    cfg, _scope, gen = tiny_gpt
    prompts = [_repetitive_prompt(10)] + _prompts(cfg, [8])
    a = gen.generate(prompts, max_new_tokens=8, temperature=0.9,
                     top_k=8, seed=11, spec_k=4)
    b = gen.generate(prompts, max_new_tokens=8, temperature=0.9,
                     top_k=8, seed=11, spec_k=4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
        assert x.shape == (8,)


def test_ngram_drafter_and_registry():
    """The default self-drafting n-gram drafter proposes the learned
    continuation of a repeating context, degrades to empty on
    structureless context, and make_drafter resolves modes."""
    d = NgramDrafter()
    ctx = np.array([1, 2, 3] * 5, np.int32)        # ends at 3
    # the chosen hit is the most recent with 4 continuation tokens
    # available, not the nearest (which could only supply 3)
    np.testing.assert_array_equal(d.draft(ctx, 4), [1, 2, 3, 1])
    assert d.draft(np.array([4], np.int32), 3).size == 0
    assert isinstance(make_drafter("ngram"), NgramDrafter)
    with pytest.raises(ValueError):
        make_drafter("no_such_mode")


# ---------------------------------------------------------------------------
# serving bank: COW under sharing, zero-leak sweep, telemetry
# ---------------------------------------------------------------------------

def _run_spec_bank(engine, reqs, spec_k, stats=None, brownout=None):
    b = DecodeBatcher(RequestQueue(max_depth=64), engine, stats=stats,
                      spec_k=spec_k, brownout=brownout).start()
    try:
        for r in reqs:
            b.queue.put(r)
        outs = [r.wait(timeout=120)[0].tolist() for r in reqs]
        return outs, b
    finally:
        b.stop()


def test_spec_cow_fires_before_speculative_write_on_shared_blocks(
        tiny_gpt, spec_flags):
    """A request adopting prefix-cached blocks speculates multi-token
    writes into the shared tail block: COW must duplicate BEFORE the
    speculative write (even for positions later rejected), so the
    cached prompt replays bitwise afterwards and nothing leaks."""
    cfg, _scope, gen = tiny_gpt
    prompt = _repetitive_prompt(11)       # odd length: unaligned tail
    eng_ref = serving.GenerationEngine(gen, slots=2,
                                       kv_block_size=4,
                                       pool_name="spec_cowref")
    ref, _ = _run_spec_bank(
        eng_ref, [GenerationRequest(prompt, max_new_tokens=8)], spec_k=0)

    set_flags({"FLAGS_prefill_chunk_tokens": 0})
    eng = serving.GenerationEngine(gen, slots=2,
                                   kv_block_size=4,
                                   pool_name="spec_cow",
                                   prefix_cache=True)
    outs = []
    for _ in range(3):                    # 2nd/3rd adopt cached blocks
        o, _b = _run_spec_bank(
            eng, [GenerationRequest(prompt, max_new_tokens=8)], spec_k=4)
        outs.append(o)
        assert eng.pool.blocks_in_use() == 0
    assert all(o == ref for o in outs)
    hits = sum(e["hits"] for e in eng.pool._prefix.values())
    assert hits >= 2, "repeat prompts did not adopt the cached prefix"
    from paddle_tpu.serving.kvpool import _PREFIX_COW
    assert _PREFIX_COW.value(labels=("spec_cow",)) >= 1


def test_spec_partial_rejection_leaks_zero_blocks_256_steps(tiny_gpt,
                                                            spec_flags):
    """256+ speculative verify steps with stochastic sampling (forcing
    partial rejections, so allocated span blocks regularly outlive the
    accepted prefix) across rotating slots under prefix-cache sharing:
    the pool drains to zero live blocks after every batch and the leak
    sweeper finds nothing."""
    cfg, _scope, gen = tiny_gpt
    st = ServingStats()
    eng = serving.GenerationEngine(gen, slots=4,
                                   kv_block_size=4,
                                   pool_name="spec_sweep",
                                   prefix_cache=True, stats=st)
    prompts = [_repetitive_prompt(9), _prompts(cfg, [7], seed=5)[0],
               _repetitive_prompt(12), _prompts(cfg, [10], seed=6)[0]]
    rounds = 0
    while st.counter("spec_steps") < 256 and rounds < 40:
        rounds += 1
        reqs = [GenerationRequest(p, max_new_tokens=8, temperature=0.9,
                                  top_k=8) for p in prompts]
        _outs, _b = _run_spec_bank(eng, reqs, spec_k=4, stats=st)
        assert eng.pool.blocks_in_use() == 0, rounds
    assert st.counter("spec_steps") >= 256
    assert st.counter("spec_rejected") > 0, \
        "sweep never exercised a partial rejection"
    assert st.counter("spec_accepted") <= st.counter("spec_drafted")
    assert eng.reclaim_leaks([]) == 0
    snap = st.snapshot()
    assert snap["spec_accept_ratio"] == pytest.approx(
        st.counter("spec_accepted") / st.counter("spec_drafted"),
        abs=1e-4)


def test_spec_server_stats_health_and_flight_events(tiny_gpt,
                                                    spec_flags):
    """Through the full server: speculative greedy == spec-off greedy
    bitwise, acceptance counters ride server.stats(), the windowed
    ratio + effective depth ride health(), the acceptance gauge is
    exported, and rejected runs land in the flight recorder."""
    from paddle_tpu.observability.recorder import flight_recorder
    from paddle_tpu.serving.metrics import _SPEC_ACCEPT
    cfg, scope, _gen = tiny_gpt
    prompt = _repetitive_prompt(10)

    set_flags({"FLAGS_decode_spec_k": 4})
    srv = serving.InferenceServer(
        generator=GPTGenerator(cfg, scope, max_len=48, bucket_min=8),
        decode_slots=2, kv_pool_name="spec_srv")
    srv.start(serve_network=False)
    try:
        out = srv.generate(prompt, max_new_tokens=10)
        srv.generate(_prompts(cfg, [7], seed=9)[0], max_new_tokens=8,
                     temperature=0.9, top_k=8)
        stats = srv.stats()
        health = srv.health()
        scope_name = srv.decode_batcher._spec_scope
    finally:
        srv.stop()

    set_flags({"FLAGS_decode_spec_k": 0})
    srv2 = serving.InferenceServer(
        generator=GPTGenerator(cfg, scope, max_len=48, bucket_min=8),
        decode_slots=2, kv_pool_name="spec_srv_ref")
    srv2.start(serve_network=False)
    try:
        ref = srv2.generate(prompt, max_new_tokens=10)
        assert srv2.stats()["spec_steps"] == 0
        assert "spec_k" not in srv2.health()
    finally:
        srv2.stop()

    np.testing.assert_array_equal(out, ref)
    assert stats["spec_steps"] > 0
    assert stats["spec_drafted"] > 0
    assert 0.0 <= stats["spec_accept_ratio"] <= 1.0
    assert health["spec_k"] == 4
    assert 1 <= health["spec_k_effective"] <= 4
    assert health["spec_accept_ratio"] is not None
    assert _SPEC_ACCEPT.value(labels=(scope_name,)) is not None
    if stats["spec_rejected"]:
        events = [e for e in flight_recorder().snapshot()
                  if e["kind"] == "spec_rejected"]
        assert events and events[-1]["proposed"] >= events[-1]["accepted"]


# ---------------------------------------------------------------------------
# brownout: draft depth is a load knob
# ---------------------------------------------------------------------------

def test_brownout_draft_depth_ladder():
    """Unit ladder semantics: level 1 halves batch drafting and stops
    best_effort; level 2 stops batch too; interactive keeps full depth
    at every level; recovery restores everything."""
    breached = [0]
    bc = BrownoutController(lambda: breached[0], enabled=True,
                            escalate_s=60.0, recover_s=0.0)
    assert [bc.draft_depth(r, 4) for r in (0, 1, 2)] == [4, 4, 4]
    breached[0] = 1
    assert [bc.draft_depth(r, 4) for r in (0, 1, 2)] == [4, 2, 0]
    assert bc.draft_depth(1, 1) == 1      # never rounds batch to zero
    breached[0] = 2
    assert [bc.draft_depth(r, 4) for r in (0, 1, 2)] == [4, 0, 0]
    breached[0] = 0
    bc.level()                            # healthy run starts
    bc.level()                            # recovery rung 2 -> 1
    bc.level()                            # rung 1 -> 0
    assert [bc.draft_depth(r, 4) for r in (0, 1, 2)] == [4, 4, 4]


def test_brownout_shrinks_batch_drafting_keeps_interactive(tiny_gpt,
                                                           spec_flags):
    """Wiring: under a breached SLO monitor the decode loop's draft
    proposals shrink for batch rows and vanish for best_effort rows
    while interactive rows keep drafting at full depth; recovery
    restores the configured depth for everyone."""
    cfg, _scope, gen = tiny_gpt
    breached = [1]
    bc = BrownoutController(lambda: breached[0], enabled=True,
                            escalate_s=60.0, recover_s=0.0)
    eng = serving.GenerationEngine(gen, slots=4,
                                   pool_name="spec_bo")
    b = DecodeBatcher(RequestQueue(max_depth=8), eng, spec_k=4,
                      brownout=bc)
    # period-4 repetition: the n-gram drafter's most recent prior hit
    # leaves a full 4-token continuation, so depth is the only limiter
    prompt = np.array([5, 6, 7, 8] * 4, np.int32)
    for slot, prio in enumerate(("interactive", "batch", "best_effort")):
        req = GenerationRequest(prompt, max_new_tokens=32, priority=prio)
        req.slot = slot
        b._active[slot] = req
    _drafts, nd = b._propose_drafts(4)
    assert nd.tolist()[:3] == [4, 2, 0]
    breached[0] = 0
    bc.level()                            # healthy run starts
    bc.level()                            # recover rung 1 -> 0
    _drafts, nd = b._propose_drafts(4)
    assert nd.tolist()[:3] == [4, 4, 4]
