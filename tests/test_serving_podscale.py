"""Pod-scale serving: tensor-parallel sharded generation (tp=2 bitwise
greedy parity dense AND paged, the compile-time gate refusing an
un-annotated build), chunked prefill interleaved with the decode bank
(== monolithic admission bitwise), the block-granular prefix cache
(repeat prompts replay cached blocks, mid-prompt COW divergence stays
bitwise correct, shared-block refcounts never leak across a 256-step
sweep, the leak sweeper's flight event covers shared blocks), and the
router's prefix-affinity dispatch."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.flags import flag, set_flags
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator, TPCompileGateError
from paddle_tpu.parallel.mesh import get_mesh, set_mesh
from paddle_tpu.serving.batching import (DecodeBatcher, GenerationRequest,
                                         RequestQueue)
from paddle_tpu.serving.kvpool import KVBlockPool


@pytest.fixture(scope="module")
def tiny_gpt():
    """One initialized tiny-GPT scope per module; generators (tp=1 and
    tp=2 compile their own executables) are built per test."""
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return cfg, scope


@pytest.fixture
def podscale_flags():
    """Serving flags this file mutates, always restored — plus the
    ambient mesh (GPTGenerator(tp=2) installs one globally)."""
    keys = ("prefill_chunk_tokens", "kv_prefix_cache",
            "shard_audit_replicated_mb", "serving_tp")
    saved = {k: flag(k) for k in keys}
    prev_mesh = get_mesh()
    yield
    set_flags({f"FLAGS_{k}": v for k, v in saved.items()})
    set_mesh(prev_mesh)


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _run_bank(engine, prompts, n_new=6):
    """Drive prompts through a DecodeBatcher (the serving admission +
    decode path) and return the generated token lists."""
    b = DecodeBatcher(RequestQueue(max_depth=16), engine).start()
    try:
        reqs = [GenerationRequest(p, max_new_tokens=n_new)
                for p in prompts]
        for r in reqs:
            b.queue.put(r)
        return [r.wait(timeout=120)[0].tolist() for r in reqs]
    finally:
        b.stop()


# ---------------------------------------------------------------------------
# tensor-parallel generation
# ---------------------------------------------------------------------------

def test_tp_generate_bitwise_parity(tiny_gpt, podscale_flags):
    """tp=2 sharded generation (conftest's virtual 8-device mesh) is
    bitwise identical to single-chip greedy decode, which is naive
    full recompute's token for token — tensor parallelism is a
    throughput lever, never a numerics one."""
    cfg, scope = tiny_gpt
    prompts = _prompts(cfg, [11, 7])
    gen1 = GPTGenerator(cfg, scope, max_len=48, bucket_min=8, tp=1)
    ref_naive = gen1.generate_naive(prompts, max_new_tokens=8, seed=0)
    ref_paged = gen1.generate(prompts, max_new_tokens=8, seed=0)
    gen2 = GPTGenerator(cfg, scope, max_len=48, bucket_min=8, tp=2)
    assert gen2.mesh is not None
    tp_naive = gen2.generate_naive(prompts, max_new_tokens=8, seed=0)
    tp_paged = gen2.generate(prompts, max_new_tokens=8, seed=0)
    for a, b in zip(ref_naive + ref_paged + ref_naive,
                    tp_naive + tp_paged + tp_paged):
        np.testing.assert_array_equal(a, b)


def test_tp_must_divide_heads(tiny_gpt, podscale_flags):
    cfg, scope = tiny_gpt        # tiny: num_heads=2
    with pytest.raises(ValueError, match="divide num_heads"):
        GPTGenerator(cfg, scope, max_len=48, bucket_min=8, tp=3)


def test_tp_compile_gate_refuses_replicated_build(tiny_gpt,
                                                  podscale_flags,
                                                  monkeypatch):
    """The compile-time gate (PR-14 sharding audit over the compiled
    executable): a tp build whose params silently replicate — the
    annotation pass dropped — raises TPCompileGateError naming the
    worst param instead of shipping tokens/s that does not scale."""
    cfg, scope = tiny_gpt
    set_flags({"FLAGS_shard_audit_replicated_mb": 0.001})
    monkeypatch.setattr(GPTGenerator, "_annotate_tp",
                        lambda self, kind, main: None)
    bad = GPTGenerator(cfg, scope, max_len=48, bucket_min=8, tp=2)
    with pytest.raises(TPCompileGateError, match="replicated large"):
        bad.generate(_prompts(cfg, [8]), max_new_tokens=2, seed=0)


# ---------------------------------------------------------------------------
# chunked prefill + prefix cache through the decode bank
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_monolithic(tiny_gpt, podscale_flags):
    """Admission prefill split into fixed 4-token chunks interleaved
    with the decode bank produces bitwise the monolithic admission's
    outputs; repeat prompts then hit the prefix cache (full-exact
    replay) with the same outputs, and the pool drains to zero live
    blocks while the cache retains evictable ones."""
    cfg, scope = tiny_gpt
    gen = GPTGenerator(cfg, scope, max_len=48, bucket_min=8)
    prompts = _prompts(cfg, [11, 7, 13], seed=1)
    eng_a = serving.GenerationEngine(gen, slots=4,
                                     pool_name="pod_mono")
    base = _run_bank(eng_a, prompts)
    assert eng_a.pool.blocks_in_use() == 0

    set_flags({"FLAGS_prefill_chunk_tokens": 4})
    eng_b = serving.GenerationEngine(gen, slots=4,
                                     pool_name="pod_chunk",
                                     prefix_cache=True)
    assert eng_b.incremental_prefill_enabled()
    assert _run_bank(eng_b, prompts) == base
    assert eng_b.pool.blocks_in_use() == 0
    assert eng_b.pool.cached_blocks() > 0
    st = eng_b.pool.stats()
    assert st["prefix_entries"] > 0 and st["evictable_blocks"] > 0

    # repeat: every prompt is a full-exact prefix hit
    h0 = sum(e["hits"] for e in eng_b.pool._prefix.values())
    assert _run_bank(eng_b, prompts) == base
    h1 = sum(e["hits"] for e in eng_b.pool._prefix.values())
    assert h1 >= h0 + len(prompts)
    assert eng_b.pool.blocks_in_use() == 0

    # prefix-only incremental mode (chunk flag 0): one whole-prompt
    # chunk after the cached prefix — same outputs
    set_flags({"FLAGS_prefill_chunk_tokens": 0})
    eng_c = serving.GenerationEngine(gen, slots=4,
                                     pool_name="pod_pfx",
                                     prefix_cache=True)
    assert eng_c.incremental_prefill_enabled()
    assert _run_bank(eng_c, prompts) == base
    assert _run_bank(eng_c, prompts) == base
    assert eng_c.pool.blocks_in_use() == 0


def test_cow_divergence_keeps_shared_prefix_bitwise(tiny_gpt,
                                                    podscale_flags):
    """Two prompts sharing an 8-token (2-block at block_size=4) head
    with different tails: the second adopts the cached blocks and
    copy-on-writes at divergence — both outputs match an uncached
    engine, and the FIRST prompt still replays its (un-corrupted)
    cached blocks bitwise afterwards."""
    cfg, scope = tiny_gpt
    gen = GPTGenerator(cfg, scope, max_len=48, bucket_min=8)
    rng = np.random.default_rng(2)
    head = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    pA = np.concatenate(
        [head, rng.integers(1, cfg.vocab_size, 3).astype(np.int32)])
    pB = np.concatenate(
        [head, rng.integers(1, cfg.vocab_size, 5).astype(np.int32)])

    eng_ref = serving.GenerationEngine(gen, slots=4,
                                       kv_block_size=4,
                                       pool_name="pod_cowref")
    ref = _run_bank(eng_ref, [pA]) + _run_bank(eng_ref, [pB])

    set_flags({"FLAGS_prefill_chunk_tokens": 4})
    eng = serving.GenerationEngine(gen, slots=4,
                                   kv_block_size=4, pool_name="pod_cow",
                                   prefix_cache=True)
    outA = _run_bank(eng, [pA])      # inserts exact-11 + aligned-8
    reused0 = sum(e["hits"] for e in eng.pool._prefix.values())
    outB = _run_bank(eng, [pB])      # adopts aligned-8, then diverges
    reused1 = sum(e["hits"] for e in eng.pool._prefix.values())
    assert reused1 > reused0, "pB did not adopt the shared prefix"
    assert outA == ref[:1] and outB == ref[1:]
    from paddle_tpu.serving.kvpool import _PREFIX_COW
    assert _PREFIX_COW.value(labels=("pod_cow",)) >= 1
    # pA replays from its cached blocks — COW protected them
    assert _run_bank(eng, [pA]) == ref[:1]
    assert eng.pool.blocks_in_use() == 0


# ---------------------------------------------------------------------------
# shared-block refcount accounting
# ---------------------------------------------------------------------------

def _pool(**kw):
    kw.setdefault("slots", 4)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("d_head", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("name", "pod_sweep")
    kw.setdefault("prefix_cache", True)
    return KVBlockPool(**kw)


def test_shared_block_leak_sweep_256_steps():
    """256 admission cycles alternating fresh prefills, prefix-cache
    deposits, and cached-prefix adoptions across rotating slots: block
    accounting never drifts — after every free, live blocks return to
    exactly the cache-shared set, and a final cache clear returns the
    pool to empty with the full free list."""
    p = _pool(num_blocks=65)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 100, n).astype(np.int32)
               for n in (8, 12, 16, 9)]
    for step in range(256):
        slot = step % p.slots
        prompt = prompts[step % len(prompts)]
        m = p.match_prefix(prompt)
        if m is not None and m["tokens"] == len(prompt):
            p.adopt_prefix(slot, m)
        else:
            p.alloc(slot, len(prompt))
            p.prefix_insert(prompt, slot)
        assert p.free_slot(slot) >= 0
        # invariant: live == cache-shared, nothing stranded
        assert p.blocks_in_use() == 0, step
        st = p.stats()
        assert st["evictable_blocks"] == p.cached_blocks()
        held = sum(p._refs.get(b, 0) > 0 for b in range(1, p.num_blocks))
        assert held == p.cached_blocks(), step
    assert p.cached_blocks() > 0          # the sweep did cache things
    p.reset()
    assert p.cached_blocks() == 0 and p.blocks_in_use() == 0
    assert len(p._free) == p.capacity_blocks


def test_reclaim_leaks_reports_shared_blocks():
    """The continuous-batching leak sweeper on a slot holding CACHED
    (shared) blocks: the slot's references are reclaimed, the cache
    keeps its co-owned blocks alive, and the kv_block_leak flight
    event distinguishes shared from physically-freed blocks."""
    from paddle_tpu.observability.recorder import flight_recorder
    p = _pool(num_blocks=33, name="pod_leak")
    prompt = np.arange(1, 9, dtype=np.int32)      # 2 blocks at bs=4
    p.alloc(0, len(prompt))
    p.prefix_insert(prompt, 0)                    # blocks now shared
    p.alloc(1, 5)                                 # unshared leak too
    assert p.blocks_in_use() == 4
    freed = p.reclaim_leaks(live_slots=[])        # both slots leaked
    assert freed == 2        # only slot 1's exclusively-owned blocks
    assert p.blocks_in_use() == 0
    assert p.cached_blocks() == 2                 # cache kept its copy
    events = [e for e in flight_recorder().snapshot()
              if e["kind"] == "kv_block_leak"]
    shared = [e for e in events if e.get("shared")]
    assert shared and shared[-1]["shared"] == 2
    # cached content is still adoptable after the sweep
    m = p.match_prefix(prompt)
    assert m is not None and m["tokens"] == len(prompt)


# ---------------------------------------------------------------------------
# router prefix affinity
# ---------------------------------------------------------------------------

def test_router_prefix_affinity(tiny_gpt, podscale_flags):
    """Repeat prompts through a 2-replica fleet land on the replica
    that cached the prefix (router_prefix_hits), replica health +
    registry snapshots carry the evictable-block count the cache-aware
    load score reads, and the replica pool records real prefix hits."""
    from paddle_tpu.serving import InferenceServer, fleet
    from paddle_tpu.serving.kvpool import _PREFIX_HITS
    cfg, scope = tiny_gpt
    set_flags({"FLAGS_kv_prefix_cache": True})

    def mksrv(name):
        g = GPTGenerator(cfg, scope, max_len=48, bucket_min=8)
        return InferenceServer(generator=g,
                               decode_slots=2,
                               kv_pool_name=name).start()

    s1, s2 = mksrv("pod_aff_a"), mksrv("pod_aff_b")
    router = fleet.Router([s1.endpoint, s2.endpoint],
                          name="pod_aff").start(serve_network=False)
    try:
        prompt = _prompts(cfg, [12], seed=11)[0]
        outs = [router.generate(prompt, max_new_tokens=6)
                for _ in range(3)]
        for o in outs[1:]:
            np.testing.assert_array_equal(o, outs[0])
        st = router.stats()
        assert st["router_prefix_hits"] >= 2, st
        assert st["router_prefix_misses"] >= 1, st
        assert st["affinity_table"] >= 1
        h1, h2 = s1.health(), s2.health()
        assert "kvpool_evictable_blocks" in h1
        assert h1["kvpool_evictable_blocks"] \
            + h2["kvpool_evictable_blocks"] > 0
        snap = router.registry.snapshot()
        assert all("kvpool_evictable_blocks" in v
                   for v in snap.values())
        pool_hits = sum(_PREFIX_HITS.value(labels=(n,)) or 0
                        for n in ("pod_aff_a", "pod_aff_b"))
        assert pool_hits >= 2
    finally:
        router.stop()
        s1.stop()
        s2.stop()
