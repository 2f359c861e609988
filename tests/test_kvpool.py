"""Paged KV-cache subsystem (serving/kvpool + kernels/paged_attention +
the paged decode wiring): free-list allocator invariants (alloc/free/
exhaustion/leak sweep), paged==dense bitwise greedy parity offline and
through the serving decode bank with slot reuse, block frees on
EOS/deadline/cancel (pool returns to empty), typed KVPoolExhaustedError
backpressure at the door / admission / mid-decode, bf16+int8
quantized-cache quality gates, the ``serving.kv_alloc`` chaos point,
and Pallas-interpret vs XLA-reference kernel parity."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.serving.kvpool import KVBlockPool, KVPoolExhaustedError


def _pool(**kw):
    kw.setdefault("slots", 4)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("d_head", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("name", "test")
    return KVBlockPool(**kw)


@pytest.fixture(scope="module")
def tiny_gen():
    """One initialized tiny-GPT scope + generator per module (the paged
    decode programs compile once into the generator's cache)."""
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


@pytest.fixture
def paged_flags():
    """Route serving through the paged pool for one test; always
    restored (the dense bank stays the suite-wide default)."""
    from paddle_tpu.flags import set_flags
    set_flags({"kv_paged": True})
    yield
    set_flags({"kv_paged": False, "kv_cache_dtype": "fp32",
               "kv_pool_blocks": 0, "kv_block_size": 16})


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_alloc_grows_and_free_returns_everything():
    p = _pool(num_blocks=9)                   # 8 allocatable + trash
    assert p.capacity_blocks == 8
    assert p.alloc(0, 1) == 1                 # first token -> 1 block
    assert p.alloc(0, 8) == 0                 # same block covers 8
    assert p.alloc(0, 9) == 1                 # 9th token opens block 2
    assert p.blocks_in_use() == 2
    # the table names real (nonzero) blocks exactly for held blocks
    assert all(b > 0 for b in p.tables[0, :2])
    assert all(b == 0 for b in p.tables[0, 2:])
    assert p.free_slot(0) == 2
    assert p.free_slot(0) == 0                # idempotent
    assert p.blocks_in_use() == 0
    assert (p.tables == 0).all()


def test_alloc_exhaustion_is_typed_and_leaves_state_untouched():
    p = _pool(num_blocks=4)                   # 3 allocatable
    p.alloc(0, 16)                            # 2 blocks
    before = dict(tables=p.tables.copy(), in_use=p.blocks_in_use())
    with pytest.raises(KVPoolExhaustedError) as ei:
        p.alloc(1, 17)                        # needs 3, 1 free
    assert ei.value.needed == 3 and ei.value.free == 1
    assert ei.value.capacity == 3
    # backpressure contract: the typed error IS ServerOverloadedError
    assert isinstance(ei.value, serving.ServerOverloadedError)
    # nothing changed: slot 1 holds no blocks, tables untouched
    assert p.blocks_in_use() == before["in_use"]
    np.testing.assert_array_equal(p.tables, before["tables"])
    p.free_slot(0)
    assert p.alloc(1, 17) == 3                # retry after frees works


def test_check_fits_rejects_never_admittable_request():
    p = _pool(num_blocks=4)                   # 24-token capacity
    p.check_fits(24)                          # exactly fits: fine
    # a request the pool could NEVER hold is a TERMINAL BadRequest
    # (backing off cannot help), not the retryable Overloaded shed
    with pytest.raises(serving.BadRequestError, match="never"):
        p.check_fits(25)


def test_admission_check_counts_pending_round():
    p = _pool(num_blocks=9)                   # 8 allocatable
    p.admission_check(32, pending_tokens=[32])       # 4 + 4 == 8 free
    with pytest.raises(KVPoolExhaustedError):
        p.admission_check(33, pending_tokens=[32])   # 5 + 4 > 8
    assert p.blocks_in_use() == 0             # the gate allocates nothing


def test_reclaim_leaks_frees_and_flight_records():
    from paddle_tpu.observability.recorder import flight_recorder
    p = _pool(num_blocks=9)
    p.alloc(0, 10)
    p.alloc(2, 5)
    rec_before = flight_recorder().counts().get("kv_block_leak", 0)
    assert p.reclaim_leaks(live_slots=[0, 2]) == 0    # nothing leaked
    assert p.reclaim_leaks(live_slots=[0]) == 1       # slot 2 leaked
    assert p.blocks_in_use() == 2                     # slot 0 intact
    events = [e for e in flight_recorder().snapshot()
              if e["kind"] == "kv_block_leak"]
    assert len(events) - rec_before >= 1
    assert events[-1]["slot"] == 2 and events[-1]["blocks"] == 1


def test_stats_occupancy_and_fragmentation():
    p = _pool(num_blocks=9, block_size=8)
    p.alloc(0, 9)                 # 2 blocks for 9 tokens: 7 slack slots
    st = p.stats()
    assert st["capacity_blocks"] == 8 and st["blocks_in_use"] == 2
    assert st["occupancy"] == pytest.approx(0.25)
    assert st["fragmentation"] == pytest.approx(1 - 9 / 16)
    assert st["tokens_held"] == 9
    assert st["saved_vs_dense_bytes"] == (
        p.slots * p.dense_slot_bytes() - 2 * p.block_bytes())
    # the registry exports the same numbers as kvpool_* gauges
    from paddle_tpu.serving.kvpool import _BLOCKS_IN_USE, _OCCUPANCY
    assert _BLOCKS_IN_USE.value(labels=(p.name,)) == 2
    assert _OCCUPANCY.value(labels=(p.name,)) == pytest.approx(0.25)


def test_pool_config_validation():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        _pool(dtype="fp16")
    with pytest.raises(ValueError, match="trash"):
        _pool(num_blocks=1)


# ---------------------------------------------------------------------------
# kernel: Pallas interpret vs XLA reference, quant codec
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_zero_and_scale():
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import (dequantize_kv,
                                                    quantize_kv)
    kv = jnp.asarray(np.random.default_rng(0).normal(
        size=(3, 2, 8, 16)).astype(np.float32))
    q, sc = quantize_kv(kv)
    assert q.dtype == jnp.int8 and sc.shape == kv.shape[:-1]
    err = np.max(np.abs(np.asarray(dequantize_kv(q, sc)) -
                        np.asarray(kv)))
    # symmetric absmax: worst case half a step of the per-vector scale
    assert err <= float(np.max(np.asarray(sc))) * 0.5 + 1e-6
    # an all-zero vector round-trips exactly (scale guarded to 1.0)
    qz, sz = quantize_kv(jnp.zeros((2, 4)))
    assert np.all(np.asarray(sz) == 1.0)
    assert np.all(np.asarray(dequantize_kv(qz, sz)) == 0.0)


def test_paged_attention_interpret_matches_xla_reference():
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import (paged_attention,
                                                    quantize_kv)
    rng = np.random.default_rng(1)
    B, H, D, bs, nblk, N = 3, 2, 16, 8, 4, 12
    q = jnp.asarray(rng.normal(size=(B, H, 1, D)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(N, H, bs, D)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(N, H, bs, D)).astype(np.float32))
    tables = jnp.asarray(rng.integers(1, N, (B, nblk)).astype(np.int32))
    pos = jnp.asarray(np.array([3, 17, 30], np.int32))

    ref = paged_attention(q, kp, vp, tables, pos, impl="xla")
    out = paged_attention(q, kp, vp, tables, pos, impl="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)
    qk, ks = quantize_kv(kp)
    qv, vs = quantize_kv(vp)
    ref8 = paged_attention(q, qk, qv, tables, pos, k_scale=ks,
                           v_scale=vs, impl="xla")
    out8 = paged_attention(q, qk, qv, tables, pos, k_scale=ks,
                           v_scale=vs, impl="interpret")
    np.testing.assert_allclose(np.asarray(out8), np.asarray(ref8),
                               atol=1e-5)


# the kernel's grid: (rows, table width / G) steps of G blocks, all heads
# a step. H=16 takes the serve cell's tile (bs 16, D 64: G is 8 from the
# score budget); H=2 takes a small one with the budget lowered to G = 4,
# so both walk a table that is a multiple of G, one that is not, and one
# narrower than the budget's G
_GRID_SHAPES = {2: dict(bs=4, D=16, score_lanes=4 * 2 * 4,
                        widths={"multiple": 8, "ragged": 6, "narrow": 2}),
                16: dict(bs=16, D=64, score_lanes=None,
                         widths={"multiple": 16, "ragged": 11,
                                 "narrow": 4})}


def _grid_positions(pattern, bs, nblk):
    """(positions, rows whose table is all trash) of one pattern."""
    mid = min(bs + bs // 2, nblk * bs - 2)
    one = {"first_slot": 0, "block_end": bs - 1, "block_start": bs,
           "mid_block": mid, "last_slot": nblk * bs - 1}
    if pattern in one:
        return [one[pattern]], []
    if pattern == "mixed":
        return list(one.values()), []
    assert pattern == "free_row"
    return [mid, 0, nblk * bs - 1, mid], [1, 3]


@pytest.mark.parametrize("H", [2, 16])
@pytest.mark.parametrize("width", ["multiple", "ragged", "narrow"])
@pytest.mark.parametrize("pattern", [
    "first_slot", "block_end", "block_start", "mid_block", "last_slot",
    "mixed", "free_row"])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_paged_kernel_grid_matches_oracle(monkeypatch, kv_dtype, pattern,
                                          width, H):
    """The grouped grid through the Pallas interpreter against the
    gather composite: every pool type, positions at each edge of a block
    and of the table, alone and mixed in one batch, a free row (table
    all trash block 0) beside live ones, and every way the table's
    width meets G."""
    import importlib
    import jax.numpy as jnp
    # the package exports the function under the module's name
    pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
    shape = _GRID_SHAPES[H]
    bs, D, nblk = shape["bs"], shape["D"], shape["widths"][width]
    if shape["score_lanes"]:
        monkeypatch.setattr(pa, "_SCORE_LANES", shape["score_lanes"])
    (_, steps), G = pa.decode_grid(1, H, bs, D, jnp.float32, nblk)
    assert G == {"multiple": nblk // 2, "ragged": 8 if H == 16 else 4,
                 "narrow": nblk}[width]
    assert steps == -(-nblk // G)

    pos, free = _grid_positions(pattern, bs, nblk)
    B, N = len(pos), len(pos) * nblk + 1
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, H, 1, D)).astype(np.float32))
    kp, vp = (jnp.asarray(rng.normal(size=(N, H, bs, D)).astype(np.float32))
              for _ in range(2))
    tables = rng.permutation(np.arange(1, N)).reshape(B, nblk)
    tables[free] = 0
    tables = jnp.asarray(tables.astype(np.int32))
    pos = jnp.asarray(np.array(pos, np.int32))
    if kv_dtype == "int8":
        (kp, ks), (vp, vs) = pa.quantize_kv(kp), pa.quantize_kv(vp)
        scales = dict(k_scale=ks, v_scale=vs)
    else:
        dt = jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32
        kp, vp, scales = kp.astype(dt), vp.astype(dt), {}
    ref = pa.paged_attention(q, kp, vp, tables, pos, impl="xla", **scales)
    out = pa.paged_attention(q, kp, vp, tables, pos, impl="interpret",
                             **scales)
    # a free row reads one slot of the trash block, like the oracle
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_kernel_names_a_block_too_large_for_vmem():
    """A block whose K and V tiles cannot sit double-buffered in the
    kernel's VMEM budget is an error that names the shape, not a silent
    fall to the composite."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import paged_attention
    pool = jax.ShapeDtypeStruct((3, 16, 512, 128), jnp.float32)
    with pytest.raises(ValueError, match=r"H=16, block_size=512, D=128"):
        jax.eval_shape(
            lambda q, k, v, t, p: paged_attention(q, k, v, t, p,
                                                  impl="interpret"),
            jax.ShapeDtypeStruct((1, 16, 1, 128), jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32))


def test_paged_attention_input_validation():
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import paged_attention
    q = jnp.zeros((1, 2, 2, 8))               # S=2: prefill shape
    kp = vp = jnp.zeros((4, 2, 8, 8))
    tables = jnp.zeros((1, 2), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="ONE query"):
        paged_attention(q, kp, vp, tables, pos, impl="interpret")
    with pytest.raises(ValueError, match="BOTH"):
        paged_attention(q[:, :, :1], kp, vp, tables, pos,
                        k_scale=jnp.zeros((4, 2, 8)))
    with pytest.raises(ValueError, match="int8"):
        paged_attention(q[:, :, :1], kp.astype(jnp.int8),
                        vp.astype(jnp.int8), tables, pos)


# ---------------------------------------------------------------------------
# offline generation parity + quantized quality gate
# ---------------------------------------------------------------------------

def test_paged_generate_bitwise_greedy_parity(tiny_gen):
    """generate(paged=True) over the block pool must be token-for-token
    identical to the dense-bank fast path (itself gated against naive
    full recompute in test_decode.py), across ragged lengths."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12))
    dense = gen.generate(prompts, max_new_tokens=14, seed=0)
    paged = gen.generate(prompts, max_new_tokens=14, seed=0, paged=True)
    for a, b in zip(dense, paged):
        np.testing.assert_array_equal(a, b)
        assert b.dtype == np.int32


def test_quantized_cache_greedy_quality_gate(tiny_gen):
    """bf16/int8 pools generate full-length outputs whose greedy tokens
    stay in high agreement with the fp32 dense reference (cache
    quantization perturbs logits but must not derail generation)."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12))
    dense = gen.generate(prompts, max_new_tokens=14, seed=0)
    for kv_dtype, floor in (("bf16", 0.9), ("int8", 0.75)):
        outs = gen.generate(prompts, max_new_tokens=14, seed=0,
                            paged=True, kv_dtype=kv_dtype)
        agree = []
        for ref, out in zip(dense, outs):
            assert out.shape == ref.shape and out.dtype == np.int32
            agree.append(float(np.mean(out == ref)))
        assert np.mean(agree) >= floor, (kv_dtype, agree)


def test_offline_paged_pool_is_transient(tiny_gen):
    """The offline paged loop frees its pool on the way out — the
    'offline' gauge series reads 0 blocks in use after generate()."""
    from paddle_tpu.serving.kvpool import _BLOCKS_IN_USE
    cfg, _, gen = tiny_gen
    gen.generate(_prompts(cfg, (6,)), max_new_tokens=4, paged=True)
    assert _BLOCKS_IN_USE.value(labels=("offline",)) == 0


def test_chaos_kv_alloc_point_offline(tiny_gen, fault_points):
    """The ``serving.kv_alloc`` chaos point fires inside the allocator:
    an armed generate fails with the injected fault, and the next
    (unarmed) call runs clean on a fresh pool."""
    from paddle_tpu.resilience import FaultInjected, chaos
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (6,))
    with chaos("serving.kv_alloc", times=1):
        with pytest.raises(FaultInjected):
            gen.generate(prompts, max_new_tokens=4, paged=True)
    out = gen.generate(prompts, max_new_tokens=4, paged=True)
    assert out[0].shape == (4,)


# ---------------------------------------------------------------------------
# serving: parity through the decode bank, frees, typed shed
# ---------------------------------------------------------------------------

def test_serving_paged_parity_slot_reuse_and_drain(tiny_gen,
                                                   paged_flags):
    """More requests than slots through the paged decode bank: every
    request matches the dense greedy reference (slot reuse re-routes a
    fresh row's blocks through a just-freed slot's table row), stats
    surface kvpool_*, and the pool returns to EMPTY when all rows
    finished — the free-on-EOS invariant after a soak."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12, 7, 4), seed=17)
    ref = gen.generate(prompts, max_new_tokens=9, seed=0)

    server = serving.InferenceServer(generator=gen, decode_slots=2)
    server.start(serve_network=False)
    try:
        assert server.gen_engine.pool is not None
        reqs = [server.submit_generate(p, max_new_tokens=9)
                for p in prompts]
        outs = [r.wait(timeout=120)[0] for r in reqs]
        for got, want in zip(outs, ref):
            np.testing.assert_array_equal(got, want)
        st = server.stats()
        assert st["kvpool_blocks_in_use"] == 0       # pool drained
        assert st["kvpool_capacity_blocks"] > 0
        assert st["decode_free_slots"] == 2
        pool = server.gen_engine.pool
        assert pool.blocks_in_use() == 0 and pool.holders() == {}
    finally:
        server.stop()


def test_paged_deadline_and_cancel_free_blocks(tiny_gen, paged_flags):
    """A row that dies mid-generation (token-level deadline, client
    cancel) returns its blocks immediately — driven synchronously so
    the expiry point is deterministic."""
    import time
    from paddle_tpu.serving.batching import (DecodeBatcher,
                                             GenerationRequest,
                                             RequestCancelledError,
                                             RequestQueue)
    cfg, _, gen = tiny_gen
    engine = serving.GenerationEngine(gen, slots=2, paged=True)
    batcher = DecodeBatcher(RequestQueue(max_depth=8), engine)
    pool = engine.pool

    # deadline: admitted, holding blocks, then the budget lapses
    req = GenerationRequest(_prompts(cfg, (6,), seed=29)[0],
                            max_new_tokens=40, deadline_ms=150.0)
    batcher.queue.put(req)
    batcher._admit()
    assert req.slot is not None and pool.blocks_in_use() > 0
    time.sleep(0.2)
    batcher._check_deadlines(time.monotonic())
    assert req.done() and pool.blocks_in_use() == 0

    # cancel/error: _finish is the one reclaim path for every exit
    req2 = GenerationRequest(_prompts(cfg, (9,), seed=31)[0],
                             max_new_tokens=30)
    batcher.queue.put(req2)
    batcher._admit()
    assert pool.blocks_in_use() > 0
    batcher._finish(req2, RequestCancelledError("client went away"))
    assert pool.blocks_in_use() == 0 and pool.holders() == {}
    with pytest.raises(RequestCancelledError):
        req2.wait(timeout=0.1)


def test_pool_exhaustion_typed_shed_and_recovery(tiny_gen, paged_flags):
    """A request whose blocks are not free RIGHT NOW is shed typed at
    admission (KVPoolExhaustedError is ServerOverloadedError: the
    client backs off), the rows already decoding are untouched, and the
    same request admits cleanly once blocks return."""
    from paddle_tpu.serving.batching import (DecodeBatcher,
                                             GenerationRequest,
                                             RequestQueue)
    cfg, _, gen = tiny_gen
    # 5 allocatable blocks of 8 tokens: one 32-token prompt (4 blocks
    # + 1 decode-growth block) fills the pool exactly
    engine = serving.GenerationEngine(gen, slots=2, paged=True,
                                      kv_block_size=8, kv_pool_blocks=6)
    batcher = DecodeBatcher(RequestQueue(max_depth=8), engine)
    big = GenerationRequest(_prompts(cfg, (32,), seed=5)[0],
                            max_new_tokens=4)
    batcher.queue.put(big)
    batcher._admit()
    assert big.slot is not None

    shed = GenerationRequest(_prompts(cfg, (32,), seed=6)[0],
                             max_new_tokens=4)
    batcher.queue.put(shed)
    batcher._admit()
    with pytest.raises(KVPoolExhaustedError):
        shed.wait(timeout=0.1)
    assert not big.done()                    # the live row kept its slot

    # blocks return -> the identical request is admitted and completes
    batcher._finish(big)
    assert engine.pool.blocks_in_use() == 0
    retry = GenerationRequest(shed.prompt, max_new_tokens=4)
    batcher.queue.put(retry)
    batcher._admit()
    assert retry.slot is not None


def test_exhaustion_flight_recorded(tiny_gen, paged_flags):
    """Shed admissions leave a kv_pool_exhausted event in the flight
    recorder (+ the kvpool_alloc_failures_total counter) so debug_dump
    explains them."""
    from paddle_tpu.observability.recorder import flight_recorder
    from paddle_tpu.serving.kvpool import _ALLOC_FAIL
    cfg, _, gen = tiny_gen
    engine = serving.GenerationEngine(gen, slots=2, paged=True,
                                      kv_block_size=8, kv_pool_blocks=6)
    fails0 = _ALLOC_FAIL.value(labels=("serving",))
    with pytest.raises(KVPoolExhaustedError):
        engine.admission_check(32, 4, pending_tokens=[32])
    events = [e for e in flight_recorder().snapshot()
              if e["kind"] == "kv_pool_exhausted"]
    assert events and events[-1]["pool"] == "serving"
    assert _ALLOC_FAIL.value(labels=("serving",)) == fails0 + 1


# ---------------------------------------------------------------------------
# admission-at-the-door regression (overlong + never-fitting requests)
# ---------------------------------------------------------------------------

def test_overlong_prompt_rejected_at_door_over_wire(tiny_gen):
    """Regression: a prompt + max_new_tokens beyond the cache length is
    refused with a typed BadRequest AT SUBMIT — before any queue wait
    or prefill compile — in-process and over the wire (the offline
    generate() path was previously the only place this was checked)."""
    cfg, _, gen = tiny_gen
    server = serving.InferenceServer(generator=gen, decode_slots=2)
    server.start()
    try:
        overlong = np.arange(1, 47, dtype=np.int32)       # 46 + 8 > 48
        with pytest.raises(serving.BadRequestError, match="exceeds"):
            server.submit_generate(overlong, max_new_tokens=8)
        with serving.Client(server.endpoint) as c:
            with pytest.raises(serving.BadRequestError, match="exceeds"):
                c.generate(overlong, max_new_tokens=8)
        # the door refused before touching the engine: no prefill ran
        assert server.stats()["prefill_count"] == 0
        # a request that fits still works end to end
        out = server.generate(np.arange(1, 7, dtype=np.int32),
                              max_new_tokens=3, timeout=60)
        assert out.shape == (3,)
    finally:
        server.stop()


def test_never_fitting_request_rejected_at_door_paged(tiny_gen,
                                                      paged_flags):
    """Paged mode adds the pool-capacity door check: a request bigger
    than the WHOLE pool is refused as a terminal BadRequest at submit
    (retry can never help at this pool size) — distinct from the
    transient wait-and-retry Overloaded shed."""
    from paddle_tpu.flags import set_flags
    cfg, _, gen = tiny_gen
    set_flags({"kv_block_size": 8, "kv_pool_blocks": 4})  # 24 tokens
    server = serving.InferenceServer(generator=gen, decode_slots=2)
    server.start(serve_network=False)
    try:
        with pytest.raises(serving.BadRequestError, match="never"):
            server.submit_generate(np.arange(1, 22, dtype=np.int32),
                                   max_new_tokens=8)       # 29 tokens
    finally:
        server.stop()
