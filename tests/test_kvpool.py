"""Paged KV-cache subsystem (serving/kvpool + kernels/paged_attention +
the paged decode wiring): free-list allocator invariants (alloc/free/
exhaustion/leak sweep), paged==naive bitwise greedy parity offline and
through the serving decode bank with slot reuse, block frees on
EOS/deadline/cancel (pool returns to empty), typed KVPoolExhaustedError
backpressure at the door / admission / mid-decode, bf16+int8
quantized-cache quality gates, the ``serving.kv_alloc`` chaos point,
Pallas-interpret vs XLA-reference kernel parity, and the pool's stored
shape with its one-token writer ``paged_kv_append``."""
import importlib
import types

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.kernels import _dispatch
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator
from paddle_tpu.ops import decode_ops
from paddle_tpu.serving.kvpool import (KVBlockPool, KVPoolExhaustedError,
                                       _np_pool_dtype, count_pool_relayouts,
                                       pool_element_counts)


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
    """Restore the pool's flags after a test that sets them."""
    from paddle_tpu.flags import set_flags
    yield
    set_flags({"kv_cache_dtype": "fp32",
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


# the kernel's walk: a step for every G live blocks of a row, all heads a
# step. H=16 takes the serve cell's tile (bs 16, D 64: G is 8 from the
# score budget); H=2 takes a small one with the budget lowered to G = 4,
# so both walk a table that is a multiple of G, one that is not, and one
# narrower than the budget's G. H=16 and H=12 are D = 64 packed two
# slots a lane row (the pool's stored shape); H=12 (GPT-base's heads, G
# is 8 too) is fed STORED arrays and pads its two parities to 16 rows each
_GRID_SHAPES = {2: dict(bs=4, D=16, score_lanes=4 * 2 * 4,
                        widths={"multiple": 8, "ragged": 6, "narrow": 2}),
                16: dict(bs=16, D=64, score_lanes=None,
                         widths={"multiple": 16, "ragged": 11,
                                 "narrow": 4}),
                12: dict(bs=16, D=64, score_lanes=None, stored=True,
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


@pytest.mark.parametrize("H", [2, 16, 12])
@pytest.mark.parametrize("width", ["multiple", "ragged", "narrow"])
@pytest.mark.parametrize("pattern", [
    "first_slot", "block_end", "block_start", "mid_block", "last_slot",
    "mixed", "free_row"])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_paged_kernel_grid_matches_oracle(monkeypatch, kv_dtype, pattern,
                                          width, H):
    """The kernel's walk through the Pallas interpreter against the
    gather composite: every pool type, positions at each edge of a block
    and of the table, alone and mixed in one batch, a free row (table
    all trash block 0) beside live ones, every way the table's width
    meets G, and the pool fed logical or stored."""
    import importlib
    import jax.numpy as jnp
    # the package exports the function under the module's name
    pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
    shape = _GRID_SHAPES[H]
    bs, D, nblk = shape["bs"], shape["D"], shape["widths"][width]
    if shape["score_lanes"]:
        monkeypatch.setattr(pa, "_SCORE_LANES", shape["score_lanes"])
    G = pa.blocks_per_step(H, bs, D, jnp.float32, nblk)
    assert G == {"multiple": nblk // 2, "ragged": 4 if H == 2 else 8,
                 "narrow": nblk}[width]
    # a row at the table's last slot walks the whole table
    assert pa.row_steps(nblk * bs - 1, bs, G, nblk) == (
        0, nblk, -(-nblk // G))

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
    if shape.get("stored"):
        assert pa.pool_packing(D, bs) == 2
        kp, vp = pa.to_stored(kp), pa.to_stored(vp)
        scales = {k: pa.scales_to_stored(v, D) for k, v in scales.items()}
        scales["kv_heads"] = H
    out = np.asarray(pa.paged_attention(q, kp, vp, tables, pos,
                                        impl="interpret", **scales))
    # a free row is not walked: the kernel writes it zeros (the oracle
    # reads it the trash block)
    live = np.setdiff1d(np.arange(B), free)
    np.testing.assert_allclose(out[live], np.asarray(ref)[live], atol=1e-5)
    assert not out[free].any()


# the serve cells' decode shapes cut small: (query heads, KV heads, D,
# block, table width, window, pool dtype) with the score budget lowered
# so that a step is G blocks of a wider table
_WALK_SHAPES = {
    "gpt2-medium": (4, 4, 64, 4, 12, None, "bf16"),          # f = 2
    "gpt2-medium-int8": (4, 4, 64, 4, 12, None, "int8"),
    "ouro": (2, 2, 128, 4, 12, None, "bf16"),                # f = 1
    "ouro-int8": (2, 2, 128, 4, 12, None, "int8"),
    "mellum-full": (8, 1, 128, 8, 12, None, "bf16"),         # 8 a KV head
    "mellum-window": (8, 1, 128, 4, 12, 32, "bf16"),         # a ring of 12
}


def _walk_rows(pattern, G, nblk, reach):
    """Blocks of context a row (0: a free slot) of one pattern, ``reach``
    the most blocks a row reads (the table, or the window's)."""
    return {"one_block": [1], "one_step": [G], "step_and_a_block": [G + 1],
            "whole_table": [nblk, reach],
            "free_beside_live": [0, G + 1, 0, 1, 3 * nblk],
            "no_live_row": [0, 0, 0]}[pattern]


@pytest.mark.parametrize("pattern", [
    "one_block", "one_step", "step_and_a_block", "whole_table",
    "free_beside_live", "no_live_row"])
@pytest.mark.parametrize("shape", list(_WALK_SHAPES))
def test_paged_kernel_walks_live_blocks_only(monkeypatch, shape, pattern):
    """The kernel that walks a row's blocks itself, through the TPU
    interpreter (DMA semaphores counted, scratch memory NaN until
    written): the oracle's numbers on every row that holds blocks, zeros
    on a free slot, and as many folds a row as ``row_steps`` says, so a
    free slot and a table's dead tail cost none."""
    import importlib
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
    hq, hkv, D, bs, nblk, window, kv_dtype = _WALK_SHAPES[shape]
    monkeypatch.setattr(pa, "_SCORE_LANES", 2 * 2 * hkv * bs)
    G = pa.blocks_per_step(hkv, bs, D, jnp.float32, nblk, window)
    assert G == 4 and G < nblk
    reach = nblk if window is None else pa.window_blocks(window, bs)
    ctx = np.array(_walk_rows(pattern, G, nblk, reach))
    if window is None:
        ctx = np.minimum(ctx, nblk)
    B, N = len(ctx), len(ctx) * nblk + 1
    rng = np.random.default_rng(11)
    pos = np.maximum(ctx * bs - 1 - rng.integers(0, bs, B), 0)
    q = jnp.asarray(rng.normal(size=(B, hq, 1, D)).astype(np.float32))
    kp, vp = (jnp.asarray(rng.normal(size=(N, hkv, bs, D)).astype(
        np.float32)) for _ in range(2))
    # a row holds blocks in the columns it has reached (a ring: in all
    # of them once it has gone round); the rest is the trash block
    held = np.minimum(ctx, nblk)
    tables = np.zeros((B, nblk), np.int32)
    tables[np.arange(nblk) < held[:, None]] = \
        rng.permutation(np.arange(1, N))[:held.sum()]
    tables, pos = jnp.asarray(tables), jnp.asarray(pos.astype(np.int32))
    ks = vs = None
    if kv_dtype == "int8":
        (kp, ks), (vp, vs) = pa.quantize_kv(kp), pa.quantize_kv(vp)
    else:
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    ref = pa.paged_attention(q, kp, vp, tables, pos, ks, vs, impl="xla",
                             window=window)
    if ks is not None:
        ks, vs = pa.scales_to_stored(ks, D), pa.scales_to_stored(vs, D)
    out, folds = pa._pallas_paged_attention(
        q, pa.to_stored(kp), pa.to_stored(vp), tables, pos, ks, vs,
        float(D) ** -0.5, pltpu.InterpretParams(uninitialized_memory="nan"),
        hq // hkv, window, count_folds=True)
    out, live = np.asarray(out), ctx > 0
    np.testing.assert_allclose(out[live], np.asarray(ref)[live], atol=1e-5)
    assert not out[~live].any()
    _, blocks, steps = pa.row_steps(np.asarray(pos), bs, G, nblk, window)
    assert np.array_equal(np.asarray(folds), np.where(live, steps, 0))
    if window is None:
        assert np.array_equal(blocks[live], ctx[live])
    assert (blocks <= reach).all()
    assert (steps[live] == -(-blocks[live] // G)).all()


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
    """generate() over the fp32 block pool must be token-for-token
    identical to naive full recompute, across ragged lengths, with the
    pool's dtype named (test_decode.py: left to ``FLAGS_kv_cache_dtype``)."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12))
    naive = gen.generate_naive(prompts, max_new_tokens=14, seed=0)
    paged = gen.generate(prompts, max_new_tokens=14, seed=0,
                         kv_dtype="fp32")
    for a, b in zip(naive, paged):
        np.testing.assert_array_equal(a, b)
        assert b.dtype == np.int32


def test_quantized_cache_greedy_quality_gate(tiny_gen):
    """bf16/int8 pools generate full-length outputs whose greedy tokens
    stay in high agreement with the full-recompute reference (cache
    quantization perturbs logits but must not derail generation)."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12))
    naive = gen.generate_naive(prompts, max_new_tokens=14, seed=0)
    for kv_dtype, floor in (("bf16", 0.9), ("int8", 0.75)):
        outs = gen.generate(prompts, max_new_tokens=14, seed=0,
                            kv_dtype=kv_dtype)
        agree = []
        for ref, out in zip(naive, outs):
            assert out.shape == ref.shape and out.dtype == np.int32
            agree.append(float(np.mean(out == ref)))
        assert np.mean(agree) >= floor, (kv_dtype, agree)


def test_offline_paged_pool_is_transient(tiny_gen):
    """The offline loop frees its pool on the way out — the
    'offline' gauge series reads 0 blocks in use after generate()."""
    from paddle_tpu.serving.kvpool import _BLOCKS_IN_USE
    cfg, _, gen = tiny_gen
    gen.generate(_prompts(cfg, (6,)), max_new_tokens=4)
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
            gen.generate(prompts, max_new_tokens=4)
    out = gen.generate(prompts, max_new_tokens=4)
    assert out[0].shape == (4,)


# ---------------------------------------------------------------------------
# serving: parity through the decode bank, frees, typed shed
# ---------------------------------------------------------------------------

def test_serving_paged_parity_slot_reuse_and_drain(tiny_gen):
    """More requests than slots through the paged decode bank: every
    request matches the full-recompute greedy reference (slot reuse re-routes a
    fresh row's blocks through a just-freed slot's table row), stats
    surface kvpool_*, and the pool returns to EMPTY when all rows
    finished — the free-on-EOS invariant after a soak."""
    cfg, _, gen = tiny_gen
    prompts = _prompts(cfg, (5, 9, 12, 7, 4), seed=17)
    ref = gen.generate_naive(prompts, max_new_tokens=9, seed=0)

    # no keyword, no flag: a server serves through a pool
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
        assert "decode_paged_fp32" in st["pool_relayouts"]
        assert st["kvpool_blocks_in_use"] == 0       # pool drained
        assert st["kvpool_capacity_blocks"] > 0
        assert st["decode_free_slots"] == 2
        pool = server.gen_engine.pool
        assert pool.blocks_in_use() == 0 and pool.holders() == {}
    finally:
        server.stop()


def test_paged_deadline_and_cancel_free_blocks(tiny_gen):
    """A row that dies mid-generation (token-level deadline, client
    cancel) returns its blocks immediately — driven synchronously so
    the expiry point is deterministic."""
    import time
    from paddle_tpu.serving.batching import (DecodeBatcher,
                                             GenerationRequest,
                                             RequestCancelledError,
                                             RequestQueue)
    cfg, _, gen = tiny_gen
    engine = serving.GenerationEngine(gen, slots=2)
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


def test_pool_exhaustion_typed_shed_and_recovery(tiny_gen):
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
    engine = serving.GenerationEngine(gen, slots=2,
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


def test_exhaustion_flight_recorded(tiny_gen):
    """Shed admissions leave a kv_pool_exhausted event in the flight
    recorder (+ the kvpool_alloc_failures_total counter) so debug_dump
    explains them."""
    from paddle_tpu.observability.recorder import flight_recorder
    from paddle_tpu.serving.kvpool import _ALLOC_FAIL
    cfg, _, gen = tiny_gen
    engine = serving.GenerationEngine(gen, slots=2,
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
    """The pool-capacity door check: a request bigger
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


# ------------------------------------------------------- the stored shape
# The pool's stored shape (``kernels/paged_attention``: logical ``[N, H,
# bs, D]`` held as ``[N, H * bs // f, f * D]``): the one-token writer
# ``paged_kv_append`` through the Pallas interpreter against the composite
# write, the block-row writers and the migration round trip on the stored
# arrays, and the count of pool-sized copies.

# the package exports the function under the module's name
pa = importlib.import_module("paddle_tpu.kernels.paged_attention")

# f = 2: the serve cells' head width over a block of 16 (slots 2r and
# 2r + 1 of a head share a lane row); f = 1: Mellum's, a row a slot
_SHAPES = {2: dict(H=4, bs=16, D=64), 1: dict(H=2, bs=16, D=128)}
_CTX = types.SimpleNamespace(abstract=False, mesh=None)


def _write(pool, kv, tables, pos, scale=None, ring=False, limit=None):
    ins = {"Cache": [pool], "KV": [kv], "Tables": [tables], "Pos": [pos]}
    if scale is not None:
        ins["Scale"] = [scale]
    if limit is not None:
        ins["Limit"] = [limit]
    return decode_ops.paged_kv_cache_write(_CTX, ins, {"ring": ring})


def _grew(before, op, impl, reason):
    key = (op, impl, reason)
    return _dispatch.resolved_counts().get(key, 0) - before.get(key, 0)


def test_packing_follows_the_head_width_and_the_block():
    assert pa.pool_packing(64, 16) == 2
    assert pa.pool_packing(128, 16) == 1 and pa.pool_packing(256, 16) == 1
    assert pa.pool_packing(32, 16) == 4
    # what the packing cannot take keeps a row a slot: a width that does
    # not divide the lanes, a block the lane row's slots do not divide
    assert pa.pool_packing(48, 16) == 1 and pa.pool_packing(16, 4) == 1
    assert pa.stored_shape(2049, 16, 16, 64) == (2049, 128, 128)
    assert pa.stored_shape(16385, 4, 16, 128) == (16385, 64, 128)
    assert pa.stored_shape(2081, 4, 16, 128) == (2081, 64, 128)


@pytest.mark.parametrize("f", [1, 2])
def test_stored_and_logical_views_are_the_same_bytes(f):
    s = _SHAPES[f]
    H, bs, D = s["H"], s["bs"], s["D"]
    x = np.arange(3 * H * bs * D, dtype=np.float32).reshape(3, H, bs, D)
    st = pa.to_stored(x)
    assert st.shape == (3, H * bs // f, f * D)
    assert np.array_equal(st.reshape(-1), x.reshape(-1))
    assert np.array_equal(pa.to_logical(st, H, D), x)
    # slot t of head h: row h * (bs // f) + t // f, lanes (t % f) * D ...
    h, t = H - 1, bs - 1
    row, lane = h * (bs // f) + t // f, (t % f) * D
    assert np.array_equal(st[1, row, lane:lane + D], x[1, h, t])
    sc = np.arange(3 * H * bs, dtype=np.float32).reshape(3, H, bs)
    ss = pa.scales_to_stored(sc, D)
    assert ss.shape == (3, f, H * bs // f)
    assert ss[1, t % f, row] == sc[1, h, t]
    assert np.array_equal(pa.scales_to_logical(ss, H), sc)
    # the same helpers on device arrays
    assert np.array_equal(np.asarray(pa.scales_to_logical(
        pa.scales_to_stored(jnp.asarray(sc), D), H)), sc)


@pytest.mark.parametrize("f", [1, 2])
@pytest.mark.parametrize("trash_rows", [False, True])
@pytest.mark.parametrize("offset", ["first", "second", "last_but_one",
                                    "last"])
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_paged_kv_append_matches_the_composite_write(
        monkeypatch, kv_dtype, ring, offset, trash_rows, f):
    """The Pallas call (interpreter) against the scatter over the logical
    view: every pool type with its scales, a plain table and a ring,
    offsets at both ends of a block (both parities of a packed lane
    row), free rows whose table names the trash block beside live ones."""
    s = _SHAPES[f]
    H, bs, D = s["H"], s["bs"], s["D"]
    assert pa.pool_packing(D, bs) == f
    B, nblk = 4, 3
    N = B * nblk + 1
    rng = np.random.default_rng(3)
    off = {"first": 0, "second": 1, "last_but_one": bs - 2,
           "last": bs - 1}[offset]
    # rows at different blocks of their tables, all at the offset; a
    # ring's positions lie past its width
    base = np.array([0, 1, 2, 1]) + (nblk * 2 if ring else 0)
    pos = jnp.asarray(base * bs + off, jnp.int32)
    tables = rng.permutation(np.arange(1, N)).reshape(B, nblk)
    if trash_rows:
        tables[[1, 3]] = 0
    tables = jnp.asarray(tables.astype(np.int32))
    kv = jnp.asarray(rng.normal(size=(B, H, 1, D)), jnp.float32)
    logical = jnp.asarray(rng.normal(size=(N, H, bs, D)), jnp.float32)
    scale = None
    if kv_dtype == "int8":
        logical, scale = pa.quantize_kv(logical)
        scale = pa.scales_to_stored(scale, D)
    pool = pa.to_stored(logical.astype(_np_pool_dtype(kv_dtype)))

    before = _dispatch.resolved_counts()
    want = _write(pool, kv, tables, pos, scale, ring)       # cpu: composite
    assert _grew(before, "paged_kv_append", "xla", "backend") == 1
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: "interpret")
    got = _write(pool, kv, tables, pos, scale, ring)
    assert _grew(before, "paged_kv_append", "interpret", "backend") == 1
    # the trash block takes whichever free row wrote last: nobody reads it
    for key in want:
        assert got[key].shape == want[key].shape
        assert got[key].dtype == want[key].dtype
        assert np.array_equal(np.asarray(got[key][1:], np.float32),
                              np.asarray(want[key][1:], np.float32)), key
    assert np.all(np.isfinite(np.asarray(got["Out"][0], np.float32)))
    # and the write landed: the live rows' vectors read back from the
    # logical view where the table says
    out = np.asarray(pa.to_logical(got["Out"], H, D), np.float32)
    col = (base % nblk) if ring else base
    for b in range(B):
        blk = int(tables[b, col[b]])
        if blk == 0:
            continue
        vec = np.asarray(kv[b, :, 0], np.float32)
        if kv_dtype == "int8":
            sc = np.asarray(pa.scales_to_logical(got["OutScale"], H))
            vec_q, vec_s = pa.quantize_kv(kv[b, :, 0])
            assert np.array_equal(out[blk, :, off], np.asarray(vec_q))
            assert np.array_equal(sc[blk, :, off], np.asarray(vec_s))
        else:
            want_vec = np.asarray(jnp.asarray(vec).astype(
                _np_pool_dtype(kv_dtype)), np.float32)
            assert np.array_equal(out[blk, :, off], want_vec)


@pytest.mark.parametrize("f", [1, 2])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_multi_token_write_takes_the_composite_and_says_so(monkeypatch,
                                                           kv_dtype, f):
    """Chunked prefill and the verify span (S > 1, a Limit) stay on the
    scatter even where the kernel would run, counted as ``multi_token``,
    and land where the one-token writes would."""
    s = _SHAPES[f]
    H, bs, D = s["H"], s["bs"], s["D"]
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: "interpret")
    B, nblk, S = 2, 3, 5
    N = B * nblk + 1
    rng = np.random.default_rng(5)
    tables = jnp.asarray(rng.permutation(np.arange(1, N)).reshape(B, nblk),
                         jnp.int32)
    pos = jnp.asarray([bs - 2, 3], jnp.int32)      # row 0 crosses a block
    kv = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    shape = pa.stored_shape(N, H, bs, D)
    pool = jnp.zeros(shape, _np_pool_dtype(kv_dtype))
    scale = jnp.ones((N, f, shape[1]), jnp.float32) \
        if kv_dtype == "int8" else None
    before = _dispatch.resolved_counts()
    limit = jnp.asarray([S, 3], jnp.int32)
    got = _write(pool, kv, tables, pos, scale, limit=limit)
    assert _grew(before, "paged_kv_append", "xla", "multi_token") == 1
    one, one_scale = pool, scale
    for b, n in ((0, S), (1, 3)):
        for i in range(n):
            step = _write(one, kv[b:b + 1, :, i:i + 1], tables[b:b + 1],
                          pos[b:b + 1] + i, one_scale)
            one, one_scale = step["Out"], step.get("OutScale")
    assert np.array_equal(np.asarray(got["Out"][1:], np.float32),
                          np.asarray(one[1:], np.float32))
    if scale is not None:
        assert np.array_equal(np.asarray(got["OutScale"][1:]),
                              np.asarray(one_scale[1:]))


def _filled_pool(kv_dtype, d_head, name, prefix_cache=False):
    pool = KVBlockPool(slots=3, num_layers=2, num_heads=2, d_head=d_head,
                       max_seq_len=64, block_size=16, dtype=kv_dtype,
                       name=name, prefix_cache=prefix_cache)
    return pool


def _rows(rng, n, d_head, length=64):
    return {f"cache_{k}_{i}": jnp.asarray(
        rng.normal(size=(n, 2, length, d_head)), jnp.float32)
        for i in range(2) for k in ("k", "v")}


@pytest.mark.parametrize("d_head", [64, 128])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_scatter_prefill_fills_the_stored_blocks(kv_dtype, d_head):
    """A prefill's dense rows land block by block in the stored arrays:
    the logical view of each table entry is that slice of the row
    (quantized for int8), and the device arrays keep the stored shape."""
    pool = _filled_pool(kv_dtype, d_head, f"st_scatter_{kv_dtype}_{d_head}")
    rng = np.random.default_rng(11)
    rows = _rows(rng, 2, d_head)
    lengths = [40, 16]
    for slot, n in enumerate(lengths):
        pool.alloc(slot, n)
    pool.scatter_prefill([0, 1], rows, 48)
    f = pa.pool_packing(d_head, 16)
    assert pool.arrays()["cache_pk_0"].shape == (
        pool.num_blocks, 2 * 16 // f, f * d_head)
    assert pool.relayouts().keys() == {"scatter"}
    for name in ("cache_pk_0", "cache_pv_1"):
        got = pool.logical(name)
        src = np.asarray(rows[name.replace("_p", "_")])
        for slot, n in enumerate(lengths):
            for blk in range(-(-n // 16)):
                want = src[slot, :, blk * 16:(blk + 1) * 16]
                have = got[pool.tables[slot, blk]]
                if kv_dtype == "int8":
                    q, sc = pa.quantize_kv(jnp.asarray(want))
                    assert np.array_equal(have, np.asarray(q))
                    # the jitted scatter's division differs by an ulp
                    np.testing.assert_allclose(
                        pool.logical(name.replace("_pk_", "_pks_").replace(
                            "_pv_", "_pvs_"))[pool.tables[slot, blk]],
                        np.asarray(sc), rtol=1e-6)
                else:
                    assert np.array_equal(
                        np.asarray(have, np.float32),
                        np.asarray(jnp.asarray(want).astype(
                            _np_pool_dtype(kv_dtype)), np.float32))


@pytest.mark.parametrize("d_head", [64, 128])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_copy_on_write_duplicates_whole_stored_blocks(kv_dtype, d_head):
    """``_copy_blocks`` indexes the block dimension alone: the copies
    are the sources bit for bit, scales included, and nothing else
    moved."""
    pool = _filled_pool(kv_dtype, d_head, f"st_cow_{kv_dtype}_{d_head}")
    rng = np.random.default_rng(13)
    pool.alloc(0, 48)
    pool.scatter_prefill([0], _rows(rng, 1, d_head), 48)
    before = {n: np.asarray(a, np.float32)
              for n, a in pool.arrays().items()}
    src = [int(b) for b in pool.tables[0, :2]]
    dst = [b for b in range(1, pool.num_blocks) if b not in
           set(int(x) for x in pool.tables[0, :3])][:2]
    pool._copy_blocks(src, dst)
    assert pool.relayouts().keys() == {"scatter", "copy_blocks"}
    for name, a in pool.arrays().items():
        a = np.asarray(a, np.float32)
        assert a.shape == before[name].shape
        assert np.array_equal(a[dst], before[name][src]), name
        keep = [b for b in range(pool.num_blocks) if b not in dst]
        assert np.array_equal(a[keep], before[name][keep]), name


@pytest.mark.parametrize("d_head", [64, 128])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_export_import_round_trip_keeps_the_wire_payload(kv_dtype, d_head):
    """The payload stays the logical ``[nblocks, H, block_size, D]``
    (scales ``[nblocks, H, block_size]``) whatever the pool stores: an
    exported slot imported elsewhere exports the same bytes again, and
    reads back from the second pool's stored arrays."""
    rng = np.random.default_rng(17)
    src = _filled_pool(kv_dtype, d_head, f"st_exp_{kv_dtype}_{d_head}")
    dst = _filled_pool(kv_dtype, d_head, f"st_imp_{kv_dtype}_{d_head}")
    src.alloc(1, 40)
    src.scatter_prefill([1], _rows(rng, 1, d_head), 48)
    payload = src.export_slot(1)
    assert payload["nblocks"] == 3 and payload["d_head"] == d_head
    wire_dt = {"fp32": np.float32, "bf16": np.uint16,
               "int8": np.int8}[kv_dtype]
    for i in range(2):
        for kind in "kv":
            assert payload[f"{kind}_{i}"].shape == (3, 2, 16, d_head)
            assert payload[f"{kind}_{i}"].dtype == wire_dt
            if kv_dtype == "int8":
                assert payload[f"{kind}s_{i}"].shape == (3, 2, 16)
    dst.alloc(0, 5)                 # the importer's blocks differ
    assert dst.import_slot(2, payload) == 3
    assert dst.relayouts().keys() == {"import"}
    again = dst.export_slot(2)
    assert again.keys() == payload.keys()
    for key, value in payload.items():
        if isinstance(value, np.ndarray):
            assert np.array_equal(again[key], value), key
        else:
            assert again[key] == value, key
    got = dst.logical("cache_pk_1", dst.tables[2, :3])
    want = payload["k_1"]
    assert np.array_equal(got.view(np.uint16) if kv_dtype == "bf16"
                          else got, want)


def test_relayout_count_reads_pool_sized_copies_alone():
    hlo = """
  %copy.68 = bf16[2049,16,16,64]{3,1,2,0:T(8,128)(2,1)} copy(%pk.1), sharding={replicated}
  %copy.70 = bf16[2049,128,128]{2,1,0:T(8,128)(2,1)} copy(%fusion.2), metadata={op_name="jit(layer)/scatter"}
  %copy.66 = s32[32,64]{1,0:T(8,128)S(1)} copy(%broadcast_bitcast_fusion)
  %copy.9 = f32[2049,2,128]{2,1,0:T(2,128)} copy(%scales)
  %fusion.2 = bf16[2049,128,128]{2,1,0:T(8,128)(2,1)} fusion(%copy.68), kind=kLoop
  %copy-start.1 = (bf16[2049,128,128]{2,1,0}, u32[]) copy-start(%x)
"""
    arrays = {"cache_pk_0": np.zeros((2049, 128, 128), np.int8),
              "token": np.zeros((2049 * 128 * 128,), np.int8)}
    assert pool_element_counts(arrays) == {2049 * 128 * 128}
    # both shapes of the one array count (a copy may relay to either)
    assert count_pool_relayouts(hlo, pool_element_counts(arrays)) == 2
    arrays["cache_pks_0"] = np.zeros((2049, 2, 128), np.float32)
    assert count_pool_relayouts(hlo, pool_element_counts(arrays)) == 3
    assert count_pool_relayouts(hlo, set()) == 0


def test_generator_counts_relayouts_by_program_kind():
    """A paged decode step's fresh compile is scanned once and the count
    stands under its program kind; programs that take no pool array are
    not listed. (The CPU's executable copies as it pleases: the count is
    read, not its value; 0 on the chip is ``chip_smoke.py``'s assert.)"""
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    gen = GPTGenerator(cfg, scope, max_len=32)
    out = gen.generate(np.array([[1, 2, 3]], np.int32), max_new_tokens=3,
                       kv_dtype="bf16")
    assert np.asarray(out).shape[-1] >= 3
    assert list(gen.pool_relayouts) == ["decode_paged_bf16"]
    assert isinstance(gen.pool_relayouts["decode_paged_bf16"], int)
    pool, = gen._paged_pools.values()
    assert pool.stats()["relayouts"].keys() == {"scatter"}


def test_donated_caches_come_back_under_the_names_they_went_in_by():
    """JAX pairs a donated argument with a result by shape and order, and
    a dict of caches flattens in sorted order (``_10`` before ``_2``):
    the generator hands the caches back as a dict too, so array i is
    updated in array i's buffer at any depth."""
    names = [f"cache_p{k}_{i}" for k in "kv" for i in range(12)]
    outs = {"cache_vars": [object()] * 24, "cache_names": names}
    feeds = ["token", "pos", "block_tables"] + names
    fetch = ["logits"] + [f"v{i}" for i in range(24)] + ["aux"]
    place = GPTGenerator._cache_places(outs, feeds, fetch)
    assert place == {n: 1 + i for i, n in enumerate(names)}
    # a prefill hands row caches back and is fed none: nothing to pair
    outs = {"cache_k": [0] * 3, "cache_v": [0] * 3}
    rows = [f"cache_{k}_{i}" for k in "kv" for i in range(3)]
    assert GPTGenerator._cache_places(
        outs, ["tokens", "pos_ids", "last_pos"], ["logits"] + rows) == {}
