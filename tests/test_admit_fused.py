"""An admission is one executable (``GenerationEngine.admit`` over
``GPTGenerator``'s ``<prefill kind>+<pick kind>``): the prefill, the pick of
the first tokens and the pool's scatter of the keys, values and states in
one donated call. Against the three calls it replaced (``_run_prefill``,
``_run_sample``, ``KVBlockPool.scatter_prefill``) bit for bit over every
kind of pool, the padding rows' writes, the executables a length bucket
compiles, a failure inside the call, and what the call's span and counters
carry. CPU, tiny configurations; no timing."""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import serving
from paddle_tpu.models.generation import GPTGenerator, length_bucket
from paddle_tpu.observability import tracing
from paddle_tpu.serving.batching import GenerationRequest
from paddle_tpu.serving.metrics import ServingStats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, BLOCK = 4, 4

# pool -> (family and configuration, None for the tiny GPT; pool dtype;
# prompt lengths; max_len). The window case's prompts pass its 8-token
# window, so a row keeps the last of its blocks in a ring of three
POOLS = {
    "gpt": (None, "fp32", (5, 11, 14), 32),
    "gpt-int8": (None, "int8", (5, 11, 14), 32),
    "window": (("mellum", "mellum2-12b-a2.5b"), "fp32", (21, 9, 30), 64),
    "passes": (("ouro", "ouro-2.6b"), "fp32", (21, 9, 30), 64),
    "state": (("jamba", "jamba2-3b"), "fp32", (21, 9, 30), 64),
}
PICKS = {"greedy": (0.0, 0), "top_k": (0.9, 5)}
_GENS = {}


def _generator(pool, fresh=False):
    """The pool case's generator: the tiny GPT from its startup program,
    or a family's rehearsal size with its seeded float32 weights; one a
    case, but for a ``fresh`` one, whose executables are its own."""
    name, _, _, max_len = POOLS[pool]
    if (name, max_len) in _GENS and not fresh:
        return _GENS[name, max_len]
    if name is None:
        from paddle_tpu.models import gpt as gpt_mod
        cfg = gpt_mod.GPTConfig.tiny()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            gpt_mod.gpt_logits(cfg)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor().run(startup)
        gen = GPTGenerator(cfg, scope, max_len=max_len, bucket_min=8)
    else:
        import importlib
        fam = importlib.import_module(f"benchmark.families.{name[0]}")
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{name[1]}.json")) as fh:
            sz = fam.Sizes(json.load(fh), rehearsal=True)
        cfg = fam.program_config(sz)
        cfg.dtype = "float32"
        gen = GPTGenerator(cfg, fluid.Scope(), max_len=max_len)
        gen.bind_params({n: jnp.asarray(a, jnp.float32)
                         for n, a in fam.init_params(sz, 41).items()})
    if not fresh:
        _GENS[name, max_len] = gen
    return gen


def _engine(pool, stats=None, slots=SLOTS, fresh=False):
    return serving.GenerationEngine(
        _generator(pool, fresh), slots=slots, seed=7, kv_block_size=BLOCK,
        kv_dtype=POOLS[pool][1], stats=stats)


def _requests(pool, pick="greedy", lens=None, seed=0):
    gen = _generator(pool)
    temperature, top_k = PICKS[pick]
    rng = np.random.default_rng(seed)
    return [GenerationRequest(
        rng.integers(1, gen.cfg.vocab_size, n).astype(np.int32),
        max_new_tokens=2, temperature=temperature, top_k=top_k)
        for n in (lens or POOLS[pool][2])]


def _trash_rows(pool, name):
    """Rows of pool array ``name`` that are a trash block: block 0 of
    each pass, or of the window group's own ids."""
    if name.startswith("cache_s"):
        return set()
    window = pool.window is not None and int(
        name.rsplit("_", 1)[1]) in pool.window.layers
    passes = 1 if window else pool.passes
    return {u * pool.num_blocks for u in range(passes)}


def _host(pool):
    return {n: np.asarray(a) for n, a in pool.arrays().items()}


@pytest.mark.parametrize("pick", sorted(PICKS))
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_one_call_is_the_split_sequence_bit_for_bit(pool, pick):
    """Three rows (a bucket of four) into slots 2, 0 and 3: the same
    first tokens, the same key after, and the same pool but for the trash
    blocks, which only the one call's padding row writes."""
    gen = _generator(pool)
    reqs, slots = _requests(pool, pick), [2, 0, 3]
    eng = _engine(pool)
    first = eng.admit(reqs, slots)

    split = gen.new_pool(SLOTS, block_size=BLOCK, dtype=POOLS[pool][1])
    for req, slot in zip(reqs, slots):
        split.alloc(slot, req.prompt.size)
    tokens, pos_ids, last = gen._pack_prompts([r.prompt for r in reqs])
    bb = tokens.shape[0]
    temp = np.zeros(bb, np.float32)
    topk = np.zeros(bb, np.int32)
    temp[:3], topk[:3] = PICKS[pick]
    logits, caches, key = gen._run_prefill(
        tokens, pos_ids, last, jax.random.PRNGKey(7), kv_dtype=split.dtype)
    toks, key = gen._run_sample(logits, temp, topk, key)
    split.scatter_prefill(slots, caches, tokens.shape[1],
                          lengths=[r.prompt.size for r in reqs])

    assert first.dtype == np.int32 and first.shape == (3,)
    np.testing.assert_array_equal(first, np.asarray(toks)[:3])
    np.testing.assert_array_equal(np.asarray(eng._key), np.asarray(key))
    np.testing.assert_array_equal(eng.pool.tables, split.tables)
    fused, want = _host(eng.pool), _host(split)
    assert set(fused) == set(want)
    for name in want:
        keep = sorted(set(range(want[name].shape[0]))
                      - _trash_rows(split, name))
        np.testing.assert_array_equal(fused[name][keep], want[name][keep],
                                      err_msg=name)


@pytest.mark.parametrize("pool", ["gpt", "window", "passes", "state"])
def test_padding_rows_write_the_trash_block_and_touch_no_other_slot(pool):
    """Over a pool full of other data, with slot 1 holding blocks of its
    own, three rows admitted in a bucket of four change only their own
    slots' blocks and states and the trash blocks."""
    eng = _engine(pool)
    held = eng.pool
    held.alloc(1, 13)
    rng = np.random.default_rng(3)
    before = {n: rng.standard_normal(a.shape).astype(a.dtype)
              for n, a in _host(held).items()}
    held.update_arrays({n: jnp.asarray(a) for n, a in before.items()})
    slots = [3, 0, 2]
    eng.admit(_requests(pool), slots)
    after = _host(held)
    mine = {int(b) for s in slots for b in held.tables[s] if b}
    ring = {int(b) for s in slots for b in held.window.tables[s] if b} \
        if held.window else set()
    assert mine and held.tables[1].any()
    for name, old in before.items():
        if name.startswith("cache_s"):
            changed = set(slots)
        elif held.window is not None and int(
                name.rsplit("_", 1)[1]) in held.window.layers:
            changed = ring
        else:
            changed = {b + u * held.num_blocks for b in mine
                       for u in range(held.passes)}
        same = sorted(set(range(old.shape[0])) - changed
                      - _trash_rows(held, name))
        np.testing.assert_array_equal(after[name][same], old[same],
                                      err_msg=name)
        assert not np.array_equal(after[name][sorted(changed)],
                                  old[sorted(changed)]), name


def test_a_length_bucket_compiles_once_a_row_bucket():
    """1, 3 and 4 rows of one length bucket are two row buckets (1, 4)
    and two executables; 5 to 8 rows one more; nothing again, and the
    pool's own scatter is never built."""
    stats = ServingStats()
    eng = _engine("gpt", stats=stats, slots=8, fresh=True)

    def admit(rows):
        eng.admit(_requests("gpt", lens=[9 + r for r in range(rows)]),
                  list(range(rows)))
        for slot in range(rows):
            eng.release_slot(slot)
        return stats.snapshot()["compiles"]

    assert [admit(r) for r in (1, 3, 4)] == [1, 2, 2]
    assert [admit(r) for r in (5, 6, 7, 8)] == [3, 3, 3, 3]
    assert [admit(r) for r in (8, 4, 1, 6)] == [3, 3, 3, 3]
    assert eng.pool._scatter_fn is None
    assert stats.snapshot()["admissions"] == 11


def test_the_benchmarks_warm_up_leaves_nothing_to_compile():
    """After the closed-loop driver's warm-up any admission of its
    traffic (1 to ``warm_rows_max`` rows, any prompt lengths it draws)
    compiles nothing: ``generator_recompiles`` stays 0."""
    from benchmark.drivers.closed_loop_serve import warm
    stats = ServingStats()
    eng = _engine("gpt", stats=stats, fresh=True)
    traffic = {"prompt_min": 3, "prompt_max": 20, "warm_rows_max": SLOTS,
               "new_min": 2}
    warm(types.SimpleNamespace(gen_engine=eng), traffic)
    warmed = stats.snapshot()["compiles"]
    # one executable a (row bucket, length bucket) and the decode step
    assert warmed == 3 * 3 + 1
    rng = np.random.default_rng(5)
    for _ in range(12):
        rows = int(rng.integers(1, SLOTS + 1))
        lens = rng.integers(3, 21, rows).tolist()
        eng.admit(_requests("gpt", lens=lens), list(range(rows)))
        for slot in range(rows):
            eng.release_slot(slot)
    assert stats.snapshot()["compiles"] == warmed


@pytest.mark.parametrize("where", ["slot_insert", "wait"])
def test_a_failure_in_the_call_frees_the_slots_and_loses_the_bank(
        where, fault_points, monkeypatch):
    eng = _engine("gpt")
    eng.admit(_requests("gpt", lens=[6]), [1])
    assert eng.pool.blocks_in_use() == 2
    if where == "wait":
        def fail(sent, *a, **k):
            raise RuntimeError("the device lost the call")
        monkeypatch.setattr(eng.gen, "_await", fail)
        with pytest.raises(RuntimeError, match="lost the call"):
            eng.admit(_requests("gpt"), [0, 2, 3])
    else:
        with fault_points.fault_injection("serving.slot_insert",
                                          exc=RuntimeError("injected")):
            with pytest.raises(RuntimeError, match="injected"):
                eng.admit(_requests("gpt"), [0, 2, 3])
    assert eng.bank_lost and eng.pool._arrays is None
    # the batch's blocks went back; slot 1's are the batcher's to fail
    assert eng.pool.blocks_in_use() == 2
    assert not eng.pool.tables[[0, 2, 3]].any()
    monkeypatch.undo()
    eng.release_slot(1)
    assert eng.admit(_requests("gpt", lens=[6]), [1]).shape == (1,)
    assert not eng.bank_lost


READ_ATTRS = {"rows", "fused", "cache_bytes", "ut_steps", "cache_layers"}
POOL_ATTRS = {"gpt": set(), "window": {"moe_tokens", "moe_experts_hit",
                                       "moe_load_max"},
              "passes": {"exit_pass_mean"},
              "state": {"state_layers", "prompt_tokens", "scan_tokens"}}


@pytest.mark.parametrize("pool", sorted(POOL_ATTRS))
def test_every_admission_is_counted_fused_and_keeps_the_readers_attrs(pool):
    """``admissions_fused`` equals ``admissions``, and each admission's
    ``generator/prefill`` carries what the benchmark's readers take, with
    ``cache_bytes`` the bytes its call wrote into the pool."""
    import time
    stats = ServingStats()
    eng = _engine(pool, stats=stats)
    t0 = time.perf_counter()
    eng.admit(_requests(pool), [0, 1, 2])
    eng.admit(_requests(pool, lens=[POOLS[pool][2][0]], seed=1), [3])
    rows = tracing.loop_spans(t0, time.perf_counter())
    prefills = [r for r in rows if r[0] == "generator/prefill"]
    assert not [r for r in rows if r[0] in ("generator/sample",
                                            "pool/scatter")]
    assert len(prefills) == 2
    snap = stats.snapshot()
    assert snap["admissions"] == snap["admissions_fused"] == 2
    seq = length_bucket(max(POOLS[pool][2]), eng.gen.bucket_min)
    prefills.sort(key=lambda r: r[1])
    for r, n in zip(prefills, (3, 1)):
        attrs = r[7]
        assert set(attrs) >= READ_ATTRS | POOL_ATTRS[pool], attrs
        assert attrs["rows"] == n and attrs["fused"] == 1
        if pool != "state":
            continue
        assert attrs["scan_tokens"] >= attrs["prompt_tokens"]
    assert prefills[0][7]["cache_bytes"] == eng.pool.scatter_bytes(4, seq) > 0
