"""The three kernels at mellum2-12b-a2.5b's shapes lower for the TPU
platform on the CPU (``tests/test_tpu_lowering.py``'s check, for grouped
queries, the window and the routed experts), each under its own kernel
name, and the round's admission cap."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.kernels.moe_experts import routed_experts
from paddle_tpu.kernels.paged_attention import paged_attention

BF = jnp.bfloat16
S = jax.ShapeDtypeStruct


def _names(fn, specs):
    text = jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()
    return re.findall(r'kernel_name = "([^"]+)"', text)


@pytest.mark.parametrize("seq", [1024, 8192])
@pytest.mark.parametrize("window", [None, 1024])
def test_grouped_window_flash_forward_lowers(seq, window):
    names = _names(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="pallas", window=window),
        [S((1, 32, seq, 128), BF), S((1, 4, seq, 128), BF),
         S((1, 4, seq, 128), BF)])
    assert names == ["flash_attention_fwd"]


@pytest.mark.parametrize("window,width", [(None, 512), (1024, 65)])
def test_grouped_window_paged_decode_lowers(window, width):
    n = 32 * width + 1
    names = _names(lambda *a: paged_attention(*a, impl="pallas",
                                              window=window),
                   [S((32, 32, 1, 128), jnp.float32), S((n, 4, 16, 128), BF),
                    S((n, 4, 16, 128), BF), S((32, width), jnp.int32),
                    S((32,), jnp.int32)])
    assert names == ["paged_attention_decode"]


@pytest.mark.parametrize("tokens", [32, 4096])
def test_expert_kernel_lowers_at_decode_and_prefill_rows(tokens):
    names = _names(lambda x, i, w, g, u, d: routed_experts(
        x, i, w, g, u, d, impl="pallas"),
        [S((tokens, 2304), jnp.float32), S((tokens, 8), jnp.int32),
         S((tokens, 8), jnp.float32), S((64, 2304, 896), BF),
         S((64, 2304, 896), BF), S((64, 896, 2304), BF)])
    assert names == ["moe_experts_swiglu"]


def test_scopes_reach_the_hlo_metadata():
    """``moe/router``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``
    and the attention scopes are in the lowered module's locations, so a
    device trace's viewer tells them apart."""
    import paddle_tpu as fluid
    from paddle_tpu.framework.lowering import (analyze_block_io,
                                               build_block_fn)
    from paddle_tpu.models import mellum
    cfg = mellum.MellumConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        outs = mellum.mellum_logits(cfg)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    feeds = outs["feed_names"]
    state_in, _ = analyze_block_io(main, 0, feeds)
    fn = build_block_fn(main, 0, feeds, [outs["logits"].name], state_in, [])
    state = {n: jnp.asarray(np.asarray(scope.find_var(n)))
             for n in state_in}
    feed = {"tokens": jnp.ones((1, 16), jnp.int32),
            "pos_ids": jnp.arange(16, dtype=jnp.int32)[None],
            "last_pos": jnp.array([15], jnp.int32)}
    text = jax.jit(lambda s, f, k: fn({}, s, f, k)).lower(
        state, feed, jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope_name in ("moe/router", "moe/dispatch", "moe/experts",
                       "moe/combine", "attn/window", "attn/full"):
        assert scope_name in text, scope_name


def test_a_round_admits_only_what_its_prefill_fits(monkeypatch):
    """``DecodeBatcher._admit_inner`` stops taking requests where the
    round's prefill, at its padded shape, would pass the device's free
    bytes; the request that did not fit leads the next round; a request
    alone is admitted whatever its size; with no count (the CPU) nothing
    is capped. ``GenerationEngine.admit`` splits by the same count."""
    import paddle_tpu as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import gpt
    from paddle_tpu.models.generation import GPTGenerator
    from paddle_tpu.serving import engine as engine_mod
    from paddle_tpu.serving.batching import (DecodeBatcher,
                                             GenerationRequest,
                                             RequestQueue)
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    gen = GPTGenerator(cfg, scope, max_len=48)
    engine = serving.GenerationEngine(gen, slots=8)
    one = engine.prefill_bytes([5])
    # GPT-2's count: the bucket-long keys and values in the pool's dtype
    # twice over and the logits
    assert (engine.pool.dtype, gen.bucket_min) == ("fp32", 16)
    assert one == 2 * 2 * cfg.num_layers * cfg.hidden_size * 16 * 4 \
        + cfg.vocab_size * 4
    assert engine.prefill_bytes([5, 5, 5]) == 4 * one     # row bucket 4

    def put(batcher, n):
        reqs = [GenerationRequest(np.arange(1, 6, dtype=np.int32),
                                  max_new_tokens=3) for _ in range(n)]
        for r in reqs:
            batcher.queue.put(r)
        return reqs

    batcher = DecodeBatcher(RequestQueue(max_depth=16), engine)
    assert engine_mod._free_device_bytes() is None         # the CPU
    put(batcher, 5)
    assert batcher._admit() == 5                           # no cap
    for req in list(batcher._active.values()):
        batcher._finish(req)

    monkeypatch.setattr(engine_mod, "_free_device_bytes",
                        lambda: 2 * one + 1)               # two rows fit
    assert engine.prefill_fit([5] * 5) == 2 and engine.prefill_fit([5]) == 1
    reqs = put(batcher, 5)
    # what does not fit the round stays in the queue, where drain,
    # close and the deadline sweep see it, and leads the next round
    assert batcher._admit() == 2 and len(batcher.queue) == 3
    assert batcher._admit() == 2 and len(batcher.queue) == 1
    assert batcher._admit() == 1 and len(batcher.queue) == 0
    assert all(r.slot is not None for r in reqs)
    monkeypatch.setattr(engine_mod, "_free_device_bytes", lambda: 1)
    for req in list(batcher._active.values()):
        batcher._finish(req)
    alone = put(batcher, 2)
    assert batcher._admit() == 1                 # alone: whatever its size
    assert len(batcher.queue) + batcher.inflight() == 2    # drain waits
    batcher.queue.close()
    with pytest.raises(serving.ServerShutdownError):
        alone[1].wait(timeout=0.1)               # told with the queue
    batcher.stop()
    # the engine splits a direct admission by the same count
    monkeypatch.setattr(engine_mod, "_free_device_bytes",
                        lambda: 2 * one + 1)
    calls = []
    real = gen._dispatch
    monkeypatch.setattr(gen, "_dispatch", lambda kind, stage, feed, *a, **k: (
        calls.append(feed["tokens"].shape[0]) if stage == "prefill" else None,
        real(kind, stage, feed, *a, **k))[1])
    for slot in range(8):
        engine.release_slot(slot)
    fresh = DecodeBatcher(RequestQueue(max_depth=16), engine)
    toks = engine.admit(put(fresh, 4), [0, 1, 2, 3])
    assert calls == [2, 2] and toks.shape == (4,)
