"""What GPT's prefill program lowers to and what it is counted as: the
keys and values it hands the pool's scatter are the bucket's length in
the pool's dtype, so the text holds no ``[B, H, max_len, D]`` array, no
per-row update loop and no scatter, and ``GPTServing.prefill_bytes`` is
what the program returns, twice over, and the logits."""
import re

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import gpt
from paddle_tpu.models.generation import GPTGenerator

MAX_LEN = 48
ELEM_BYTES = {"bf16": 2, "fp32": 4, "int8": 1}


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    return cfg, GPTGenerator(cfg, scope, max_len=MAX_LEN, bucket_min=8)


def _feed(rows, seq):
    return {"tokens": np.ones((rows, seq), np.int32),
            "pos_ids": np.broadcast_to(np.arange(seq, dtype=np.int32),
                                       (rows, seq)).copy(),
            "last_pos": np.full((rows,), seq - 1, np.int32)}


@pytest.mark.parametrize("kind,row_dtype", [("prefill_bf16", "bf16"),
                                            ("prefill_fp32", "f32")])
@pytest.mark.parametrize("text_of", ["lowered", "optimised"])
def test_prefill_text_holds_no_cache_sized_array_and_no_update_loop(
        tiny, kind, row_dtype, text_of):
    cfg, gen = tiny
    rows, seq = 2, 16
    heads, d_head = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    jitted, state = gen._ensure_fn(kind)
    lowered = jitted.lower(state, {}, _feed(rows, seq),
                           jax.random.PRNGKey(0))
    text = lowered.as_text() if text_of == "lowered" \
        else lowered.compile().as_text()
    for op in ("scatter", "dynamic_update_slice", "dynamic-update-slice"):
        assert not re.search(rf"\b{op}\b", text), op
    # the one loop left splits the RNG key: unsigned words, no values
    loops = [line for line in text.splitlines()
             if re.search(r"stablehlo\.while|\bwhile\(", line)]
    assert len(loops) <= 1
    assert not any(re.search(r"f32|bf16", line.split("metadata=")[0])
                   for line in loops), loops
    # no array the cache's length, in either text's spelling
    dims = (r"\d+", str(heads), str(MAX_LEN), str(d_head))
    assert not re.search(r"f32\[" + ",".join(dims) + r"\]", text)
    assert not re.search("tensor<" + "x".join(dims) + "xf32>", text)
    # the rows it returns: the bucket's length, the pool's dtype
    shape = (rows, heads, seq, d_head)
    assert (f"{row_dtype}[{','.join(map(str, shape))}]" in text
            or f"tensor<{'x'.join(map(str, shape))}x{row_dtype}>" in text)


@pytest.mark.parametrize("kv", ["bf16", "fp32", "int8"])
def test_prefill_bytes_is_twice_what_the_program_returns_and_the_logits(
        tiny, kv):
    """At two buckets: the count ``prefill_fit`` and a round's cap read
    is the program's cache fetches once as its result and once more in
    the scatter, plus the logits; nothing of it follows ``max_len``."""
    cfg, gen = tiny
    seen = []
    for rows, seq in ((2, 16), (4, 32)):
        feed = _feed(rows, seq)
        logits, caches, _ = gen._run_prefill(
            feed["tokens"], feed["pos_ids"], feed["last_pos"],
            jax.random.PRNGKey(0), kv_dtype=kv)
        assert len(caches) == 2 * cfg.num_layers
        want = 2 * sum(a.nbytes for a in caches.values()) + logits.nbytes
        for max_len in (MAX_LEN, 4 * MAX_LEN):
            assert gen.arch.prefill_bytes(rows, seq, max_len,
                                          ELEM_BYTES[kv]) == want
        seen.append(want)
    assert seen[1] > 3 * seen[0]        # rows x bucket, not rows alone
