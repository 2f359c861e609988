"""Grouped queries and a window in the flash forward and in the paged
decode kernel (Pallas interpreter) against their ``xla`` oracles and
against attention written out position by position: 4 and 8 query heads
a KV head, windows shorter and longer than the context."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.kernels.paged_attention import (blocks_per_step,
                                                paged_attention, row_steps,
                                                window_blocks)


def _dense(q, k, v, pos, window):
    """q [B, Hq, D] at positions ``pos`` over k, v [B, Hkv, L, D], one
    query at a time."""
    B, Hq, D = q.shape
    rep = Hq // k.shape[1]
    out = np.zeros((B, Hq, D), np.float32)
    for b in range(B):
        p = int(pos[b])
        lo = 0 if window is None else max(0, p - window + 1)
        for h in range(Hq):
            s = k[b, h // rep, lo:p + 1] @ q[b, h] / np.sqrt(D)
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ v[b, h // rep, lo:p + 1]
    return out


@pytest.mark.parametrize("rep", [4, 8])
@pytest.mark.parametrize("window,blocks", [
    (None, (None, None)), (24, (None, None)), (24, (32, 32)),
    (500, (32, 32)), (40, (64, 32))])
def test_flash_forward_groups_and_window(rep, window, blocks):
    S, D, Hkv = 128, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(rep), 3)
    q = jax.random.normal(ks[0], (2, Hkv * rep, S, D))
    k = jax.random.normal(ks[1], (2, Hkv, S, D))
    v = jax.random.normal(ks[2], (2, Hkv, S, D))
    got = flash_attention(q, k, v, causal=True, impl="interpret",
                          window=window, block_q=blocks[0],
                          block_k=blocks[1])
    oracle = flash_attention(q, k, v, causal=True, impl="xla",
                             window=window)
    np.testing.assert_allclose(got, oracle, rtol=0, atol=5e-6)
    # the oracle itself, against one query at a time
    qn, kn, vn = (np.asarray(x) for x in (q, k, v))
    for i in (0, 23, 24, 25, 127):
        want = _dense(qn[:, :, i], kn, vn, np.full(2, i), window)
        np.testing.assert_allclose(oracle[:, :, i], want, rtol=0, atol=5e-6)


def test_flash_backward_refuses_what_it_cannot_differentiate():
    q = jnp.ones((1, 4, 16, 8))
    kv = jnp.ones((1, 2, 16, 8))
    for kw, args in (({}, (q, kv, kv)), ({"window": 4}, (q, q, q))):
        with pytest.raises(NotImplementedError, match="backward"):
            jax.grad(lambda q, k, v: flash_attention(
                q, k, v, causal=True, impl="interpret", **kw).sum())(*args)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=4, impl="xla")
    with pytest.raises(ValueError, match="divide"):
        flash_attention(jnp.ones((1, 3, 16, 8)), kv, kv, impl="xla")
    # the composite differentiates both
    g = jax.grad(lambda q: flash_attention(q, kv, kv, causal=True,
                                           impl="xla", window=4).sum())(q)
    assert g.shape == q.shape


@pytest.mark.parametrize("rep", [4, 8])
@pytest.mark.parametrize("window,extra", [(None, 0), (10, 0), (10, 2),
                                          (100, 0), (16, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_groups_and_window(rep, window, extra, dtype):
    """Rows written position by position through a full table or a ring
    (a block is overwritten once it falls behind the window), then read
    at contexts before, at and far past the window."""
    Hkv, D, bs, L = 2, 16, 4, 96
    positions = [0, 3, 9, 10, 11, 40, 63, 95]
    B = len(positions)
    rng = np.random.default_rng(rep)

    def stored(shape):
        a = jnp.asarray(rng.normal(size=shape), jnp.float32)
        return np.asarray(a.astype(dtype).astype(jnp.float32))

    K, V = stored((B, Hkv, L, D)), stored((B, Hkv, L, D))
    q = rng.normal(size=(B, Hkv * rep, 1, D)).astype(np.float32)
    width = L // bs if window is None else window_blocks(window, bs) + extra
    kp = np.zeros((B * width + 1, Hkv, bs, D), np.float32)
    vp = np.zeros_like(kp)
    tables = rng.permutation(np.arange(1, B * width + 1)).reshape(
        B, width).astype(np.int32)
    for b, p in enumerate(positions):
        for t in range(p + 1):
            blk = tables[b, (t // bs) % width]
            kp[blk, :, t % bs], vp[blk, :, t % bs] = K[b, :, t], V[b, :, t]
    pos = np.asarray(positions, np.int32)
    want = _dense(q[:, :, 0], K, V, pos, window)
    for impl in ("xla", "interpret"):
        got = paged_attention(
            jnp.asarray(q), jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
            jnp.asarray(tables), jnp.asarray(pos), impl=impl, window=window)
        np.testing.assert_allclose(np.asarray(got)[:, :, 0], want, rtol=0,
                                   atol=5e-6, err_msg=impl)


def test_paged_ring_must_hold_the_window_and_groups_must_divide():
    q = jnp.ones((1, 4, 1, 8))
    pool = jnp.ones((9, 2, 4, 8))
    tables, pos = jnp.ones((1, 3), jnp.int32), jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="ring"):
        paged_attention(q, pool, pool, tables, pos, impl="xla", window=16)
    with pytest.raises(ValueError, match="divide"):
        paged_attention(jnp.ones((1, 3, 1, 8)), pool, pool, tables, pos,
                        impl="xla")
    assert window_blocks(1024, 16) == 65
    # the walk of a window layer's row follows the window, not the ring
    # or the context: 65 blocks of a ring of 96, from the first in reach
    g = blocks_per_step(4, 16, 128, jnp.bfloat16, 96, window=1024)
    assert g == blocks_per_step(4, 16, 128, jnp.bfloat16, 65) == 32
    assert row_steps(6000, 16, g, 96, window=1024) == (311, 65, 3)
    assert row_steps(100, 16, g, 96, window=1024) == (0, 7, 1)
