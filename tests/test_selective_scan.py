"""The selective scan (``kernels/selective_scan.py``): the Pallas kernel
through the interpreter and the XLA path against a token-by-token numpy
oracle in float64, over lengths, chunk sizes, initial states and per-row
lengths; the op's decode step against a prefill; the convolution's tail."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import selective_scan as ss
from paddle_tpu.kernels.selective_scan import selective_scan

# float32 against float64 over up to 130 dependent steps of numbers of
# magnitude 1 to 30: 3e-6 measured; a state taken one token late or at
# the bucket's end differs by 1e-2 and more
TOL = 2e-5


def oracle(x, delta, z, b, c, a_log, d, bias, s0, length):
    rows, seq, ch = x.shape
    n = a_log.shape[1]
    a = -np.exp(a_log.astype(np.float64)).T
    out = np.zeros((rows, seq, ch))
    fin = np.zeros((rows, n, ch))
    for r in range(rows):
        s = s0[r].astype(np.float64).copy()
        for t in range(min(seq, int(length[r]))):
            dt = np.logaddexp(0, delta[r, t].astype(np.float64) + bias)
            s = np.exp(dt[None, :] * a) * s \
                + (dt * x[r, t])[None, :] * b[r, t][:, None]
            y = (s * c[r, t][:, None]).sum(0) + d * x[r, t]
            out[r, t] = y * z[r, t] / (1 + np.exp(-z[r, t].astype(
                np.float64)))
        fin[r] = s
    return out, fin


def inputs(rows, seq, ch, n, seed=0, zero_state=False):
    rng = np.random.default_rng(seed)
    x, delta, z = (rng.normal(size=(rows, seq, ch)).astype(np.float32)
                   for _ in range(3))
    b, c = (rng.normal(size=(rows, seq, n)).astype(np.float32)
            for _ in range(2))
    a_log = np.log(np.tile(np.arange(1, n + 1, dtype=np.float32), (ch, 1)))
    d = rng.normal(size=ch).astype(np.float32)
    bias = rng.normal(size=ch).astype(np.float32) - 2
    s0 = np.zeros((rows, n, ch), np.float32) if zero_state \
        else rng.normal(size=(rows, n, ch)).astype(np.float32)
    return x, delta, z, b, c, a_log, d, bias, s0


CASES = [
    # (rows, seq, channels, states, per-row lengths, chunk)
    (2, 24, 256, 16, [24, 7], 8),       # a length inside the first chunk
    (3, 130, 128, 8, [130, 64, 65], 64),  # at, one under, one over an edge
    (1, 5, 384, 16, [2], 64),           # shorter than a sublane tile
    (2, 48, 128, 16, [48, 1], 16),      # a row of one token
    (1, 64, 640, 16, [63], 32),         # five lane groups: 4 + 1
]


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("rows,seq,ch,n,lens,chunk", CASES)
def test_both_paths_match_the_token_by_token_oracle(
        impl, rows, seq, ch, n, lens, chunk, monkeypatch):
    monkeypatch.setattr(ss, "_CHUNK", chunk)
    args = inputs(rows, seq, ch, n, seed=seq)
    length = np.asarray(lens, np.int32)
    ref_out, ref_state = oracle(*args, length)
    out, state = selective_scan(*(jnp.asarray(a) for a in args),
                                jnp.asarray(length), impl=impl)
    real = (np.arange(seq)[None, :] < length[:, None])[:, :, None]
    assert np.abs(np.asarray(out) * real - ref_out).max() <= TOL
    assert np.abs(np.asarray(state) - ref_state).max() <= TOL
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_state_is_the_one_after_the_last_real_token_not_the_buckets_end(
        impl):
    """A prompt of n tokens in a bucket of 32: the state handed back is
    the unpadded prompt's, and differs from the bucket's end by far more
    than the tolerance."""
    args = inputs(1, 32, 128, 16, seed=9)
    for n in (1, 2, 15, 16, 17, 31):
        short = tuple(a[:, :n] if a.ndim == 3 and a.shape[1] == 32 else a
                      for a in args)
        _, want = selective_scan(*(jnp.asarray(a) for a in short),
                                 impl="xla")
        _, got = selective_scan(*(jnp.asarray(a) for a in args),
                                jnp.asarray([n], jnp.int32), impl=impl)
        _, at_end = selective_scan(*(jnp.asarray(a) for a in args),
                                   impl=impl)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() <= TOL
        assert np.abs(np.asarray(at_end) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_a_prefill_equals_single_token_steps_from_the_carried_state(impl):
    """Two calls over the halves of a sequence, the second from the
    first's state, and n calls of one token each: the same outputs and
    the same final state as one call over the whole."""
    args = inputs(2, 24, 128, 16, seed=4, zero_state=True)
    x, delta, z, b, c, a_log, d, bias, s0 = (jnp.asarray(a) for a in args)
    whole, final = selective_scan(x, delta, z, b, c, a_log, d, bias, s0,
                                  impl=impl)

    def part(lo, hi, state, how):
        return selective_scan(x[:, lo:hi], delta[:, lo:hi], z[:, lo:hi],
                              b[:, lo:hi], c[:, lo:hi], a_log, d, bias,
                              state, impl=how)

    first, mid = part(0, 10, s0, impl)
    second, end = part(10, 24, mid, impl)
    halves = np.concatenate([np.asarray(first), np.asarray(second)], axis=1)
    assert np.abs(halves - np.asarray(whole)).max() <= TOL
    assert np.abs(np.asarray(end) - np.asarray(final)).max() <= TOL
    state, outs = s0, []
    for t in range(24):
        y, state = part(t, t + 1, state, None)   # one token: the XLA step
        outs.append(np.asarray(y))
    assert np.abs(np.concatenate(outs, axis=1)
                  - np.asarray(whole)).max() <= TOL
    assert np.abs(np.asarray(state) - np.asarray(final)).max() <= TOL


def test_shapes_the_kernel_does_not_take_fall_to_the_xla_path():
    from paddle_tpu.kernels import _dispatch
    before = _dispatch.resolved_counts()
    args = inputs(1, 8, 96, 4, seed=1)      # 96 channels, 4 states
    out, _ = selective_scan(*(jnp.asarray(a) for a in args),
                            impl="interpret")
    assert out.shape == (1, 8, 96)
    after = _dispatch.resolved_counts()
    key = ("selective_scan", "xla", "shape")
    assert after.get(key, 0) == before.get(key, 0) + 1
    assert ss.channel_block(5120) == 2560 and ss.channel_block(128) == 128
    assert ss.channel_block(2560) == 2560 and ss.channel_block(96) == 96
    assert ss.chunk_length(2048) == 64 and ss.chunk_length(5) == 8


# --------------------------------------------- the convolution in front of it

def _conv_op(x, w, bias, tail=None, length=None):
    from paddle_tpu.ops.ssm_ops import causal_conv1d
    ins = {"X": [jnp.asarray(x)], "W": [jnp.asarray(w)],
           "Bias": [jnp.asarray(bias)]}
    if tail is not None:
        ins["Tail"] = [jnp.asarray(tail)]
    if length is not None:
        ins["Length"] = [jnp.asarray(length, jnp.int32)]
    out = causal_conv1d(None, ins, {})
    return np.asarray(out["Out"]), np.asarray(out["NewTail"])


def test_convolution_is_causal_and_its_tail_stops_at_the_last_real_token():
    rng = np.random.default_rng(2)
    seq, ch, taps = 12, 128, 4
    x = rng.normal(size=(3, seq, ch)).astype(np.float32)
    w = rng.normal(size=(taps, ch)).astype(np.float32)
    bias = rng.normal(size=ch).astype(np.float32)
    lens = np.array([12, 5, 2], np.int32)       # 2 < taps - 1
    out, tail = _conv_op(x, w, bias, length=lens)
    padded = np.concatenate([np.zeros((3, taps - 1, ch), np.float32), x], 1)
    want = bias + sum(padded[:, j:j + seq] * w[j] for j in range(taps))
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(out, want, atol=1e-5)
    for r, n in enumerate(lens):
        # the inputs at n - 3 .. n - 1, zeros before the row's first
        np.testing.assert_array_equal(
            tail[r].reshape(taps - 1, ch), padded[r, n:n + taps - 1])
    # a decode step from that tail is the next position of a longer row
    nxt = rng.normal(size=(3, 1, ch)).astype(np.float32)
    step_out, step_tail = _conv_op(nxt, w, bias, tail=tail)
    for r, n in enumerate(lens):
        longer = np.concatenate([x[r, :n], nxt[r]])[None]
        ref_out, ref_tail = _conv_op(longer, w, bias)
        np.testing.assert_allclose(step_out[r, 0], ref_out[0, n], atol=1e-5)
        np.testing.assert_array_equal(step_tail[r], ref_tail[0])
