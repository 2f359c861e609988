"""The selective state-space recurrence (Mamba-1; Gu and Dao 2023) over a
prefill as a chunked Pallas TPU kernel, with a ``lax.scan`` oracle.

For one row, with ``x`` the mixer's activation after its causal
convolution, ``delta`` the step's projection before its bias, ``z`` the
gate and ``B_t``, ``C_t`` the token's input and output maps (``N`` numbers
each), channel ``c`` keeps ``N`` numbers of state::

    dt_t   = softplus(delta_t + dt_bias)                      # [C]
    S_t    = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t   # [N, C]
    y_t    = S_t . C_t + D * x_t                              # [C]
    out_t  = y_t * silu(z_t)

``A = -exp(a_log)`` is negative, so a step's decay lies in (0, 1]. A
token at or past the row's ``length`` takes ``dt = 0``: its decay is 1 and
its input 0, so the state a call hands back is the state after the row's
last REAL token however far the row was padded (prompts are right-padded
into length buckets); what it writes at such a position is finite and
nobody's to read.

Two implementations, same math, same signature:

- ``pallas``: grid ``(rows, channel blocks, L / chunk)``, the chunks of a
  row in order. The state ``[N, cb]`` is the call's second result, whose
  block does not move along the chunk axis: it stays in VMEM from a row's
  first chunk (where it is loaded from ``state0``) to its last and
  crosses HBM once each way. A step computes the chunk's ``dt`` and
  ``dt * x`` for all its tokens at once, then walks the tokens in order,
  128 lanes of channels at a time with the ``N`` states of a channel on
  the sublanes: a token costs a multiply and an ``exp`` for the decay,
  three more multiply-adds, and one sublane sum for ``y``. ``B_t`` and
  ``C_t`` arrive with their ``N`` numbers already on the sublanes and
  repeated along the lanes (``[L, N, 128]``: the broadcast is XLA's,
  outside, where it is one fused write; inside it would be a transpose
  a token). The skip and the gate are applied to the whole chunk after
  the walk. A chunk wholly past the row's length writes zeros and walks
  nothing. Nothing of ``[L, N, C]`` ever exists. ``interpret`` runs the
  same kernel through the Pallas interpreter on the CPU.
- ``xla``: a ``lax.scan`` over single tokens carrying ``[rows, N, C]``:
  the CPU path and the parity oracle; also what a decode step (one token
  a row over the whole slot bank) runs on any backend, where it is one
  fused elementwise pass over the bank.

Everything is float32: the recurrence multiplies thousands of decays
together and the stored state is read again at every later token.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch

_LANES = 128
_SUBLANES = 8
# tokens a grid step walks: their x, delta, z and y blocks are [chunk, cb]
# float32 each, double-buffered, beside B and C at [chunk, N, 128]
_CHUNK = 64
# channels a block holds at most: at 5120 channels two blocks, so B and
# C are fetched twice a chunk and the blocks of a step come to 7 MB
_CB_MAX = 2560
# lane groups the token walk carries together: 2 vregs of state and 2 of
# A a group, so four groups keep 16 of 64 vregs resident and give the
# scheduler four independent chains a token
_GROUPS = 4
_VMEM_LIMIT = 48 * 1024 * 1024


def channel_block(channels):
    """Channels a block of the kernel holds: all of them up to
    ``_CB_MAX``, else the largest divisor that is whole lane groups."""
    if channels <= _CB_MAX or channels % _LANES:
        return channels
    groups = channels // _LANES
    for parts in range(2, groups + 1):
        if groups % parts == 0 and channels // parts <= _CB_MAX:
            return channels // parts
    return _LANES


def chunk_length(seq):
    """Tokens a grid step walks: ``_CHUNK``, or the whole of a shorter
    sequence rounded up to the sublanes."""
    return min(_CHUNK, -(-int(seq) // _SUBLANES) * _SUBLANES)


def _softplus(x):
    # log(1 + exp(x)) without overflow; exact to float32 either side
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _scan_kernel(len_ref, x_ref, dl_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
                 bias_ref, s0_ref, y_ref, s_ref, dt_scr, dtx_scr, *, chunk,
                 groups):
    row, k = pl.program_id(0), pl.program_id(2)
    cb = x_ref.shape[2]

    @pl.when(k == 0)
    def _first_chunk():
        s_ref[...] = s0_ref[...]

    live = len_ref[row] - k * chunk         # real tokens from here on

    @pl.when(live <= 0)
    def _padding():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live > 0)
    def _walk():
        x = x_ref[0]                                        # [chunk, cb]
        at = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        dt = jnp.where(at < live, _softplus(dl_ref[0] + bias_ref[...]), 0.0)
        dt_scr[...] = dt
        dtx_scr[...] = dt * x
        n_groups = cb // _LANES
        sublane = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
        for g0 in range(0, n_groups, groups):
            lanes = [pl.ds(g * _LANES, _LANES)
                     for g in range(g0, min(g0 + groups, n_groups))]
            a = [a_ref[:, ln] for ln in lanes]              # [N, 128] each

            def eight_tokens(i, states):
                t0 = pl.multiple_of(i * _SUBLANES, _SUBLANES)
                dt8 = [dt_scr[pl.ds(t0, _SUBLANES), ln] for ln in lanes]
                dtx8 = [dtx_scr[pl.ds(t0, _SUBLANES), ln] for ln in lanes]
                states = list(states)
                # a token's y is one row: the eight rows of a tile are put
                # together under a sublane mask and stored whole (Mosaic
                # has no store of one row at a dynamic index)
                y8 = [jnp.zeros((_SUBLANES, _LANES), jnp.float32)] \
                    * len(lanes)
                for u in range(_SUBLANES):
                    b_t, c_t = b_ref[0, t0 + u], c_ref[0, t0 + u]  # [N,128]
                    for j in range(len(lanes)):
                        decay = jnp.exp(dt8[j][u:u + 1, :] * a[j])
                        s = decay * states[j] + dtx8[j][u:u + 1, :] * b_t
                        states[j] = s
                        y8[j] = jnp.where(
                            sublane == u,
                            jnp.sum(s * c_t, axis=0, keepdims=True), y8[j])
                for j, ln in enumerate(lanes):
                    y_ref[0, pl.ds(t0, _SUBLANES), ln] = y8[j]
                return tuple(states)

            final = jax.lax.fori_loop(
                0, chunk // _SUBLANES, eight_tokens,
                tuple(s_ref[0, :, ln] for ln in lanes))
            for ln, s in zip(lanes, final):
                s_ref[0, :, ln] = s
        z = z_ref[0]
        y_ref[0] = (y_ref[0] + d_ref[...] * x) * (z * jax.nn.sigmoid(z))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _pallas_scan(x, delta, z, b, c, a, d_skip, dt_bias, state0, length,
                 chunk, interpret):
    # jitted: a model calls this once a layer at one set of shapes, and
    # the walk's unrolled body is traced and lowered once for them all
    # (26 calls of a prefill program: 13.6 s of tracing and lowering
    # down to 0.6)
    rows, seq, channels = x.shape
    n = a.shape[0]
    cb = channel_block(channels)
    padded = -(-seq // chunk) * chunk
    if padded != seq:
        pad = ((0, 0), (0, padded - seq), (0, 0))
        x, delta, z, b, c = (jnp.pad(t, pad) for t in (x, delta, z, b, c))
    # N on the sublanes, repeated along the lanes (module docstring)
    b4 = jnp.broadcast_to(b[..., None], b.shape + (_LANES,))
    c4 = jnp.broadcast_to(c[..., None], c.shape + (_LANES,))

    def tokens():
        return pl.BlockSpec((1, chunk, cb), lambda r, j, k, ln: (r, k, j))

    def maps():
        return pl.BlockSpec((1, chunk, n, _LANES),
                            lambda r, j, k, ln: (r, k, 0, 0))

    def channel_rows(height):
        return pl.BlockSpec((height, cb), lambda r, j, k, ln: (0, j))

    def state():
        return pl.BlockSpec((1, n, cb), lambda r, j, k, ln: (r, 0, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, channels // cb, padded // chunk),
        in_specs=[tokens(), tokens(), tokens(), maps(), maps(),
                  channel_rows(n), channel_rows(1), channel_rows(1),
                  state()],
        out_specs=[tokens(), state()],
        scratch_shapes=[pltpu.VMEM((chunk, cb), jnp.float32),
                        pltpu.VMEM((chunk, cb), jnp.float32)])
    y, final = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, groups=_GROUPS),
        name="selective_scan_fwd", grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, padded, channels),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((rows, n, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(length, x, delta, z, b4, c4, a, d_skip.reshape(1, channels),
      dt_bias.reshape(1, channels), state0)
    return y[:, :seq], final


def _xla_scan(x, delta, z, b, c, a, d_skip, dt_bias, state0, length):
    """Token by token, the state ``[rows, N, C]`` the carry."""
    seq = x.shape[1]
    dt = _softplus(delta + dt_bias)
    dt = jnp.where(jnp.arange(seq)[None, :, None] < length[:, None, None],
                   dt, 0.0)

    def token(s, at):
        dt_t, x_t, b_t, c_t = at                # [rows, C] x 2, [rows, N] x 2
        s = jnp.exp(dt_t[:, None, :] * a) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    if seq == 1:
        # a decode step: one fused pass over the bank, no loop
        final, y = token(state0, (dt[:, 0], x[:, 0], b[:, 0], c[:, 0]))
        y = y[:, None]
    else:
        final, y = jax.lax.scan(token, state0, tuple(
            jnp.swapaxes(t, 0, 1) for t in (dt, x, b, c)))
        y = jnp.swapaxes(y, 0, 1)
    with jax.named_scope("ssm/gate"):
        return (y + d_skip * x) * (z * jax.nn.sigmoid(z)), final


def selective_scan(x, delta, z, b, c, a_log, d_skip, dt_bias, state0=None,
                   length=None, impl=None):
    """The recurrence of the module docstring over ``[rows, L]`` tokens.

    x, delta, z ``[rows, L, C]``; b, c ``[rows, L, N]``; a_log ``[C, N]``
    (``A = -exp(a_log)``); d_skip, dt_bias ``[C]``; state0 ``[rows, N,
    C]`` (zeros where None); length ``[rows]`` int32, the real tokens of
    each row (all ``L`` where None). Everything float32. Returns ``(out
    [rows, L, C], state [rows, N, C])``, the state after each row's last
    real token. impl: None (auto: pallas on a TPU, xla elsewhere and for
    a single token a row), "pallas", "interpret", "xla"."""
    rows, seq, channels = x.shape
    n = a_log.shape[1]
    f32 = jnp.float32
    x, delta, z, b, c = (t.astype(f32) for t in (x, delta, z, b, c))
    a = -jnp.exp(a_log.astype(f32)).T                       # [N, C]
    d_skip, dt_bias = d_skip.astype(f32), dt_bias.astype(f32)
    if state0 is None:
        state0 = jnp.zeros((rows, n, channels), f32)
    if length is None:
        length = jnp.full((rows,), seq, jnp.int32)
    length = length.astype(jnp.int32)
    reason = "requested" if impl else "backend"
    impl = impl or _dispatch.auto_impl()
    if impl != "xla" and (seq == 1 or channels % _LANES or n % _SUBLANES):
        impl, reason = "xla", "single_token" if seq == 1 else "shape"
    with _dispatch.resolved("selective_scan", impl, reason):
        if impl == "xla":
            return _xla_scan(x, delta, z, b, c, a, d_skip, dt_bias,
                             state0.astype(f32), length)
        return _pallas_scan(x, delta, z, b, c, a, d_skip, dt_bias,
                            state0.astype(f32), length,
                            chunk=chunk_length(seq),
                            interpret=impl == "interpret")
