"""Routed experts without capacity: a grouped SwiGLU as one Pallas TPU
kernel, with a ``jax.lax.ragged_dot`` oracle.

A routed layer sends each token to ``k`` of ``E`` experts. Nothing is
dropped: the ``N * k`` assignments are sorted by expert, each expert's
rows are one group, and the work is ``N * k`` rows through one expert
each, whatever ``E`` is. Two implementations, same math:

- ``pallas``: the sorted rows are laid out with every group padded to a
  whole number of ``tm``-row tiles, so that a tile belongs to one expert.
  Grid ``(tiles,)``; a step takes its tile's rows ``[tm, d]`` and its
  expert's three matrices whole (scalar-prefetched ``tile_expert`` is the
  index map of the weights) and computes ``(silu(x Wg) * (x Wu)) Wd`` in
  VMEM. Consecutive tiles of one expert name the same weight blocks, so
  an expert's 3 x d x f weights cross HBM once a call, however many rows
  it got; tiles past the last live one repeat its expert and fold
  nothing. At decode (a few rows an expert) the call is bound by the
  weights of the experts that got a token, at prefill (hundreds of rows
  an expert) by the products: ``tm`` follows the rows an expert gets on
  average (:func:`rows_per_tile`). ``interpret`` runs the same kernel
  through the Pallas interpreter on the CPU.
- ``xla``: three ``jax.lax.ragged_dot`` over the sorted rows, unpadded —
  the CPU path and the parity oracle.

Products take bfloat16 (or whatever the weights are held in) operands
and accumulate in float32; the hidden ``silu(g) * u`` is rounded to the
weights' dtype before the third product, as the first two round ``x``.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _dispatch

# v5e has 128 MiB of VMEM a core; Mosaic's default scoped limit (16 MiB)
# is below one expert's three matrices double-buffered (25 MB at
# d = 2304, f = 896 in bfloat16)
_VMEM_LIMIT = 96 * 1024 * 1024
_TM_MIN, _TM_MAX = 16, 256


def rows_per_tile(assignments, num_experts):
    """``tm``: the power of two nearest above the rows an expert gets on
    average, between a bfloat16 tile's 16 sublanes and 256 (an MXU pass
    and a third of a megabyte of rows)."""
    mean = max(1, -(-int(assignments) // int(num_experts)))
    tm = _TM_MIN
    while tm < mean and tm < _TM_MAX:
        tm *= 2
    return tm


def padded_rows(assignments, num_experts, tm):
    """Rows of the padded layout: every group rounds up to whole tiles,
    so at most ``E * (tm - 1)`` rows of padding."""
    return ((int(assignments) + int(num_experts) * (tm - 1)) // tm) * tm


def group_layout(expert_of, num_experts, tm, rows):
    """Where each assignment's row goes. ``expert_of`` ``[A]`` int32
    holds every assignment's expert, ``num_experts`` for one that routes
    nowhere (a padding token). Returns ``counts`` ``[E]``, ``row_of``
    ``[A]`` (the assignment's row in the padded layout; assignments that
    route nowhere get row 0), ``src_of`` ``[rows]`` (the assignment whose
    token fills the row, 0 for padding rows), ``tile_expert``
    ``[rows // tm]`` and ``tiles`` ``[1]``, the live tiles."""
    E = int(num_experts)
    A = expert_of.shape[0]
    order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)
    sorted_e = expert_of[order]
    counts = jnp.bincount(expert_of, length=E + 1)[:E].astype(jnp.int32)
    padded = ((counts + tm - 1) // tm) * tm
    start = jnp.cumsum(counts) - counts
    pstart = jnp.cumsum(padded) - padded
    live = sorted_e < E
    e_safe = jnp.minimum(sorted_e, E - 1)
    dest = pstart[e_safe] + (jnp.arange(A, dtype=jnp.int32) - start[e_safe])
    dest = jnp.where(live, dest, rows)                  # dropped below
    src_of = jnp.zeros((rows,), jnp.int32).at[dest].set(order, mode="drop")
    row_of = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.where(live, dest, 0))
    ends = jnp.cumsum(padded)
    tiles = ends[-1] // tm
    first_row = jnp.arange(rows // tm, dtype=jnp.int32) * tm
    # a dead tile repeats the last live tile's expert: no weight moves
    first_row = jnp.minimum(first_row, jnp.maximum(ends[-1] - tm, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, first_row, side="right"), E - 1
    ).astype(jnp.int32)
    return counts, row_of, src_of, tile_expert, tiles.reshape(1).astype(
        jnp.int32)


def _experts_kernel(tile_expert_ref, tiles_ref, x_ref, wg_ref, wu_ref,
                    wd_ref, out_ref):
    t = pl.program_id(0)

    @pl.when(t < tiles_ref[0])
    def _live():
        x = x_ref[...]                                       # [tm, d]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)      # [tm, f]
        out_ref[...] = jnp.dot(h, wd_ref[0],
                               preferred_element_type=jnp.float32)

    @pl.when(t >= tiles_ref[0])
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)


def _pallas_experts(xs, w_gate, w_up, w_down, tile_expert, tiles, tm,
                    interpret):
    rows, d = xs.shape
    E, _, f = w_gate.shape

    def weights(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda t, te, nt: (te[t], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(rows // tm,),
        in_specs=[pl.BlockSpec((tm, d), lambda t, te, nt: (t, 0)),
                  weights((d, f)), weights((d, f)), weights((f, d))],
        out_specs=pl.BlockSpec((tm, d), lambda t, te, nt: (t, 0)))
    return pl.pallas_call(
        _experts_kernel, name="moe_experts_swiglu", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, tiles, xs, w_gate, w_up, w_down)


def _xla_experts(xs, w_gate, w_up, w_down, counts):
    """The sorted, unpadded rows through ``ragged_dot``; rows past the
    groups' total (assignments that route nowhere) come out zero."""
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=counts,
                            preferred_element_type=jnp.float32)
    g, u = dot(xs, w_gate), dot(xs, w_up)
    h = (g * jax.nn.sigmoid(g) * u).astype(xs.dtype)
    return dot(h, w_down)


def routed_experts(x, expert_idx, weights, w_gate, w_up, w_down,
                   valid=None, impl=None):
    """``sum_j weights[n, j] * swiglu_{expert_idx[n, j]}(x[n])``.

    x ``[N, d]`` float32; expert_idx ``[N, k]`` int32 and weights
    ``[N, k]`` float32 from the router; w_gate, w_up ``[E, d, f]`` and
    w_down ``[E, f, d]`` in the dtype the products run in. ``valid``
    ``[N]`` bool marks real tokens: the others route nowhere, cost
    nothing and come out zero. Returns ``(out [N, d] float32, counts
    [E] int32)``, the assignments each expert got. impl: None (auto —
    pallas on a TPU, xla elsewhere), "pallas", "interpret", "xla"."""
    N, d = x.shape
    k = expert_idx.shape[1]
    E = w_gate.shape[0]
    A = N * k
    reason = "requested" if impl else "backend"
    impl = impl or _dispatch.auto_impl()
    expert_of = expert_idx.reshape(A).astype(jnp.int32)
    if valid is not None:
        expert_of = jnp.where(jnp.repeat(valid, k), expert_of, E)
    xb = x.astype(w_gate.dtype)
    with _dispatch.resolved("moe_experts", impl, reason):
        if impl == "xla":
            with jax.named_scope("moe/dispatch"):
                order = jnp.argsort(expert_of, stable=True)
                counts = jnp.bincount(expert_of, length=E + 1)[:E].astype(
                    jnp.int32)
                row_of = jnp.zeros((A,), jnp.int32).at[order].set(
                    jnp.arange(A, dtype=jnp.int32))
                xs = xb[order // k]
            with jax.named_scope("moe/experts"):
                ys = _xla_experts(xs, w_gate, w_up, w_down, counts)
        else:
            tm = rows_per_tile(A, E)
            rows = padded_rows(A, E, tm)
            with jax.named_scope("moe/dispatch"):
                counts, row_of, src_of, tile_expert, tiles = group_layout(
                    expert_of, E, tm, rows)
                xs = xb[src_of // k]
            with jax.named_scope("moe/experts"):
                ys = _pallas_experts(xs, w_gate, w_up, w_down, tile_expert,
                                     tiles, tm, impl == "interpret")
        with jax.named_scope("moe/combine"):
            w = weights.astype(jnp.float32)
            if valid is not None:
                w = jnp.where(valid[:, None], w, 0.0)
            picked = ys[row_of].reshape(N, k, d)
            # a token that routes nowhere reads row 0, which may belong
            # to nobody: its weight is 0 and the row must not be NaN
            picked = jnp.where((w > 0)[:, :, None], picked, 0.0)
            out = jnp.einsum("nk,nkd->nd", w, picked)
    return out, counts
