"""Shared helpers for op lowerings."""
import jax.numpy as jnp

from ..framework.dtype import np_dtype


def x_of(ins, slot="X"):
    v = ins.get(slot)
    return v[0] if v else None


def int64_t():
    """Canonical device dtype for a fluid `int64` tensor.

    Int64 policy (see PARITY.md): TPU vector units are 32-bit; with
    jax_enable_x64 off (the default) int64 device tensors are stored
    int32 — deliberately and silently HERE (values are op-internal
    indices/counts that provably fit), while user-fed int64 data is
    validated at the executor feed boundary and raises on overflow
    instead of wrapping (framework/executor.py). Enabling
    jax_enable_x64 restores true int64 end to end."""
    import jax
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def as_dtype(attrs, key="dtype", default="float32"):
    """Resolve an op's dtype attr to the device dtype. Int64 policy
    (PARITY.md): with x64 off, attr-requested (u)int64 storage maps to
    32-bit — op outputs are indices/counts that fit; user-fed int64 is
    range-checked at the executor feed boundary instead."""
    dt = np_dtype(attrs.get(key, default))
    import numpy as np
    if dt in (np.int64, np.uint64):
        import jax
        if not jax.config.jax_enable_x64:
            return np.int32 if dt == np.int64 else np.uint32
    return dt


def host_concrete(*vals):
    """True when every value is host-resident (numpy / python scalar).

    Shape arithmetic stays on host: the `shape` op emits a numpy array
    (a tensor's shape is trace-time metadata, not device data), and the
    scalar-arithmetic lowerings below preserve numpy-ness so dims
    flowing into ShapeTensorList inputs (reshape/fill_constant) remain
    concrete ints at lowering. Mirrors the reference, which computes
    shapes on CPU (reshape_op.cc reads its ShapeTensor host-side)."""
    import numpy as _np
    return all(v is None or isinstance(v, (_np.ndarray, _np.generic,
                                           int, float, bool))
               for v in vals)


def bcast_y(x, y, axis):
    """Fluid elementwise broadcast: Y's shape matches a contiguous slice of
    X's shape starting at `axis` (reference:
    operators/elementwise/elementwise_op_function.h). axis=-1 means align to
    the trailing dims (numpy broadcasting)."""
    if x.ndim == y.ndim:
        return y
    if axis is None or axis == -1:
        axis = x.ndim - y.ndim
    # strip trailing size-1 dims fluid allows on Y
    yshape = list(y.shape)
    while len(yshape) > 0 and len(yshape) + axis > x.ndim and yshape[-1] == 1:
        yshape.pop()
    n_trail = x.ndim - axis - len(yshape)
    return y.reshape(tuple(yshape) + (1,) * n_trail)


def reduce_axes(attrs, ndim):
    if attrs.get("reduce_all", False):
        return tuple(range(ndim)), bool(attrs.get("keep_dim", False))
    dim = attrs.get("dim", [0])
    if isinstance(dim, int):
        dim = [dim]
    axes = tuple(d % ndim for d in dim)
    return axes, bool(attrs.get("keep_dim", False))


def normalize_padding(paddings, n_spatial):
    """[p]*n, [ph, pw], or [ph0, ph1, pw0, pw1] -> ((lo, hi), ...)."""
    p = list(paddings)
    if len(p) == n_spatial:
        return tuple((q, q) for q in p)
    if len(p) == 2 * n_spatial:
        return tuple((p[2 * i], p[2 * i + 1]) for i in range(n_spatial))
    if len(p) == 1:
        return tuple((p[0], p[0]) for _ in range(n_spatial))
    raise ValueError(f"bad paddings {paddings}")


def bilinear_sample(img, yy, xx):
    """Bilinear sample img [C, H, W] at float coords yy/xx (same shape);
    taps outside the image contribute ZERO (the convention every sampling
    op here shares — grid_sampler, deformable_conv, prroi_pool)."""
    H, W = img.shape[-2], img.shape[-1]
    y0 = jnp.floor(yy)
    x0 = jnp.floor(xx)
    wy = yy - y0
    wx = xx - x0
    out = 0.0
    for (ys, xs, wgt) in ((y0, x0, (1 - wy) * (1 - wx)),
                          (y0, x0 + 1, (1 - wy) * wx),
                          (y0 + 1, x0, wy * (1 - wx)),
                          (y0 + 1, x0 + 1, wy * wx)):
        ok = (ys >= 0) & (ys < H) & (xs >= 0) & (xs < W)
        yi = jnp.clip(ys, 0, H - 1).astype(jnp.int32)
        xi = jnp.clip(xs, 0, W - 1).astype(jnp.int32)
        v = img[..., yi, xi]                      # [C, *coords]
        out = out + v * (wgt * ok.astype(img.dtype))
    return out


def compact_rows(x, keep):
    """Compact kept rows to a zero-padded prefix (masked-dense idiom shared
    by split_lod_tensor, split_ids, sequence_erase): returns
    (out_like_x, count) where out[:count] are x's rows with keep==True in
    order and the tail is zero."""
    keep = keep.astype(bool)
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    dest = jnp.where(keep, pos, x.shape[0])
    out = jnp.zeros_like(x).at[dest].set(x, mode="drop")
    return out, jnp.sum(keep, dtype=jnp.int32)


def sigmoid_bce(logit, label):
    """Numerically stable sigmoid binary cross-entropy (shared by
    sigmoid_cross_entropy_with_logits and yolov3_loss)."""
    return (jnp.maximum(logit, 0) - logit * label
            + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def roi_batch_indices(ins, n_rois):
    """Per-ROI image index from the optional RoisBatch ([R] explicit) or
    RoisNum ([B] counts) inputs; all-zero when neither is given. Shared
    by every roi-consuming op (roi_align, psroi family, perspective
    transform, roi_pool)."""
    import jax.numpy as jnp
    if ins.get("RoisBatch"):
        return jnp.reshape(ins["RoisBatch"][0], (-1,)).astype(jnp.int32)
    if ins.get("RoisNum"):
        counts = jnp.reshape(ins["RoisNum"][0], (-1,)).astype(jnp.int32)
        ends = jnp.cumsum(counts)
        return jnp.searchsorted(
            ends, jnp.arange(n_rois, dtype=jnp.int32),
            side="right").astype(jnp.int32)
    return jnp.zeros((n_rois,), jnp.int32)


def named(scope):
    """``jax.named_scope(scope)``, or nothing where the op names none."""
    import contextlib
    import jax
    return jax.named_scope(scope) if scope else contextlib.nullcontext()
