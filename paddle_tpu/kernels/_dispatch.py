"""Which implementation an attention op resolved to, said where a reader
of counters and traces will see it.

Both kernels have a composite that runs anywhere and is their parity
oracle. Taking it on a TPU is legitimate in two places (more than one
query per row over the paged pool; a general ``[B, H, Sq, Sk]`` bias in
flash attention) but it must never be silent: each traced call site
bumps ``attention_impl_total{op, impl, reason}`` and runs under a
``<op>/<impl>`` ``jax.named_scope``, so the HLO of a device trace names
the path that ran.

Under a mesh a Pallas custom call is one opaque op to GSPMD: it cannot
be partitioned, so XLA would all-gather the batch (or the head-sharded
KV pool) onto every chip to feed it. :func:`per_shard` runs the kernel
under ``jax.shard_map`` instead, batch over the data axes and heads over
``tp``, so each chip's kernel sees only its own shard.
"""
import math

import jax
from jax.sharding import PartitionSpec as P

from ..observability.metrics import default_registry

# logical dimension -> the mesh axes it shards over
_LOGICAL_AXES = {"batch": ("dcn_dp", "dp"), "heads": ("tp",)}

_RESOLVED = default_registry().counter(
    "attention_impl_total",
    "attention op call sites traced, by the implementation each "
    "resolved to (pallas / interpret / xla) and why (requested, "
    "backend, multi_query, general_bias)",
    labels=("op", "impl", "reason"), max_series=32)


def auto_impl():
    """The Pallas kernel on a TPU, the XLA composite everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolved(op, impl, reason):
    """Count one traced call site of ``op`` under ``impl`` and return
    the named scope its computation runs in."""
    _RESOLVED.inc(labels=(op, impl, reason))
    return jax.named_scope(f"{op}/{impl}")


def resolved_counts():
    """``{(op, impl, reason): call sites traced}`` so far in this
    process."""
    return {labels: int(v) for labels, v in _RESOLVED.samples()}


def per_shard(fn, mesh, args, dims, out_dims):
    """``fn(*args)``, run once per shard of ``mesh``.

    ``dims`` names each argument's dimensions (``"batch"``, ``"heads"``
    or None, one tuple per argument; a None argument is passed through)
    and ``out_dims`` the result's (a tuple of such tuples where ``fn``
    returns several arrays). A logical dimension shards only when
    every array that carries it divides by its axes' size; with nothing
    to shard, without a mesh, or inside an enclosing ``shard_map`` (the
    hierarchical data-parallel path, where arrays are per-device
    already) this is a plain call."""
    if mesh is None or mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return fn(*args)
    axes = {}
    for logical, names in _LOGICAL_AXES.items():
        names = tuple(a for a in names
                      if a in mesh.axis_names and mesh.shape[a] > 1)
        n = math.prod(mesh.shape[a] for a in names)
        if names and all(a.shape[i] % n == 0
                         for a, d in zip(args, dims) if a is not None
                         for i, l in enumerate(d) if l == logical):
            axes[logical] = names
    if not axes:
        return fn(*args)

    def spec(d):
        return P(*(axes.get(l) for l in d))

    # a None argument is an empty pytree: its spec binds nothing
    several = bool(out_dims) and isinstance(out_dims[0], tuple)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(spec(d) for d in dims),
        out_specs=tuple(spec(d) for d in out_dims) if several
        else spec(out_dims), check_vma=False)(*args)
