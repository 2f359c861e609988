"""Collective-traffic ledger: what the mesh actually moves per step.

The utilization gauges say how hard one chip works; nothing says what
the MESH does — GSPMD (arXiv 2105.04663) decides where all-reduces,
all-gathers, reduce-scatters, all-to-alls and collective-permutes land,
and those decisions are invisible until the step is slow. This module
parses a compiled executable's HLO (``compiled.as_text()``), attributes
every collective to a mesh axis via its ``replica_groups`` (or
``source_target_pairs``) shape, and aggregates **bytes + counts per
(collective, axis) per executable** — the MegaScale-style communication
attribution the mesh PRs (tensor-parallel serving, 1F1B pipeline, MoE)
get gated on.

Conventions (documented because they ARE the numbers):

- ``payload_bytes`` — the tensor bytes the collective operates on (the
  result for all-reduce/all-gather/all-to-all/collective-permute, the
  larger OPERAND for reduce-scatter), per step, per instance.
- ``wire_bytes`` — per-device link traffic under the standard ring
  algorithms: all-reduce ``2(S-1)/S``, all-gather / reduce-scatter /
  all-to-all ``(S-1)/S`` of the payload, collective-permute ``1x``
  (S = replica-group size). An upper-bound model, same spirit as the
  pre-fusion ``cost_analysis`` bytes the HBM gauge rides.
- axis attribution — replica-group device ids are unraveled over the
  mesh's axis sizes (XLA's device assignment follows the flattened
  mesh device list); the label is the ``+``-join of every axis the
  group varies over (``"tp"``, ``"dp+sp"``), ``"none"`` for
  single-device groups.

Rooflining divides each axis's per-step wire bytes by the ICI (or DCN,
for axes the caller marks cross-slice) bandwidth peak tables in
:mod:`utilization` — the same ``set_peaks()``-overridable tables
``bench.py`` reads, so the live ``device_comm_bound_ratio`` gauge and
the offline bench agree by construction. Off a TPU (CPU dev boxes) the
reference-chip peaks below rank/predict instead, flagged ``ref_peaks``
— the profiling.py convention. On a TPU they are never used: a chip
that is missing from the tables is an error (:func:`_fabric_peaks`).
"""
import re
import time

import numpy as np

from .. import profiler as _prof
from . import tracing as _tracing
from .metrics import default_registry
from .utilization import dcn_peak, ici_peak, peak_flops, hbm_peak

# reference-chip comm peaks for prediction when the local device is
# unlisted (CPU CI): v5e ICI / host DCN — ordering and fractions are
# what matter offline, not absolute seconds (profiling.REF_PEAK_* idiom)
REF_ICI_PEAK = 200e9
REF_DCN_PEAK = 25e9



class UnknownDevicePeakError(LookupError):
    """A TPU is present but its ``device_kind`` is in neither fabric
    peak table: pricing its collectives at another chip's bandwidth
    would put a made-up number under a device metric's name."""


def _fabric_peaks():
    """``(ici, dcn, ici_is_ref, dcn_is_ref)`` bytes/s for the local
    device: the :mod:`utilization` tables (or ``set_peaks`` overrides),
    else the reference peaks — off a TPU only."""
    ici, dcn = ici_peak(), dcn_peak()
    if ici is None or dcn is None:
        import jax
        if jax.default_backend() == "tpu":
            raise UnknownDevicePeakError(
                f"TPU device kind {jax.devices()[0].device_kind!r} has no "
                f"ICI/DCN entry in observability/utilization.py; add it "
                f"with its source or call utilization.set_peaks(...)")
    return (REF_ICI_PEAK if ici is None else ici,
            REF_DCN_PEAK if dcn is None else dcn, ici is None, dcn is None)


COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

# wire-traffic multiplier per payload byte under ring algorithms; S is
# the replica-group size (lambdas so S=1 degenerates to 0 traffic)
_WIRE_FACTOR = {
    "all-reduce": lambda s: 2.0 * (s - 1) / s if s > 1 else 0.0,
    "all-gather": lambda s: (s - 1) / s if s > 1 else 0.0,
    "reduce-scatter": lambda s: (s - 1) / s if s > 1 else 0.0,
    "all-to-all": lambda s: (s - 1) / s if s > 1 else 0.0,
    "collective-permute": lambda s: 1.0,
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1,
    "f8e5m2": 1,
}

_KIND_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\}|\{\{[0-9,\s]+\}(?:,\s*\{[0-9,\s]+\})*\}|"
    r"\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{[0-9]+,[0-9]+\},?)*)\}")
_METADATA_RE = re.compile(r'op_name="([^"]*)"')

_BYTES_TOTAL = default_registry().counter(
    "comms_bytes_total",
    "predicted per-step collective wire bytes of newly audited "
    "executables, by collective kind and mesh axis",
    labels=("collective", "axis"), max_series=64)
_OPS_TOTAL = default_registry().counter(
    "comms_ops_total",
    "collective instances found in newly audited executables' HLO, by "
    "collective kind and mesh axis",
    labels=("collective", "axis"), max_series=64)
_COMM_BOUND = default_registry().gauge(
    "device_comm_bound_ratio",
    "predicted fraction of step time spent in collectives for the most "
    "recently compiled executable (ledger wire bytes / axis bandwidth "
    "vs the compute/HBM roofline)",
    labels=("where",), max_series=16)


def _matching_paren(line, open_idx):
    """Index of the ')' closing the '(' at ``open_idx`` — TPU tiled
    layouts put parens INSIDE operand shapes (``{1,0:T(8,128)}``), so
    a first-')' scan truncates variadic operand lists."""
    depth = 0
    for i in range(open_idx, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i
    return -1


def _shapes_bytes(text):
    """Total bytes of every typed shape literal in ``text`` (handles
    tuple result types and multi-operand lists)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue                       # token/opaque types
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nbytes
    return total


def parse_replica_groups(attr):
    """Replica groups from either HLO syntax: explicit
    ``{{0,1},{2,3}}`` or iota ``[G,S]<=[d0,d1,..]T(p0,p1,..)``.
    Returns a list of int tuples."""
    attr = attr.strip()
    if attr.startswith("{"):
        groups = []
        for grp in re.findall(r"\{([0-9,\s]*?)\}", attr):
            ids = tuple(int(x) for x in grp.replace(" ", "").split(",")
                        if x != "")
            if ids:
                groups.append(ids)
        return groups
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?",
                 attr)
    if not m:
        return []
    out_shape = [int(x) for x in m.group(1).split(",")]
    dims = [int(x) for x in m.group(2).split(",")]
    perm = [int(x) for x in m.group(3).split(",")] if m.group(3) \
        else list(range(len(dims)))
    ids = np.arange(int(np.prod(dims))).reshape(dims).transpose(perm) \
        .reshape(out_shape)
    return [tuple(int(x) for x in row) for row in ids]


def axes_label(groups, mesh):
    """The mesh-axis attribution of a replica-group list: device ids
    unravel over the mesh's axis sizes (XLA's device assignment is the
    flattened mesh device list), and the label names every axis the
    groups vary over, joined ``+`` in mesh-axis order. ``"none"`` for
    degenerate single-device groups, ``"unknown"`` when the ids don't
    fit the mesh (foreign device assignment)."""
    if mesh is None:
        return "unknown"
    names = tuple(mesh.axis_names)
    dims = tuple(int(mesh.shape[a]) for a in names)
    total = int(np.prod(dims))
    varying = set()
    for g in groups:
        if len(g) < 2:
            continue
        if any(d >= total or d < 0 for d in g):
            return "unknown"
        coords = [np.unravel_index(d, dims) for d in g]
        for i in range(len(dims)):
            if len({c[i] for c in coords}) > 1:
                varying.add(i)
    if not varying:
        return "none"
    return "+".join(names[i] for i in sorted(varying))


def parse_collectives(hlo_text, mesh=None):
    """Scan optimized-HLO text for collective instructions. Returns one
    dict per instance::

        {"kind", "axis", "group_size", "n_groups", "payload_bytes",
         "wire_bytes", "op_name"}

    ``-done`` halves of async pairs are skipped (the ``-start`` carries
    the shape); explicit user collectives keep their own op_name in
    ``metadata`` while GSPMD-inserted reshards carry the op they were
    inserted FOR — the sharding audit keys off that distinction."""
    out = []
    for line in hlo_text.splitlines():
        m = _KIND_RE.search(line)
        if m is None:
            continue
        kind, variant = m.group(1), m.group(2)
        if variant == "-done":
            continue                       # counted at the -start half
        eq = line.find(" = ")
        rtype = line[eq + 3:m.start()] if eq >= 0 else ""
        close = _matching_paren(line, m.end() - 1)
        operands = line[m.end():close if close >= 0 else len(line)]
        attrs = line[close + 1:] if close >= 0 else line
        if kind == "collective-permute":
            pm = _PAIRS_RE.search(attrs)
            groups = parse_replica_groups("{" + pm.group(1) + "}") \
                if pm else []
        else:
            gm = _GROUPS_RE.search(attrs)
            groups = parse_replica_groups(gm.group(1)) if gm else []
        unknown_global = False
        if not groups and kind != "collective-permute":
            # replica_groups={} (or absent) is HLO for "ALL devices in
            # one group" — an empty parse must not let the largest
            # collective vanish with group_size 1 / wire 0
            if mesh is not None:
                names = tuple(mesh.axis_names)
                total = int(np.prod([int(mesh.shape[a])
                                     for a in names]))
                if total > 1:
                    groups = [tuple(range(total))]
            else:
                # no mesh to size the group: count it at the S=2 wire
                # LOWER bound under an "unknown" axis rather than 0
                unknown_global = True
        size = 2 if unknown_global \
            else max((len(g) for g in groups), default=1)
        if kind == "collective-permute" and groups:
            # pairs, not groups: the payload crosses one link per pair
            size = 2
        if kind == "reduce-scatter":
            payload = _shapes_bytes(operands)  # the larger, pre-scatter
        elif variant == "-start":
            # async halves type their result as a tuple carrying the
            # operand(s) alongside the output (+ backend contexts) —
            # summing the tuple would overcount, so derive from the
            # operand list instead: the gathered result is operand x S
            payload = _shapes_bytes(operands)
            if kind == "all-gather":
                payload *= size
        else:
            payload = _shapes_bytes(rtype)
            if kind == "all-reduce" and payload == 0:
                payload = _shapes_bytes(operands)
        md = _METADATA_RE.search(attrs)
        out.append({
            "kind": kind,
            "axis": "unknown" if unknown_global
            else axes_label(groups, mesh),
            "group_size": int(size),
            "n_groups": len(groups),
            "payload_bytes": int(payload),
            "wire_bytes": int(payload * _WIRE_FACTOR[kind](size)),
            "op_name": md.group(1) if md else "",
        })
    return out


def _rides_dcn(axis, dcn_axes):
    """A multi-axis group label (``"dp+sp+tp"``) rides DCN when ANY of
    its component axes is cross-slice — the slowest fabric in the path
    prices the collective."""
    return any(part in dcn_axes for part in axis.split("+"))


class CommLedger:
    """Per-(collective, axis) aggregation of one executable's parsed
    collectives, with the roofline prediction attached."""

    def __init__(self, collectives, mesh=None):
        self.collectives = list(collectives)
        self.mesh = mesh
        self.rows = {}
        for c in self.collectives:
            key = (c["kind"], c["axis"])
            row = self.rows.setdefault(
                key, {"count": 0, "payload_bytes": 0, "wire_bytes": 0,
                      "group_size": c["group_size"]})
            row["count"] += 1
            row["payload_bytes"] += c["payload_bytes"]
            row["wire_bytes"] += c["wire_bytes"]
            row["group_size"] = max(row["group_size"], c["group_size"])

    @classmethod
    def from_hlo(cls, hlo_text, mesh=None):
        return cls(parse_collectives(hlo_text, mesh), mesh=mesh)

    @classmethod
    def from_compiled(cls, compiled, mesh=None):
        return cls.from_hlo(compiled.as_text(), mesh=mesh)

    def __bool__(self):
        return bool(self.rows)

    def totals(self):
        by_axis = {}
        count = payload = wire = 0
        for (kind, axis), row in self.rows.items():
            count += row["count"]
            payload += row["payload_bytes"]
            wire += row["wire_bytes"]
            by_axis[axis] = by_axis.get(axis, 0) + row["wire_bytes"]
        return {"count": count, "payload_bytes": payload,
                "wire_bytes": wire, "by_axis": by_axis}

    def predicted_comm_s(self, dcn_axes=()):
        """Predicted per-step seconds in collectives: each axis's wire
        bytes over its fabric bandwidth (DCN for axes in ``dcn_axes``,
        ICI otherwise; reference peaks on unlisted hardware), summed —
        a serial upper bound. Returns ``(seconds, used_ref_peaks)``;
        the flag is True iff any axis ACTUALLY divided by a reference
        peak (a fabric whose table/override has a real value never
        taints the flag)."""
        ici, dcn, ici_ref, dcn_ref = _fabric_peaks()
        total = 0.0
        ref = False
        for axis, wire in self.totals()["by_axis"].items():
            if _rides_dcn(axis, dcn_axes):
                total += wire / dcn
                ref = ref or dcn_ref
            else:
                total += wire / ici
                ref = ref or ici_ref
        return total, ref

    def comm_bound_ratio(self, cost, dcn_axes=()):
        """Predicted fraction of step time spent communicating:
        ``t_comm / (t_comm + t_step)`` with ``t_step`` the
        compute/bandwidth roofline of ``cost`` (an
        ``utilization.executable_cost`` dict). None when ``cost`` is
        missing/empty (incl. the ``cost_for`` False sentinel on
        backends without cost_analysis) — unknown compute must read as
        "no prediction", not as 100% comm-bound."""
        if not cost:
            return None
        t_comm, _ref = self.predicted_comm_s(dcn_axes=dcn_axes)
        from .profiling import REF_HBM_PEAK, REF_PEAK_FLOPS
        pf = peak_flops() or REF_PEAK_FLOPS
        pb = hbm_peak() or REF_HBM_PEAK
        t_step = max(cost.get("flops", 0.0) / pf,
                     cost.get("bytes", 0.0) / pb)
        if t_comm <= 0 and t_step <= 0:
            return None
        return t_comm / (t_comm + t_step)

    def to_dict(self):
        """JSON-safe nesting for the MULTICHIP dryrun records and the
        shard_report CLI: ``{"<kind>@<axis>": row, ..., "totals": {...}}``
        (no dots in keys — tools/bench_compare.py dotted paths reach
        every leaf)."""
        out = {f"{kind}@{axis}": dict(row)
               for (kind, axis), row in sorted(self.rows.items())}
        out["totals"] = self.totals()
        return out

    def format_table(self):
        lines = [f"{'collective':<20} {'axis':<8} {'count':>5} "
                 f"{'payload MiB':>12} {'wire MiB':>10}"]
        for (kind, axis), row in sorted(self.rows.items()):
            lines.append(
                f"{kind:<20} {axis:<8} {row['count']:>5} "
                f"{row['payload_bytes'] / 2**20:>12.3f} "
                f"{row['wire_bytes'] / 2**20:>10.3f}")
        t = self.totals()
        lines.append(f"{'TOTAL':<20} {'':<8} {t['count']:>5} "
                     f"{t['payload_bytes'] / 2**20:>12.3f} "
                     f"{t['wire_bytes'] / 2**20:>10.3f}")
        return "\n".join(lines)


def flat_allreduce_wire_bytes(ledger, mesh, dcn_axes=("dcn_dp",)):
    """What the NAIVE flat all-reduce would move over DCN per step: the
    full gradient volume (reconstructed as the hier path's cross-slice
    payload x the in-slice degree it was scattered by) all-reduced over
    the whole ``S = dcn x dp`` group at DCN pricing —
    ``2(S-1)/S x B_total`` per device. The yardstick
    :func:`assert_hier_decomposition` holds the observed DCN traffic
    against."""
    inner = 1
    total = 1
    for a in mesh.axis_names:
        total *= int(mesh.shape[a])
        if a not in dcn_axes:
            inner *= int(mesh.shape[a])
    dcn_payload = sum(row["payload_bytes"]
                      for (kind, axis), row in ledger.rows.items()
                      if _rides_dcn(axis, dcn_axes))
    return _WIRE_FACTOR["all-reduce"](total) * dcn_payload * inner


def assert_hier_decomposition(compiled_or_ledger, mesh, dcn_axes=None,
                              where="train"):
    """Pre-burn gate for the multi-slice hierarchical grad sync: parse
    the compiled executable's collectives and PROVE the decomposition
    before the first slab is dispatched. Three checks, all fatal
    (:class:`~paddle_tpu.resilience.HierarchicalCommsError`):

    1. every DCN-priced collective's group varies ONLY over declared
       cross-slice axes — a ``"dcn_dp+dp"`` label means a collective
       spans both fabrics and the whole payload crawls at DCN speed;
    2. the observed cross-slice wire bytes are STRICTLY below the flat
       all-reduce estimate (:func:`flat_allreduce_wire_bytes`) — the
       decomposition must actually pay off, not just exist;
    3. cross-slice collectives exist at all — zero DCN rows on a
       dcn_dp mesh means hier_grad_sync never ran and gradients are
       not synchronized across slices.

    Returns the ledger on success so callers can log it. ``dcn_axes``
    defaults to ``FLAGS_comms_dcn_axes``, falling back to
    ``("dcn_dp",)`` (the axis the mesh module declares cross-slice).
    """
    from ..resilience import HierarchicalCommsError
    if dcn_axes is None:
        from ..flags import flag as _flag
        dcn_axes = tuple(a.strip() for a in
                         _flag("comms_dcn_axes").split(",")
                         if a.strip()) or ("dcn_dp",)
    ledger = compiled_or_ledger \
        if isinstance(compiled_or_ledger, CommLedger) \
        else CommLedger.from_compiled(compiled_or_ledger, mesh)
    violations = []
    dcn_wire = 0
    dcn_rows = 0
    for (kind, axis), row in sorted(ledger.rows.items()):
        if not _rides_dcn(axis, dcn_axes):
            continue
        dcn_rows += row["count"]
        dcn_wire += row["wire_bytes"]
        stray = [p for p in axis.split("+") if p not in dcn_axes]
        if stray:
            violations.append(
                f"{kind}@{axis}: group varies over non-DCN axes "
                f"{stray} ({row['wire_bytes']} wire bytes would cross "
                f"slices carrying in-slice traffic)")
    if dcn_rows == 0:
        violations.append(
            "no cross-slice collectives found — the hier_grad_sync "
            "pass did not run on this program (compile it through "
            "CompiledProgram.with_data_parallel over the dcn_dp mesh) "
            "and per-slice gradients would silently diverge")
    else:
        flat = flat_allreduce_wire_bytes(ledger, mesh, dcn_axes)
        if flat and dcn_wire >= flat:
            violations.append(
                f"cross-slice wire bytes {dcn_wire} do not beat the "
                f"flat all-reduce estimate {flat:.0f} — the "
                f"decomposition exists but does not pay")
    if violations:
        raise HierarchicalCommsError(
            f"hierarchical-comms gate failed for {where!r} on mesh "
            f"{dict(mesh.shape)} (DCN axes {tuple(dcn_axes)}):\n  - "
            + "\n  - ".join(violations),
            violations=violations, ledger=ledger)
    return ledger


def observe_ledger(where, ledger, cost=None, dcn_axes=()):
    """Export one newly compiled executable's ledger: bump the
    per-(collective, axis) byte/op counters, set the predicted
    ``device_comm_bound_ratio{where}`` gauge, and — under an active
    profiler — lay down the ``comms/<axis>_bytes`` Perfetto counter
    track plus per-collective child spans (span length = the PREDICTED
    per-axis comm time, so the flame chart shows relative cost).
    Returns the comm-bound ratio (or None)."""
    for (kind, axis), row in ledger.rows.items():
        lab = (kind, axis)
        _BYTES_TOTAL.inc(row["wire_bytes"], labels=lab)
        _OPS_TOTAL.inc(row["count"], labels=lab)
    ratio = ledger.comm_bound_ratio(cost, dcn_axes=dcn_axes)
    # the gauge describes the MOST RECENTLY compiled executable: when
    # this one has no prediction (cost unavailable) it must not keep
    # exporting the previous executable's ratio — NaN is Prometheus's
    # "no value" (the PR-12 stale-gauge discipline)
    _COMM_BOUND.set(ratio if ratio is not None else float("nan"),
                    labels=(where,))
    if ledger.rows and (_prof.is_profiling()
                        or _tracing.current() is not None):
        _record_tracks(where, ledger, dcn_axes=dcn_axes)
    return ratio


def _record_tracks(where, ledger, dcn_axes=()):
    """One ``comms/ledger_<where>`` parent span with a child span per
    (collective, axis) — each child's duration is its predicted wire
    time — plus cumulative ``comms/<axis>_bytes`` counter samples."""
    ici, dcn, _, _ = _fabric_peaks()
    parent = _tracing.current() or _tracing.new_trace()
    t0 = time.perf_counter()
    cursor = t0
    cum_by_axis = {}
    with _tracing.ambient(parent):
        with _tracing.span(f"comms/ledger_{where}") as span_ctx:
            for (kind, axis), row in sorted(ledger.rows.items()):
                bw = dcn if _rides_dcn(axis, dcn_axes) else ici
                dur = max(row["wire_bytes"] / bw, 1e-9)
                _tracing.record_child(f"comm/{kind}@{axis}", cursor,
                                      cursor + dur, span_ctx)
                cursor += dur
                cum_by_axis[axis] = cum_by_axis.get(axis, 0) \
                    + row["wire_bytes"]
                _prof.record_counter(f"comms/{axis}_bytes", cursor,
                                     cum_by_axis[axis])
