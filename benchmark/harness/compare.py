"""What decides ``correct``: the timed path's own numbers against the
plain reference's, each held to a limit of its own.

Every number is a gap that is 0 where program and reference agree. The
limits are data of the cell (``limits`` in its traffic file), set between
what sound runs read and what the lower-precision control reads
(``benchmark/calibrate.py``; the readings are in ``PERF.md``).
"""
import statistics


def check(name, value, limit):
    return {"name": name, "value": float(value), "limit": float(limit)}


def worst_leaf_gap(program, reference, leave_out=()):
    """The worst leaf's gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger: some leaves are all but zero."""
    floor = statistics.median(reference.values())
    worst, where = 0.0, None
    for name, ref in reference.items():
        if name in leave_out:
            continue
        gap = abs(program[name] - ref) / max(ref, floor)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def still_leaves(reference_grad_norms):
    """Leaves whose gradient is nought to rounding in the reference (a
    key's bias under softmax): under a thousandth of the median leaf's.
    Adam moves them by round-off alone, so their change is not compared."""
    floor = 1e-3 * statistics.median(reference_grad_norms.values())
    return {n for n, g in reference_grad_norms.items() if g < floor}


def train_gaps(program, reference):
    """``program`` and ``reference`` both hold ``losses``, ``grad_norms``
    and ``change_norms`` of the same first steps."""
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(program["losses"], reference["losses"]))
    grad, grad_leaf = worst_leaf_gap(program["grad_norms"],
                                     reference["grad_norms"])
    change, change_leaf = worst_leaf_gap(
        program["change_norms"], reference["change_norms"],
        leave_out=still_leaves(reference["grad_norms"]))
    return {"loss_gap": loss, "grad_norm_gap": grad,
            "change_norm_gap": change}, \
        {"grad_norm_gap": grad_leaf, "change_norm_gap": change_leaf}


def verdict(checks):
    return all(c["value"] <= c["limit"] for c in checks)
