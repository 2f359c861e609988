"""``closed_loop_serve`` with one more number deciding ``correct``:
``served_off_share``, the share of the compared served tokens that are not
the reference's first choice at their position.

The widest gap alone cannot tell a program in the configuration's
precision from one in the precision below where one discrete choice moves
the logits far: a routing choice flipped at a near tie between two experts
that each carry a large part of a token's output. There a sound run's
widest gap reaches the lower precision's, but the share of tokens that the
flips move off the reference's choice stays a fraction of what rounding
every product to the lower precision moves (``PERF.md`` lists both
readings). Both limits are data of the cell (``limits`` in its traffic
file); the widest gap is still compared, and bounds how far off any single
served token lies.

The traffic, the window, the sampled replies and the reference are
``closed_loop_serve``'s own; ``calibrate`` is too, and its lines'
``nonzero_share`` is this share.
"""
import numpy as np

from ..harness import compare
from . import closed_loop_serve as base

calibrate = base.calibrate


class _Recording:
    """A cell's family that keeps every gap its reference hands back."""

    def __init__(self, family):
        self.family, self.gaps = family, []

    def __getattr__(self, name):
        return getattr(self.family, name)

    def reference_served_gaps(self, *args, **kwargs):
        gaps = self.family.reference_served_gaps(*args, **kwargs)
        self.gaps.extend(gaps)
        return gaps


def off_share(gaps):
    """The share of served tokens whose gap is not 0; 1 where no token was
    compared, which no limit passes."""
    flat = np.concatenate([np.ravel(g) for g in gaps]) if gaps else []
    return float(np.mean(flat > 0)) if len(flat) else 1.0


def run(run):
    family = run.cell.family
    recording = run.cell.family = _Recording(family)
    try:
        outcome = base.run(run)
    finally:
        run.cell.family = family
    outcome["facts"]["family"] = family
    _, traffic, _ = base.sizes_of(run)
    outcome["checks"].insert(1, compare.check(
        "served_off_share", off_share(recording.gaps),
        traffic["limits"]["served_off_share"]))
    return outcome
