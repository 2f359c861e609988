"""Readers for a latent-attention configuration: what the decode steps of
the traced slice had to read of the latent pool, over the time the latent
decode kernel took.

Since the PR that added these, over a pool with a latent group an
``engine/step`` span that sent a decode step carries ``mla_positions``
(the latent positions its live rows read, summed over the layers) and
``mla_rows`` (its live rows), and a ``generator/prefill`` span
``mla_positions`` (the prompts' real positions, summed over the layers).
A program that has no such attrs, as the parent of that PR has not, gives
every reader here nothing to read: None, and the line leaves the metric
out; a share is never 0 for want of a reading.

The family gives ``mla_decode_bytes(sz, positions, row_layers,
kv_bytes)``. The kernel is found by its Pallas call's name (a metric's
``match``, as ``readers_moe``'s).
"""
from .readers import of_a_chip
from .readers_moe import seconds_matching_any
from .readers_ssm import ATTRS, spans_with


@of_a_chip
def mla_decode_roofline(facts, events, spec):
    """Bandwidth-bound: the bytes the slice's decode steps had to move
    through the latent decode kernel (every latent position read once,
    each row's query in and output out, a layer) at the peak HBM
    bytes/s, over the device time of the kernel's events. The positions
    come from the program's step spans, whatever implements the
    kernel."""
    seconds = seconds_matching_any(events, [spec["match"]])
    steps = spans_with(facts, "engine/step", "mla_positions")
    if not seconds or not steps:
        return None
    fam, sz = facts["family"], facts["sizes"]
    nbytes = sum(fam.mla_decode_bytes(
        sz, r[ATTRS]["mla_positions"],
        r[ATTRS]["mla_rows"] * sz.num_hidden_layers, facts["kv_bytes"])
        for r in steps)
    return 100.0 * nbytes / facts["peaks"]["hbm_bytes_per_s"] / seconds
