"""A training loop: one ``Executor.run`` of the cell's program a step, a
fresh batch of token ids from the seed every step, the loss fetched every
step as a user's loop does.

Set-up builds the one compiled step with its state, drives it through its
first steps from the seed (the reference follows the same ones after the
window) and hands that same object to the window. ``train_tokens_per_s`` is
the tokens of every step of the window over all its seconds; the window
closes with the first step that ends past ``--seconds``.
"""
import gc
import time

import numpy as np

from ..harness import compare
from ..harness.tracing import Slice, span

FOLLOWED = 3    # steps the reference follows


def expected_impl(run):
    return "interpret" if run.rehearsal else "pallas"


def composite_sites(run, ops):
    """Traced call sites of ``ops`` that did not resolve to the kernel."""
    from paddle_tpu.kernels import _dispatch
    return sum(n for (op, impl, _why), n in
               _dispatch.resolved_counts().items()
               if op in ops and impl != expected_impl(run))


def memory_peak():
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def sizes_of(run):
    cell = run.cell
    traffic = dict(cell.traffic)
    if run.rehearsal:
        traffic.update(traffic["rehearsal"])
    return cell.family.Sizes(cell.config, run.rehearsal), traffic


def make_step(fam, built):
    """The one call and feed that set-up and the window both use."""
    def step(tokens):
        with span("feed"):
            feed = fam.train_feed(tokens)
        with span("dispatch"):
            loss, = built["exe"].run(
                built["main"], feed=feed, scope=built["scope"],
                fetch_list=[built["loss"]], return_numpy=False)
        with span("fetch"):
            return float(np.asarray(loss).reshape(()))
    return step


def follow(fam, sz, recipe, built, step, first, seed):
    """The program's own readings of its first steps: each loss, the norm
    of every leaf of the first gradient as Adam got it (its first moment
    after one step, over 1 - beta1), and of every leaf's change."""
    scope = built["scope"]
    program = {"losses": [step(first[0])]}
    moments = fam.leaf_norms({name: scope.find_var(var)
                              for name, var in built["moment1"].items()})
    program["grad_norms"] = {n: m / (1.0 - recipe["beta1"])
                             for n, m in moments.items()}
    program["losses"] += [step(t) for t in first[1:]]
    program["change_norms"] = fam.leaf_norms(
        {name: scope.find_var(name) for name in built["moment1"]},
        fam.init_params(sz, seed))
    return program


def run(run):
    cell, fam = run.cell, run.cell.family
    sz, traffic = sizes_of(run)
    batch, seq = traffic["batch"], traffic["seq"]
    recipe = cell.config["train"]
    rng = np.random.default_rng(run.seed)

    def draw():
        return rng.integers(0, sz.vocab_size, (batch, seq + 1),
                            dtype=np.int32)

    phases = {"open_s": time.perf_counter() - run.t0}
    built = fam.build_train(sz, recipe, batch, seq, run.seed)
    exe = built["exe"]
    step = make_step(fam, built)
    phases["build_s"] = time.perf_counter() - run.t0 - phases["open_s"]

    # ---- set-up: the first steps, through the window's own call and feed
    first = [draw() for _ in range(FOLLOWED)]
    program = follow(fam, sz, recipe, built, step, first, run.seed)
    compiles = exe.cache_stats()["compiles"]
    setup_s = time.perf_counter() - run.t0
    phases["first_steps_s"] = setup_s - phases["open_s"] - phases["build_s"]

    # ---- the window
    t0 = time.perf_counter()
    steps, losses = 0, []
    while True:
        losses.append(step(draw()))
        steps += 1
        now = time.perf_counter()
        if now - t0 >= run.seconds:
            break
    window_s = now - t0
    tokens_per_s = steps * batch * seq / window_s
    recompiles = exe.cache_stats()["compiles"] - compiles

    events = trace_read_s = None
    if run.trace:
        with Slice(run.root, run.keep_xplane) as traced:
            for _ in range(traffic["traced_steps"]):
                losses.append(step(draw()))
        events, trace_read_s = traced.read(), traced.read_s

    peak = memory_peak()
    composite = composite_sites(run, ("flash_attention",))
    failed = sum(1 for x in losses if not np.isfinite(x))
    del built, exe, step
    gc.collect()

    # ---- the reference follows the first steps, once the state is freed
    t_ref = time.perf_counter()
    reference = fam.reference_train(sz, recipe, run.seed, first)
    gaps, where = compare.train_gaps(program, reference)
    reference_s = time.perf_counter() - t_ref
    limits = traffic["limits"]
    checks = [compare.check(name, gaps[name], limits[name])
              for name in ("loss_gap", "grad_norm_gap", "change_norm_gap")]
    checks.append(compare.check("attention_composite_sites", composite, 0))

    facts = {"family": fam, "sizes": sz, "peaks": run.peaks,
             "batch": batch, "seq": seq, "window_s": window_s,
             "steps": steps, "tokens_per_s": tokens_per_s,
             "executor_recompiles": recompiles,
             "traced_steps": traffic["traced_steps"] if run.trace else None}
    return {"attempted": len(losses), "failed": failed,
            "end_to_end": {"train_tokens_per_s": tokens_per_s,
                           "setup_s": setup_s},
            "facts": facts, "events": events, "checks": checks,
            "memory_peak_bytes": peak,
            "notes": {"setup_phases": phases, "worst_leaves": where,
                      "reference_s": reference_s,
                      "trace_read_s": trace_read_s,
                      "steps": steps, "window_s": window_s,
                      "losses": program["losses"] + reference["losses"]}}


def calibrate(run, seeds):
    """The readings that the limits are set from, one line a seed: the
    program against the reference (the lower reading), the reference in
    the precision below the configuration's against itself (the control),
    and the reference with half its batch left out (a fault). One compiled
    step serves every seed; no window is needed."""
    cell, fam = run.cell, run.cell.family
    sz, traffic = sizes_of(run)
    batch, seq = traffic["batch"], traffic["seq"]
    recipe = cell.config["train"]
    built = fam.build_train(sz, recipe, batch, seq, seeds[0])
    step = make_step(fam, built)
    for seed in seeds:
        rng = np.random.default_rng(seed)
        first = [rng.integers(0, sz.vocab_size, (batch, seq + 1),
                              dtype=np.int32) for _ in range(FOLLOWED)]
        built["restart"](seed)
        program = follow(fam, sz, recipe, built, step, first, seed)
        reference = fam.reference_train(sz, recipe, seed, first)
        out = {"seed": seed,
               "program": compare.train_gaps(program, reference)}
        for mode in traffic["controls"]:
            out[mode] = compare.train_gaps(fam.reference_train(
                sz, recipe, seed, first, mode=mode), reference)
        out["half_batch"] = compare.train_gaps(fam.reference_train(
            sz, recipe, seed, first, keep_rows=batch // 2), reference)
        out["ref_losses"] = reference["losses"]
        yield out
