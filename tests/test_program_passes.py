"""Pre-lowering program optimization pipeline (framework/passes.py):
registry ordering/override/error surface, DCE/CSE semantics, bucketed
multi-tensor optimizer fusion bitwise parity (A/B against the unfused
path, guard on/off, run() and run_steps()), the FLAGS_program_passes=0
bitwise guard, compile-cache keying on the pass configuration, and the
trace/compile telemetry split."""
import json
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import passes
import pytest

from paddle_tpu.framework.passes import (Pass, UnknownPassError,
                                         apply_passes, get_pass)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _set_passes(spec):
    fluid.set_flags({"FLAGS_program_passes": spec})


class _passes_flag:
    def __init__(self, spec):
        self.spec = spec

    def __enter__(self):
        self.old = fluid.get_flags("FLAGS_program_passes")[
            "FLAGS_program_passes"]
        _set_passes(self.spec)

    def __exit__(self, *a):
        _set_passes(self.old)


def _build(optimizer="adam", with_dropout=False, lr=0.01):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        y = layers.data("y", [-1, 1], dtype="float32")
        h = layers.fc(x, 16, act="relu")
        if with_dropout:
            h = layers.dropout(h, dropout_prob=0.3)
        h2 = layers.fc(h, 8, act="relu")
        loss = layers.mean(layers.square_error_cost(layers.fc(h2, 1), y))
        opt = {"adam": lambda: fluid.optimizer.Adam(lr),
               "sgd": lambda: fluid.optimizer.SGD(lr),
               "momentum": lambda: fluid.optimizer.Momentum(lr, 0.9),
               }[optimizer]()
        opt.minimize(loss)
    return main, startup, loss


def _feeds(k, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal((batch, 4)).astype(np.float32),
             "y": rng.standard_normal((batch, 1)).astype(np.float32)}
            for _ in range(k)]


def _key_data(v):
    import jax
    if jax.dtypes.issubdtype(getattr(v, "dtype", None),
                             jax.dtypes.prng_key):
        return np.asarray(jax.random.key_data(v))
    return np.asarray(v)


def _scope_snapshot(scope):
    return {n: _key_data(v) for n, v in scope.items()}


def _assert_snapshots_equal(a, b):
    assert sorted(a) == sorted(b)
    for n in a:
        assert np.array_equal(a[n], b[n]), \
            f"scope var {n!r} diverged between pass configurations"


def _run_k_steps(main, startup, loss, feeds, spec, use_run_steps=False,
                 check_nan_inf=False):
    exe = fluid.Executor()
    scope = fluid.Scope()
    with _passes_flag(spec):
        with fluid.scope_guard(scope):
            exe.run(startup)
            if use_run_steps:
                out = exe.run_steps(main, feed=feeds, fetch_list=[loss],
                                    check_nan_inf=check_nan_inf)
                losses = np.asarray(out[0]).reshape(-1)
            else:
                losses = np.stack([
                    np.asarray(exe.run(main, feed=f, fetch_list=[loss],
                                       check_nan_inf=check_nan_inf)[0]
                               ).reshape(())
                    for f in feeds])
    return losses, _scope_snapshot(scope)


# ------------------------------------------------------------ registry

def test_unknown_pass_error_names_registry():
    try:
        get_pass("definitely_not_a_pass")
        raise AssertionError("expected UnknownPassError")
    except UnknownPassError as e:
        msg = str(e)
        assert "definitely_not_a_pass" in msg
        assert "dce" in msg and "cse" in msg and "fuse_optimizer" in msg
    assert isinstance(UnknownPassError("x"), KeyError)  # catchable as before
    try:
        passes.resolve_pipeline("dce,typo_pass")
        raise AssertionError("expected UnknownPassError")
    except UnknownPassError as e:
        assert "typo_pass" in str(e)


def test_registry_override():
    @passes.register_pass("_test_override")
    class A(Pass):
        def apply(self, program):
            program._touched = "A"

    @passes.register_pass("_test_override")
    class B(Pass):
        def apply(self, program):
            program._touched = "B"

    p = fluid.Program()
    get_pass("_test_override")(p)
    assert p._touched == "B"        # latest registration wins
    passes._PASSES.pop("_test_override", None)


def test_apply_passes_canonical_order_for_unordered_input():
    main, startup, loss = _build()
    # a SET of names must still run in the canonical order
    apply_passes(main.clone(), {"fuse_optimizer", "cse", "dce"},
                 fetch_names=(loss.name,))
    order = [r["pass"] for r in passes.stats()["passes"]]
    assert order == ["dce", "cse", "fuse_optimizer"], order


def test_resolve_pipeline_specs():
    assert passes.resolve_pipeline("0") == ()
    assert passes.resolve_pipeline("off") == ()
    assert passes.resolve_pipeline("1") == ("dce", "cse", "fuse_optimizer")
    # explicit lists canonicalize too
    assert passes.resolve_pipeline("cse,dce") == ("dce", "cse")
    assert passes.pipeline_signature("0") == ()
    assert passes.pipeline_signature("1") != passes.pipeline_signature(
        "dce,cse")


def test_stats_report_shape():
    main, startup, loss = _build()
    opt = passes.optimize_program(main, fetch_names=[loss.name])
    assert opt is not main          # pipeline on: a clone was optimized
    st = passes.stats()
    assert len(st["passes"]) == 3 and st["total_ms"] >= 0
    for row in st["passes"]:
        assert row["ops_before"] >= row["ops_after"] >= 0
        assert row["ms"] >= 0 and "detail" in row
    with _passes_flag("0"):
        assert passes.optimize_program(main, fetch_names=[loss.name]) \
            is main                 # off: the very same object


# ------------------------------------------------------------ DCE / CSE

def test_dce_drops_dead_branch_keeps_roots():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        y = layers.data("y", [-1, 1], dtype="float32")
        h = layers.fc(x, 8, act="relu")
        loss = layers.mean(layers.square_error_cost(layers.fc(h, 1), y))
        # dead branch: computed but never fetched / never persisted
        dead = layers.reduce_sum(layers.exp(h))
        # side-effect op over the dead branch: must survive DCE
        layers.Print(dead, message="dce-keep")
        # persistable write: must survive DCE
        snap = layers.create_global_var([1], 0.0, "float32",
                                        persistable=True,
                                        name="dce_snapshot")
        layers.assign(loss, output=snap)
        fluid.optimizer.SGD(0.05).minimize(loss)
        # a second dead chain with NO side effect: must be removed
        dead2 = layers.sigmoid(layers.scale(h, scale=4.0))

    opt = passes.optimize_program(main, fetch_names=[loss.name])
    types = [op.type for op in opt.global_block().ops]
    n_before = len(main.global_block().ops)
    assert len(types) < n_before
    assert "print" in types                       # side effect kept
    assert "sigmoid" not in types                 # dead chain removed
    # the persistable write survives: run and check the scope value
    exe = fluid.Executor()
    scope = fluid.Scope()
    feeds = _feeds(1, seed=3)[0]
    with fluid.scope_guard(scope):
        exe.run(startup)
        out, = exe.run(main, feed=feeds, fetch_list=[loss])
        assert np.array_equal(
            np.asarray(scope.find_var("dce_snapshot")).reshape(-1),
            np.asarray(out).reshape(-1))
    del dead2


def test_dce_keeps_fetched_intermediate():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        h = layers.fc(x, 8)
        extra = layers.reduce_mean(h)     # read by nothing downstream
        out = layers.reduce_sum(h)
    opt = passes.optimize_program(main, fetch_names=[out.name, extra.name])
    types = [op.type for op in opt.global_block().ops]
    assert "reduce_mean" in types
    opt2 = passes.optimize_program(main, fetch_names=[out.name])
    assert "reduce_mean" not in [op.type for op in
                                 opt2.global_block().ops]


def test_cse_merges_duplicate_pure_ops_not_rng():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        # identical pure subexpressions -> one survives
        a = layers.scale(x, scale=2.5)
        b = layers.scale(x, scale=2.5)
        # identical RNG consumers -> must NOT merge (distinct streams)
        d1 = layers.dropout(x, dropout_prob=0.5)
        d2 = layers.dropout(x, dropout_prob=0.5)
        out = layers.reduce_sum(a + b + d1 + d2)
    opt = passes.optimize_program(main, fetch_names=[out.name],
                                  spec="cse")
    types = [op.type for op in opt.global_block().ops]
    assert types.count("scale") == 1, types
    assert types.count("dropout") == 2, types
    # merged program computes the same value (dropout off via seed: just
    # check the deterministic part by running both programs seeded)
    exe = fluid.Executor()
    feed = _feeds(1, seed=5)[0]
    vals = []
    for spec in ("0", "cse"):
        scope = fluid.Scope()
        with _passes_flag(spec):
            with fluid.scope_guard(scope):
                exe.run(startup)
                vals.append(np.asarray(
                    exe.run(main, feed={"x": feed["x"]},
                            fetch_list=[out])[0]))
    assert np.array_equal(vals[0], vals[1])


def test_cse_respects_rebinding():
    """An op identical to an earlier one must NOT merge when an input
    name was rebound in between (the value changed)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        a = layers.scale(x, scale=3.0)
        # rebind a's name through an assign writing the SAME var
        layers.assign(layers.scale(x, scale=5.0), output=a)
        b = layers.scale(a, scale=1.0)
        out = layers.reduce_sum(b)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 4), np.float32)}
    vals = []
    for spec in ("0", "cse"):
        scope = fluid.Scope()
        with _passes_flag(spec):
            with fluid.scope_guard(scope):
                exe.run(startup)
                vals.append(np.asarray(exe.run(main, feed=feed,
                                               fetch_list=[out])[0]))
    assert np.array_equal(vals[0], vals[1])


# ------------------------------------------- fusion + bitwise parity

def test_fused_optimizer_op_emitted():
    main, startup, loss = _build("adam")
    opt = passes.optimize_program(main, fetch_names=[loss.name])
    types = [op.type for op in opt.global_block().ops]
    assert "fused_adam" in types
    assert "adam" not in types       # all 6 params landed in the bucket
    fused = next(op for op in opt.global_block().ops
                 if op.type == "fused_adam")
    assert len(fused.inputs["Param"]) == 6
    assert fused.inputs["Param"] == fused.outputs["ParamOut"]
    report = next(r for r in passes.stats()["passes"]
                  if r["pass"] == "fuse_optimizer")
    assert report["detail"]["fused_buckets"] == 1
    assert report["detail"]["fused_params"] == 6


def test_bucket_byte_cap_splits_buckets():
    main, startup, loss = _build("adam")
    p = get_pass("fuse_optimizer", fetch_names=(loss.name,),
                 max_bucket_bytes=128)      # tiny cap: many buckets
    prog = main.clone()
    p(prog)
    fused = [op for op in prog.global_block().ops
             if op.type == "fused_adam"]
    singles = [op for op in prog.global_block().ops if op.type == "adam"]
    assert len(fused) >= 2 or (len(fused) >= 1 and singles)
    total = sum(len(op.inputs["Param"]) for op in fused) + len(singles)
    assert total == 6                # nothing lost, nothing duplicated


@pytest.mark.slow
def test_fused_optimizer_bitwise_parity_all_types():
    """Acceptance gate: fused updates match per-param updates BITWISE —
    params and fetched losses over K=8 steps, guard off and on."""
    for optimizer in ("adam", "sgd", "momentum"):
        feeds = _feeds(8, seed=11)
        main, startup, loss = _build(optimizer, with_dropout=True)
        for guard in (False, True):
            l0, s0 = _run_k_steps(main, startup, loss, feeds, "0",
                                  check_nan_inf=guard)
            l1, s1 = _run_k_steps(main, startup, loss, feeds, "1",
                                  check_nan_inf=guard)
            assert np.array_equal(l0, l1), \
                f"{optimizer} losses diverged (guard={guard})"
            _assert_snapshots_equal(s0, s1)


@pytest.mark.slow
def test_flag_zero_reproduces_unoptimized_lowering():
    """FLAGS_program_passes=0 must restore today's behavior bitwise —
    including the RNG stream (dropout on)."""
    feeds = _feeds(8, seed=23)
    main, startup, loss = _build("adam", with_dropout=True)
    l_off, s_off = _run_k_steps(main, startup, loss, feeds, "0")
    l_on, s_on = _run_k_steps(main, startup, loss, feeds, "1")
    l_off2, s_off2 = _run_k_steps(main, startup, loss, feeds, "0")
    assert np.array_equal(l_off, l_off2)      # off-path deterministic
    _assert_snapshots_equal(s_off, s_off2)
    assert np.array_equal(l_off, l_on)        # pipeline value-preserving
    _assert_snapshots_equal(s_off, s_on)


@pytest.mark.slow
def test_run_steps_composes_with_passes():
    """The pipeline must compose with the fused K-step scan lowering:
    run_steps with passes on == sequential run() with passes off,
    bitwise, guard on and off."""
    feeds = _feeds(8, seed=31)
    main, startup, loss = _build("adam", with_dropout=True)
    for guard in (False, True):
        l_seq, s_seq = _run_k_steps(main, startup, loss, feeds, "0",
                                    check_nan_inf=guard)
        l_fused, s_fused = _run_k_steps(main, startup, loss, feeds, "1",
                                        use_run_steps=True,
                                        check_nan_inf=guard)
        assert np.array_equal(l_seq, np.asarray(l_fused).reshape(-1))
        _assert_snapshots_equal(s_seq, s_fused)


def test_adamw_fused_parity():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        y = layers.data("y", [-1, 1], dtype="float32")
        h = layers.fc(x, 16, act="relu")
        loss = layers.mean(layers.square_error_cost(layers.fc(h, 1), y))
        fluid.optimizer.AdamW(0.01, weight_decay=0.02).minimize(loss)
    opt = passes.optimize_program(main, fetch_names=[loss.name])
    assert any(op.type == "fused_adamw"
               for op in opt.global_block().ops)
    feeds = _feeds(8, seed=41)
    l0, s0 = _run_k_steps(main, startup, loss, feeds, "0")
    l1, s1 = _run_k_steps(main, startup, loss, feeds, "1")
    assert np.array_equal(l0, l1)
    # the fused op updates the bucket as ONE flat vector, and XLA:CPU
    # picks its vector loop (and where it contracts the decay multiply
    # into an FMA) by that vector's length, not each parameter's: the
    # decayed update lands within a few ulp (of the tensor's largest
    # entry: every entry moves by an update of one scale) of the
    # per-parameter op, not on it. What is not a float stays exact.
    assert sorted(s0) == sorted(s1)
    for n in s0:
        if np.issubdtype(s0[n].dtype, np.floating):
            np.testing.assert_allclose(
                s1[n], s0[n], rtol=0, err_msg=n,
                atol=4 * np.spacing(np.max(np.abs(s0[n]))))
        else:
            assert np.array_equal(s0[n], s1[n]), n


def test_sparse_grad_stays_unfused():
    """SelectedRows embedding grads must keep the sparse per-param
    update path (fusing would densify)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = layers.data("ids", [-1, 1], dtype="int64")
        y = layers.data("y", [-1, 1], dtype="float32")
        emb = layers.embedding(ids, size=[50, 8], is_sparse=True)
        emb = layers.reshape(emb, [-1, 8])
        loss = layers.mean(layers.square_error_cost(
            layers.fc(emb, 1), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    opt = passes.optimize_program(main, fetch_names=[loss.name])
    for op in opt.global_block().ops:
        if op.type == "fused_sgd":
            emb_params = [p for p in op.inputs["Param"]
                          if "emb" in p.lower()]
            assert not emb_params, \
                f"sparse-grad param fused: {emb_params}"


def test_side_effect_classification_covers_grad_ops():
    """Grad ops of side-effecting ops carry the effect themselves
    (distributed_lookup_table_grad pushes sparse grads to the pserver):
    DCE must treat them as roots even though their only local output is
    a dead stub grad."""
    from paddle_tpu.framework.passes import _is_side_effect_type
    assert _is_side_effect_type("distributed_lookup_table")
    assert _is_side_effect_type("distributed_lookup_table_grad")
    assert _is_side_effect_type("py_func_grad")
    assert _is_side_effect_type("c_allgather")
    assert not _is_side_effect_type("scale")
    assert not _is_side_effect_type("scale_grad")


# ------------------------------------------------- cache + telemetry

def test_cache_key_includes_pass_config():
    """Toggling FLAGS_program_passes between runs must MISS the compile
    cache, never replay a stale executable built under another config."""
    main, startup, loss = _build("adam")
    exe = fluid.Executor()
    scope = fluid.Scope()
    feed = _feeds(1, seed=51)[0]
    with fluid.scope_guard(scope):
        with _passes_flag("1"):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            misses_on = exe.cache_stats()["misses"]
        with _passes_flag("0"):
            exe.run(main, feed=feed, fetch_list=[loss])
            st = exe.cache_stats()
            assert st["misses"] > misses_on     # new config recompiled
        with _passes_flag("1"):
            exe.run(main, feed=feed, fetch_list=[loss])
            st2 = exe.cache_stats()
            assert st2["hits"] > st["hits"]     # old config still cached


def test_reregistered_pass_invalidates_compile_cache():
    """register_pass is documented as the override extension point: a
    re-registered pass must change pipeline_signature so cached
    executables compiled under the old implementation never replay."""
    from paddle_tpu.framework.passes import (_PASSES, register_pass,
                                             DeadCodeEliminationPass)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        out = layers.scale(x, scale=2.0)
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 4), np.float32)}
    old_sig = passes.pipeline_signature()
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            v1, = exe.run(main, feed=feed, fetch_list=[out])

            @register_pass("dce")
            class ScaleTripler(Pass):
                pipeline_order = 10

                def apply(self, program):
                    for op in program.global_block().ops:
                        if op.type == "scale":
                            op.attrs["scale"] = 3.0

            assert passes.pipeline_signature() != old_sig
            v2, = exe.run(main, feed=feed, fetch_list=[out])
        assert np.allclose(np.asarray(v1), 2.0)
        assert np.allclose(np.asarray(v2), 3.0), \
            "override served a stale executable"
    finally:
        register_pass("dce")(DeadCodeEliminationPass)
        assert _PASSES["dce"] is DeadCodeEliminationPass


def test_cache_stats_trace_compile_split():
    main, startup, loss = _build("adam")
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feeds(1)[0], fetch_list=[loss])
    st = exe.cache_stats()
    assert st["compiles"] >= 2                  # startup + main
    assert st["trace_ms"] > 0 and st["compile_ms"] > 0
    assert st["pass_ms"] >= 0


def test_pass_profiler_events():
    from paddle_tpu import profiler
    main, startup, loss = _build("adam")
    exe = fluid.Executor()
    profiler.reset_profiler()
    profiler.start_profiler("All")
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed=_feeds(1)[0], fetch_list=[loss])
    finally:
        rows = profiler.stop_profiler(profile_path=None)
        profiler.reset_profiler()
    names = {r[0] for r in rows}
    assert any(n.startswith("pass/program_") for n in names), names
    assert any(n.startswith("trace/program_") for n in names), names
    assert any(n.startswith("compile/program_") for n in names), names


@pytest.mark.slow
def test_bench_passes_smoke():
    """bench.py --config passes: the A/B (passes on/off) record reports
    lowered-op-count and trace+compile reductions on a BERT-shaped
    program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--config",
         "passes"], capture_output=True, text=True, timeout=600,
        env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    on, off = rec["passes_on"], rec["passes_off"]
    assert on["lowered_op_count"] < off["lowered_op_count"]
    assert on["fused_buckets"] >= 1
    for side in (on, off):
        assert side["trace_ms"] > 0 and side["compile_ms"] > 0
        assert side["cold_start_ms"] > 0
    # FLAGS_verify_passes overhead: per-pass translation validation must
    # stay a small fraction of the pipeline itself (acceptance < 20% on
    # the tiny-BERT config; generous slack here for CI timing noise)
    assert rec["verify_ms"] > 0
    assert rec["verify_pct_of_pass_ms"] < 35.0, rec
