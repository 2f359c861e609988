"""Program IR verifier + dataflow analysis (framework/analysis.py):
def-use/liveness units, one seeded mutation per verifier diagnostic
(each asserting the exact ProgramVerifyError code and producing-pass
provenance), per-pass translation validation through optimize_program,
verifier-clean assertions over the bench program zoo, the degenerate
empty-program edges, and the lint_program.py CLI."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.framework import analysis, passes
from paddle_tpu.framework.analysis import (ProgramVerifyError,
                                           collect_diagnostics,
                                           verify_program)
from paddle_tpu.framework.passes import Pass, register_pass

from test_program_passes import _build, _feeds, _passes_flag

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _verify_flag:
    def __init__(self, on):
        self.on = on

    def __enter__(self):
        self.old = fluid.get_flags("FLAGS_verify_passes")[
            "FLAGS_verify_passes"]
        fluid.set_flags({"FLAGS_verify_passes": self.on})

    def __exit__(self, *a):
        fluid.set_flags({"FLAGS_verify_passes": self.old})


def _codes(diags):
    return [d.code for d in diags]


# --------------------------------------------------------- analysis units

def test_def_use_chains_track_binding_versions():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        a = layers.scale(x, scale=2.0)                    # a@1
        layers.assign(layers.scale(x, scale=5.0), output=a)   # a@2
        out = layers.reduce_sum(a)                        # reads a@2
    du = analysis.block_def_use(main)
    assert du.def_count[a.name] == 2
    assert du.last_version(a.name) == 2
    # the final reader consumes version 2, nobody reads version 1
    readers_v2 = du.readers_of(a.name, 2)
    assert len(readers_v2) == 1
    assert main.global_block().ops[readers_v2[0]].type == "reduce_sum"
    assert du.readers_of(a.name, 1) == []
    # defs map (name, version) -> defining op index
    assert main.global_block().ops[du.defs[(a.name, 1)]].type == "scale"
    assert main.global_block().ops[du.defs[(a.name, 2)]].type == "assign"
    del out


def test_live_op_ids_matches_dce_roots():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        h = layers.fc(x, 8)
        out = layers.reduce_sum(h)
        dead = layers.sigmoid(layers.scale(h, scale=4.0))
        layers.Print(out, message="root")
    live = analysis.live_op_ids(main, [out.name])
    ops = main.global_block().ops
    live_types = [op.type for op in ops if id(op) in live]
    assert "print" in live_types and "reduce_sum" in live_types
    assert "sigmoid" not in live_types
    del dead


def test_op_writes_is_sub_block_aware():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 3)
        acc = layers.fill_constant([1], "float32", 0.0)
        cond_v = layers.less_than(i, n)
        w = layers.While(cond_v)
        with w.block():
            layers.assign(layers.scale(acc, scale=2.0), acc)
            layers.increment(i, value=1)
            layers.less_than(i, n, cond=cond_v)
    while_op = next(op for op in main.global_block().ops
                    if analysis.has_sub_block(op))
    writes = analysis.op_writes(main, while_op)
    assert acc.name in writes and i.name in writes
    reads = analysis.op_reads(main, while_op)
    assert acc.name in reads


def test_passes_consume_shared_classifier():
    # the ad-hoc copies in passes.py are gone: same objects
    assert passes.SIDE_EFFECT_OPS is analysis.SIDE_EFFECT_OPS
    assert passes._is_side_effect_type is analysis.is_side_effect_type
    assert passes._needs_rng is analysis.needs_rng
    assert analysis.is_side_effect_type("distributed_lookup_table_grad")
    assert analysis.is_side_effect_type("c_allgather")
    assert not analysis.is_side_effect_type("scale_grad")


# ---------------------------------- well-formedness checker mutations
# (one seeded broken program per diagnostic, exact code asserted)

def _simple_chain():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 4], dtype="float32")
        a = layers.scale(x, scale=2.0)
        b = layers.scale(a, scale=3.0)
        out = layers.reduce_sum(b)
    return main, startup, x, a, b, out


def test_checker_unknown_op():
    main, _, _, _, _, out = _simple_chain()
    main.global_block().ops[1].type = "definitely_not_an_op"
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(main, fetch_names=[out.name])
    assert ei.value.code == "unknown-op"
    assert ei.value.op_index == 1
    assert "definitely_not_an_op" in str(ei.value)


def test_checker_missing_rng_seed():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 4], dtype="float32")
        d = layers.dropout(x, dropout_prob=0.5)
        out = layers.reduce_sum(d)
    drop = next(op for op in main.global_block().ops
                if op.type == "dropout")
    del drop.attrs["__rng_seed__"]
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(main, fetch_names=[out.name])
    assert ei.value.code == "missing-rng-seed"
    assert ei.value.op_type == "dropout"


def test_checker_dangling_read():
    main, _, _, _, _, out = _simple_chain()
    op = main.global_block().ops[2]
    op.inputs["X"] = ["__ghost__"]
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(main, fetch_names=[out.name])
    assert ei.value.code == "dangling-read"
    assert ei.value.var == "__ghost__"


def test_checker_use_before_def():
    main, _, _, _, _, out = _simple_chain()
    ops = main.global_block().ops
    ops[1], ops[2] = ops[2], ops[1]     # reader now precedes producer
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(main, fetch_names=[out.name])
    assert ei.value.code == "use-before-def"


def test_checker_duplicate_output():
    main, _, _, a, _, out = _simple_chain()
    op = main.global_block().ops[1]
    op.outputs["Out"] = [a.name, a.name]
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(main, fetch_names=[out.name])
    assert ei.value.code == "duplicate-output"
    assert ei.value.var == a.name


def test_checker_dead_persistable_write():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 4], dtype="float32")
        snap = layers.create_global_var([1], 0.0, "float32",
                                        persistable=True, name="dw_snap")
        layers.assign(layers.reduce_sum(x), output=snap)       # dead
        layers.assign(layers.reduce_mean(x), output=snap)      # final
    diags = collect_diagnostics(main, fetch_names=["dw_snap"],
                                pedantic=True)
    assert "dead-persistable-write" in _codes(diags)
    d = next(d for d in diags if d.code == "dead-persistable-write")
    assert d.var == "dw_snap"
    # the pedantic tier is opt-in: user programs legally double-init
    # shared params, so the default collect stays quiet
    assert collect_diagnostics(main, fetch_names=["dw_snap"]) == []
    # a read between the writes makes the first write live again
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        x = layers.data("x", [4, 4], dtype="float32")
        snap = layers.create_global_var([1], 0.0, "float32",
                                        persistable=True, name="dw_snap2")
        layers.assign(layers.reduce_sum(x), output=snap)
        y = layers.scale(snap, scale=2.0)                      # read
        layers.assign(layers.reduce_mean(x), output=snap)
    assert collect_diagnostics(main2, fetch_names=[y.name],
                               pedantic=True) == []


def test_checker_sub_block_scope():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 3)
        cond_v = layers.less_than(i, n)
        w = layers.While(cond_v)
        with w.block():
            layers.increment(i, value=1)
            layers.less_than(i, n, cond=cond_v)
    # (a) a sub-block op reads a name invisible in its frame chain
    bad = main.clone()
    sub_idx = next(op.attrs["sub_block"]
                   for op in bad.global_block().ops
                   if analysis.has_sub_block(op))
    sop = bad.blocks[sub_idx].ops[0]
    sop.inputs[list(sop.inputs)[0]] = ["__nowhere__"]
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(bad, fetch_names=[i.name])
    assert ei.value.code == "sub-block-scope"
    # (b) a sub_block attr pointing at a missing block
    bad2 = main.clone()
    wop = next(op for op in bad2.global_block().ops
               if analysis.has_sub_block(op))
    wop.attrs["sub_block"] = 99
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(bad2, fetch_names=[i.name])
    assert ei.value.code == "sub-block-scope"


def test_checker_unreachable_fetch():
    main, _, _, _, _, out = _simple_chain()
    with pytest.raises(ProgramVerifyError) as ei:
        verify_program(main, fetch_names=[out.name, "__no_such_var__"])
    assert ei.value.code == "unreachable-fetch"
    assert ei.value.var == "__no_such_var__"
    # scope_names can supply it (PTQ-style scope fetch)
    verify_program(main, fetch_names=[out.name, "__no_such_var__"],
                   scope_names={"__no_such_var__"})


def test_checker_shape_and_dtype_mismatch():
    main, _, _, a, _, out = _simple_chain()
    assert collect_diagnostics(main, fetch_names=[out.name],
                               check_shapes=True) == []
    av = main.global_block().var(a.name)
    av.shape = (3, 7)
    diags = collect_diagnostics(main, fetch_names=[out.name],
                                check_shapes=True)
    assert "shape-mismatch" in _codes(diags)
    av.shape = (4, 4)
    av.dtype = "float64"
    diags = collect_diagnostics(main, fetch_names=[out.name],
                                check_shapes=True)
    assert "dtype-mismatch" in _codes(diags)


# ------------------------------- per-pass translation validation
# (a deliberately-buggy pass per preservation invariant; the error must
# name the pass and carry the diagnostic code)

def _run_mutant(pass_name, program, fetch_names):
    with _verify_flag(True):
        with pytest.raises(ProgramVerifyError) as ei:
            passes.optimize_program(program, fetch_names=fetch_names,
                                    spec=pass_name)
    assert ei.value.pass_name == pass_name, ei.value
    passes._PASSES.pop(pass_name, None)
    return ei.value


def test_mutant_dce_drops_side_effect_op():
    @register_pass("_mut_dce_print")
    class BadDce(Pass):
        def apply(self, program):
            blk = program.global_block()
            blk.ops = [op for op in blk.ops if op.type != "print"]

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        out = layers.reduce_sum(layers.fc(x, 8))
        layers.Print(out, message="must-survive")
    err = _run_mutant("_mut_dce_print", main, [out.name])
    assert err.code == "side-effect-dropped"
    assert err.op_type == "print"


def test_mutant_cse_merges_rng_ops():
    @register_pass("_mut_cse_rng")
    class BadCse(Pass):
        def apply(self, program):
            blk = program.global_block()
            drops = [op for op in blk.ops if op.type == "dropout"]
            keep, merge = drops[0], drops[1]
            rename = dict(zip(merge.output_arg_names,
                              keep.output_arg_names))
            blk.ops.remove(merge)
            for op in blk.ops:
                for slot, names in op.inputs.items():
                    op.inputs[slot] = [rename.get(n, n) for n in names]

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        d1 = layers.dropout(x, dropout_prob=0.5)
        d2 = layers.dropout(x, dropout_prob=0.5)
        out = layers.reduce_sum(d1 + d2)
    err = _run_mutant("_mut_cse_rng", main, [out.name])
    assert err.code == "rng-stream-dropped"
    assert err.op_type == "dropout"


def test_mutant_drops_optimizer_update():
    @register_pass("_mut_drop_sgd")
    class BadFuse(Pass):
        def apply(self, program):
            blk = program.global_block()
            idx = next(i for i, op in enumerate(blk.ops)
                       if op.type == "sgd")
            del blk.ops[idx]

    main, startup, loss = _build("sgd")
    err = _run_mutant("_mut_drop_sgd", main, [loss.name])
    assert err.code == "persistable-write-dropped"


def test_mutant_drops_one_of_two_persistable_writes():
    """persist_writes is a multiset: dropping ONE of two live writes to
    the same persistable var must not hide behind the survivor."""
    @register_pass("_mut_drop_one")
    class BadDropOne(Pass):
        def apply(self, program):
            blk = program.global_block()
            idx = next(i for i, op in enumerate(blk.ops)
                       if op.type == "assign"
                       and "dw2_snap" in op.output_arg_names)
            del blk.ops[idx]

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 4], dtype="float32")
        snap = layers.create_global_var([1], 0.0, "float32",
                                        persistable=True,
                                        name="dw2_snap")
        layers.assign(layers.reduce_sum(x), output=snap)
        y = layers.scale(snap, scale=2.0)          # read between writes
        layers.assign(layers.elementwise_add(layers.reduce_mean(x), y),
                      output=snap)
    err = _run_mutant("_mut_drop_one", main, [y.name])
    assert err.code == "persistable-write-dropped"
    assert err.var == "dw2_snap"


def test_mutant_fusion_reorders_past_sub_block_reader():
    @register_pass("_mut_reorder")
    class BadReorder(Pass):
        def apply(self, program):
            blk = program.global_block()
            idx = next(i for i, op in enumerate(blk.ops)
                       if op.type == "assign"
                       and "rp_param" in op.output_arg_names)
            blk.ops.append(blk.ops.pop(idx))   # move write past the loop

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        p = layers.create_global_var([1], 1.0, "float32",
                                     persistable=True, name="rp_param")
        layers.assign(layers.fill_constant([1], "float32", 0.5),
                      output=p)
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 3)
        acc = layers.fill_constant([1], "float32", 0.0)
        cond_v = layers.less_than(i, n)
        w = layers.While(cond_v)
        with w.block():
            layers.assign(layers.elementwise_add(acc, p), acc)
            layers.increment(i, value=1)
            layers.less_than(i, n, cond=cond_v)
    err = _run_mutant("_mut_reorder", main, [acc.name])
    assert err.code == "reordered-past-observer"
    assert err.var == "rp_param"


def test_mutant_introduces_dangling_read():
    @register_pass("_mut_dangle")
    class BadRename(Pass):
        def apply(self, program):
            op = program.global_block().ops[-1]
            slot = list(op.inputs)[0]
            op.inputs[slot] = ["__invented_by_pass__"]

    main, _, _, _, _, out = _simple_chain()
    err = _run_mutant("_mut_dangle", main, [out.name])
    assert err.code == "dangling-read"
    assert err.var == "__invented_by_pass__"


def test_preexisting_findings_not_blamed_on_passes():
    """Translation validation diffs against the pipeline INPUT: a user
    program that already carries a diagnostic must flow through the
    default pipeline unflagged (the executor's own verify, which has the
    scope, owns user-program errors)."""
    main, _, _, _, _, out = _simple_chain()
    # seed a pre-existing dangling read the passes don't touch
    op = main.global_block().ops[1]
    op.inputs.setdefault("__extra__", ["__preexisting_ghost__"])
    with _verify_flag(True):
        opt = passes.optimize_program(main, fetch_names=[out.name])
    assert opt is not main             # pipeline ran, nothing raised


def test_correct_pipeline_validates_clean_with_stats():
    main, startup, loss = _build("adam", with_dropout=True)
    with _verify_flag(True):
        opt = passes.optimize_program(main, fetch_names=[loss.name])
    st = passes.stats()
    assert st["verify_ms"] > 0
    assert all("verify_ms" in row for row in st["passes"])
    assert collect_diagnostics(opt, fetch_names=[loss.name]) == []
    with _verify_flag(False):
        passes.optimize_program(main, fetch_names=[loss.name])
    assert passes.stats()["verify_ms"] == 0.0


# ------------------------------------------------ executor + io wiring

def test_executor_raises_typed_error_not_keyerror():
    """A program reading a var that is neither produced, fed, nor in the
    scope fails as ProgramVerifyError BEFORE lowering (the old behavior
    was a KeyError from the middle of the trace)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 4], dtype="float32")
        ghost = main.global_block().create_var(
            name="vr_ghost", shape=[4, 4], dtype="float32")
        y = layers.elementwise_add(x, ghost)
    exe = fluid.Executor()
    with _verify_flag(True):
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            with pytest.raises(ProgramVerifyError) as ei:
                exe.run(main, feed={"x": np.ones((4, 4), np.float32)},
                        fetch_list=[y])
    assert ei.value.code == "dangling-read"
    assert ei.value.var == "vr_ghost"
    assert exe.cache_stats()["verify_ms"] > 0


def test_executor_verify_not_stale_across_scopes():
    """The user-program verification runs on every executable-cache
    miss: a clean verdict under one (feed shape, scope) must not be
    memoized past a later call whose scope lacks the state var."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        ghost = main.global_block().create_var(
            name="vr_state2", shape=[-1, 4], dtype="float32")
        y = layers.elementwise_add(x, ghost)
    exe = fluid.Executor()
    good = fluid.Scope()
    with _verify_flag(True):
        with fluid.scope_guard(good):
            exe.run(startup)
            good.set("vr_state2", np.ones((2, 4), np.float32))
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y])
        bad = fluid.Scope()
        with fluid.scope_guard(bad):
            exe.run(startup)
            with pytest.raises(ProgramVerifyError) as ei:
                # different feed SHAPE -> executable-cache miss -> the
                # verifier must re-run against THIS scope
                exe.run(main, feed={"x": np.ones((3, 4), np.float32)},
                        fetch_list=[y])
    assert ei.value.code == "dangling-read"
    assert ei.value.var == "vr_state2"


def test_executor_scope_supplies_state_reads():
    """The same read verifies clean when the scope actually holds the
    var (run-to-run state), flag on or off — the verifier must consult
    the live scope, not just the IR."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 4], dtype="float32")
        ghost = main.global_block().create_var(
            name="vr_state", shape=[4, 4], dtype="float32")
        y = layers.elementwise_add(x, ghost)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with _verify_flag(True):
        with fluid.scope_guard(scope):
            exe.run(startup)
            scope.set("vr_state", np.full((4, 4), 2.0, np.float32))
            out, = exe.run(main,
                           feed={"x": np.ones((4, 4), np.float32)},
                           fetch_list=[y])
    np.testing.assert_allclose(np.asarray(out), 3.0)


def test_load_inference_model_verifies_version_skew(tmp_path):
    """An op deleted from the registry after a model was saved fails the
    load with a named unknown-op diagnostic, not a mid-lowering
    NotImplementedError on the first Predictor.run."""
    from paddle_tpu.framework.registry import OPS
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 8], dtype="float32")
        out = layers.fc(x, 4, act="softmax")
    exe = fluid.Executor()
    d = str(tmp_path / "model")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [out], exe,
                                      main_program=main)
        # version skew: the softmax op vanishes from the registry
        saved = OPS.pop("softmax")
        try:
            with pytest.raises(ProgramVerifyError) as ei:
                fluid.io.load_inference_model(d, exe)
        finally:
            OPS["softmax"] = saved
        assert ei.value.code == "unknown-op"
        assert ei.value.op_type == "softmax"
        # registry restored: the same artifact loads clean
        prog, feeds, fetches = fluid.io.load_inference_model(d, exe)
        assert feeds == ["x"]


# -------------------------------------------------- verifier-clean zoo

def test_zoo_programs_verify_clean():
    """The bench program zoo — tiny-BERT pretrain, widedeep CTR, GPT
    prefill/decode — is verifier-clean before AND after the default
    pipeline (the acceptance bar for checker false positives)."""
    from paddle_tpu.models import bert, gpt, widedeep

    zoo = []
    cfg = bert.BertConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = bert.bert_pretrain(cfg, 4, 32, 5)
        fluid.optimizer.AdamOptimizer(1e-4).minimize(out["loss"])
    zoo.append(("bert", main, [out["loss"].name]))
    zoo.append(("bert-startup", startup, []))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        wd = widedeep.wide_deep(batch_size=8)
    zoo.append(("widedeep", main, [wd["loss"].name]))

    gcfg = gpt.GPTConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        pre = gpt.gpt_prefill(gcfg, batch_size=2, seq_len=8)
    zoo.append(("gpt-prefill", main,
                [v.name for v in pre.values()
                 if hasattr(v, "name")][:1]))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        dec = gpt.gpt_decode_step_paged(gcfg, batch_size=2)
    zoo.append(("gpt-decode", main,
                [v.name for v in dec.values()
                 if hasattr(v, "name")][:1]))

    with _verify_flag(True):
        for name, prog, fetches in zoo:
            diags = collect_diagnostics(prog, fetch_names=fetches)
            assert diags == [], (name, diags)
            opt = passes.optimize_program(prog, fetch_names=fetches)
            diags = collect_diagnostics(opt, fetch_names=fetches)
            assert diags == [], (name, "post-pipeline", diags)


# ------------------------------------- degenerate / empty-program edges

def test_empty_program_with_persistable_fetch():
    """The op-free program + persistable-aliasing fetch edge: DCE root
    collection, the verifier, and a full executor run must all handle
    it (fetch rides scope state)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        layers.create_global_var([1], 7.0, "float32", persistable=True,
                                 name="deg_snap")
    assert main.global_block().ops == []
    with _verify_flag(True):
        opt = passes.optimize_program(main, fetch_names=["deg_snap"])
        assert [op.type for op in opt.global_block().ops] == []
        verify_program(main, fetch_names=["deg_snap"])
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            out, = exe.run(main, feed={}, fetch_list=["deg_snap"])
    assert float(np.asarray(out).reshape(())) == 7.0


def test_all_ops_dead_program_runs():
    """A program whose every op is dead (nothing fetched from it) plus a
    persistable fetch: DCE empties the block and the run still serves
    the fetch from scope state."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 4], dtype="float32")
        layers.scale(x, scale=2.0)                     # dead
        layers.create_global_var([1], 3.0, "float32", persistable=True,
                                 name="deg_live")
    with _verify_flag(True):
        opt = passes.optimize_program(main, fetch_names=["deg_live"])
        assert opt.global_block().ops == []
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            out, = exe.run(main, feed={"x": np.ones((4, 4), np.float32)},
                           fetch_list=["deg_live"])
    assert float(np.asarray(out).reshape(())) == 3.0


def test_string_fetch_names_not_char_split():
    """A bare-string fetch name must mean ONE target: tuple('loss')
    used to char-split into nonsense DCE roots that dropped the whole
    program."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 4], dtype="float32")
        out = layers.reduce_sum(layers.scale(x, scale=2.0))
    opt = passes.optimize_program(main, fetch_names=out.name)
    types = [op.type for op in opt.global_block().ops]
    assert "scale" in types and "reduce_sum" in types, types
    # and straight through apply_passes/DCE attrs too
    prog2 = main.clone()
    passes.apply_passes(prog2, ["dce"], fetch_names=out.name)
    types2 = [op.type for op in prog2.global_block().ops]
    assert "scale" in types2 and "reduce_sum" in types2, types2


def test_cyclic_sub_block_reports_instead_of_recursing():
    """A hand-edited artifact whose sub_block attr points back at its
    own (or an ancestor) block must produce the sub-block-scope
    diagnostic, not a RecursionError — exactly the corrupted-model case
    load_inference_model and lint_program exist to diagnose."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 3)
        cond_v = layers.less_than(i, n)
        w = layers.While(cond_v)
        with w.block():
            layers.increment(i, value=1)
            layers.less_than(i, n, cond=cond_v)
    wop = next(op for op in main.global_block().ops
               if analysis.has_sub_block(op))
    wop.attrs["sub_block"] = 0          # self-cycle
    diags = collect_diagnostics(main, fetch_names=[i.name])
    assert "sub-block-scope" in _codes(diags), diags
    # the sub-block-aware helpers survive the cycle too
    assert isinstance(analysis.op_writes(main, wop), set)
    assert isinstance(analysis.op_reads(main, wop), set)
    assert isinstance(analysis.live_op_ids(main, [i.name]), set)


# ----------------------------------------------------- lint_program CLI

def test_lint_program_cli(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [-1, 8], dtype="float32")
        out = layers.fc(x, 4)
    exe = fluid.Executor()
    d = str(tmp_path / "model")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [out], exe,
                                      main_program=main)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    clean = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_program.py"),
         d, "--shapes"], capture_output=True, text=True, env=env,
        timeout=300)
    assert clean.returncode == 0, clean.stdout + clean.stderr[-1000:]
    assert "OK" in clean.stdout

    # hand-edit the saved model: unknown op type + garbage fetch
    mp = os.path.join(d, "__model__")
    with open(mp) as f:
        model = json.load(f)
    model["program"]["blocks"][0]["ops"][0]["type"] = "bogus_op_v99"
    model["fetch_var_names"].append("__gone__")
    with open(mp, "w") as f:
        json.dump(model, f)
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_program.py"),
         d], capture_output=True, text=True, env=env, timeout=300)
    assert bad.returncode == 1, bad.stdout + bad.stderr[-1000:]
    assert "unknown-op" in bad.stdout and "bogus_op_v99" in bad.stdout
    assert "unreachable-fetch" in bad.stdout
