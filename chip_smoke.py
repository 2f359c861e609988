#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py                 # needs a TPU; exits 2 without one
    python chip_smoke.py kernels         # that phase alone (any of them)
    python chip_smoke.py --rehearse-cpu  # CPU dry run, never a pass

One process drives the main path once at the full width of GPT-base
(12 layers, hidden 768, 12 heads of 64, FFN 3,072, vocab 32,000, 2,048
positions), the one model this repository both trains and serves, with
seeded random weights:

  kernels  the Pallas kernels compiled (never interpreted) and compared
           with their in-file oracles, the paged pair (paged_kv_append,
           paged_attention_decode) over the pool's stored shape in its
           three dtypes; the decode kernel and one append timed alone
           at the shapes of the benchmark's serve cell, and the decode
           kernel at every serve cell's decode shape under a bank of
           free slots, of whole tables and of the cell's own mix (a
           table on stderr: microseconds a call and a walked step, the
           share of the bandwidth bound), the mix against the oracle
  train    gpt_pretrain at 8 x 2,048 + Adam + bf16 AMP through
           Executor.run and Executor.run_steps
  serve    GPTGenerator -> InferenceServer -> six
           concurrent Client.generate calls over the loopback socket,
           no pool-sized copy in the decode step's or an admission's
           optimised HLO (the admission: prefill, pick and scatter in
           one executable), then one logits check of paged decode against
           a full recompute
  prefill  GPT's prefill at benchmark configuration gpt2-medium's
           widths over a bf16 pool, at the largest and the smallest
           shape its one-token cell warms: what the executable holds
           (XLA's memory_analysis: arguments, outputs, temporaries),
           the rows it hands the scatter (bucket-long, the pool's
           dtype), and no pool-sized copy in that scatter
  loop     the looped block of benchmark configuration ouro-2.6b (its
           published widths, four passes, 2 of 48 layers) through
           InferenceServer: no pool-sized copy in its decode step (the
           append runs inside the loop of passes) or its admissions, and a
           logits check of prefill then paged decode against the
           family's plain float32 reference
  state    the hybrid block of benchmark configuration jamba2-3b (its
           published widths, one period of three layers with its
           attention layer, twice) through InferenceServer: no copy of a
           pool array or of the slot bank of recurrent states in its
           decode step or its admissions, and a logits check of prefill (the
           scan kernel) then paged decode against the family's plain
           float32 reference
  mesh     only with four or more devices: the train step under
           with_data_parallel over every chip, and tp=2 paged generation

Every phase prints one JSON line (platform, device kind and count, jax,
jaxlib and libtpu versions, compile seconds, persistent-cache hits, the
implementation each attention op resolved to). Every timing is a smoke
timing of one cold pass, not a benchmark number. The last line of stdout
is ``{"ok": true, "device": {...}}`` and the exit code is 0 only if every
phase passed.

It sets no JAX platform and falls back to nothing: where JAX finds no
TPU it names the platform it found on stderr, prints no result and exits
2. ``--rehearse-cpu`` is the exception made explicit: GPTConfig.tiny(),
kernels through the Pallas interpreter, every line marked a rehearsal.
"""
import argparse
import gc
import importlib.metadata
import json
import math
import os
import re
import sys
import threading
import time
import traceback

import numpy as np


class Sizes:
    """What one pass runs at. ``full`` is the contract; ``rehearsal`` is
    the same code at GPTConfig.tiny() for the CPU."""

    def __init__(self, rehearsal):
        from paddle_tpu.models import gpt
        self.rehearsal = rehearsal
        if rehearsal:
            self.cfg = gpt.GPTConfig.tiny()
            self.batch, self.seq = 4, 64
            self.flash = (4, 2, 64, 16)
            self.paged_rows, self.paged_positions = 2, 64
            self.paged_pos = (0, 37)
            self.paged_timed = dict(rows=4, heads=2, blocks=8, block=4,
                                    d_head=16, pos=(3, 20))
            self.paged_micro = (
                dict(name="f2", rows=4, hq=2, hkv=2, d=64, block=4, table=8,
                     mixes={"some": (3, 1, 5)}),
                dict(name="ring", rows=4, hq=4, hkv=2, d=128, block=4,
                     table=6, window=16, mixes={"some": (4, 5, 20)}))
            # (query heads, KV heads, head width, window, block, expert
            # width in, hidden, experts, experts a token, prefill length)
            self.gqa = dict(hq=4, hkv=2, d=8, window=8, block=4, hidden=32,
                            f=16, experts=8, top_k=2, seq=32, rows=4,
                            positions=40)
            self.prompt_lens = (3, 5, 9, 12, 17, 20)
            self.new_tokens = 4
            self.check_prompt, self.check_steps = 9, 2
            self.kernel_impl = "interpret"
            self.prefill_shapes = ((4, 64), (1, 16))
            self.loop = dict(layers=None, slots=2, prompt_lens=(5, 11, 18),
                             new_tokens=4)
            self.state = dict(layers=None, slots=2, prompt_lens=(5, 11, 18),
                              new_tokens=4)
        else:
            self.cfg = gpt.GPTConfig.base()
            self.batch, self.seq = 8, 2048
            self.flash = (8, 12, 2048, 64)
            self.paged_rows, self.paged_positions = 8, 2048
            # first slot, block edges either side, mid-cache, last slot
            self.paged_pos = (0, 15, 16, 100, 777, 1000, 1500, 2047)
            # the decode step of benchmark cell
            # gpt2-medium.serve_chat_closed32: 32 slots over 64 blocks of
            # 16, bf16 pool, contexts of 48 to 320 tokens
            self.paged_timed = dict(rows=32, heads=16, blocks=64, block=16,
                                    d_head=64, pos=(48, 320))
            # one decode call of every serve cell, as the cell's decode
            # step feeds it: mixes are (live rows, fewest, most blocks of
            # context a live row); the other rows are free slots
            self.paged_micro = (
                dict(name="gpt2-medium", rows=32, hq=16, hkv=16, d=64,
                     block=16, table=64,
                     mixes={"chat": (31, 2, 20), "long_decode": (8, 48, 64)}),
                dict(name="ouro", rows=16, hq=16, hkv=16, d=128, block=16,
                     table=20, mixes={"reason": (16, 2, 20)}),
                dict(name="mellum-full", rows=32, hq=32, hkv=4, d=128,
                     block=16, table=512, mixes={"code": (32, 64, 400)}),
                dict(name="mellum-window", rows=32, hq=32, hkv=4, d=128,
                     block=16, table=96, window=1024,
                     mixes={"code": (32, 64, 400)}))
            # the shapes of mellum2-12b-a2.5b: 32 query heads over 4 KV
            # heads of 128, window 1024, 64 experts of 896 top-8
            self.gqa = dict(hq=32, hkv=4, d=128, window=1024, block=16,
                            hidden=2304, f=896, experts=64, top_k=8,
                            seq=4096, rows=32, positions=6400)
            self.prompt_lens = (17, 100, 300, 700, 1100, 1500)
            self.new_tokens = 32
            self.check_prompt, self.check_steps = 100, 3
            self.kernel_impl = "pallas"
            # (rows, length bucket): the largest and the smallest prefill
            # of benchmark cell gpt2-medium.serve_one_token
            self.prefill_shapes = ((8, 1024), (1, 64))
            # benchmark configuration ouro-2.6b at its published widths
            # and four passes, 2 of its 48 layers: 8 cache layers
            self.loop = dict(layers=2, slots=4,
                             prompt_lens=(33, 70, 100, 128), new_tokens=24)
            # benchmark configuration jamba2-3b at its published widths:
            # two periods of three layers, an attention layer in each
            self.state = dict(layers=6, period=3, offset=1, slots=4,
                              prompt_lens=(33, 70, 300, 520), new_tokens=24)
        self.max_len = self.cfg.max_position


class Smoke:
    """Shared state of one pass: the device, the versions every line
    carries, the compile-cache handle, and the per-phase results."""

    def __init__(self, sizes, cache):
        import jax
        import jaxlib
        self.sizes = sizes
        self.cache = cache
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        try:
            libtpu = importlib.metadata.version("libtpu")
        except importlib.metadata.PackageNotFoundError:
            libtpu = None
        self.versions = {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "libtpu": libtpu}
        self.results = {}

    def run_phase(self, name, fn):
        from paddle_tpu.kernels import _dispatch
        cache = self.cache
        hits, misses, compile_s = (cache.hits, cache.misses,
                                   cache.compile_seconds)
        resolved = _dispatch.resolved_counts()
        t0 = time.perf_counter()
        detail, error = {}, None
        try:
            detail = fn(self) or {}
        except Exception as exc:  # noqa: BLE001 — a phase boundary
            traceback.print_exc(file=sys.stderr)
            error = f"{type(exc).__name__}: {exc}"[:2000]
        impl = {"/".join(k): n - resolved.get(k, 0)
                for k, n in sorted(_dispatch.resolved_counts().items())
                if n - resolved.get(k, 0)}
        if error:
            status = "fail"
        else:
            status = "rehearsed" if self.sizes.rehearsal else "pass"
        line = {
            "phase": name, "status": status,
            "rehearsal_not_a_chip_run": self.sizes.rehearsal,
            "platform": self.device["platform"],
            "device_kind": self.device["kind"],
            "device_count": self.device["count"],
            **self.versions,
            "compile_s": round(cache.compile_seconds - compile_s, 2),
            "persistent_cache_hits": cache.hits - hits,
            "persistent_cache_misses": cache.misses - misses,
            "attention_impl": impl,
            "smoke_wall_s": round(time.perf_counter() - t0, 2),
            **detail,
        }
        if error:
            line["error"] = error
        print(json.dumps(line), flush=True)
        self.results[name] = status
        gc.collect()


def _max_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)))


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ kernels

def phase_kernels(smoke):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.flags import flag
    from paddle_tpu.kernels.flash_attention import (_xla_attention,
                                                    flash_attention)
    from paddle_tpu.kernels.paged_attention import (
        _xla_paged_attention, paged_attention, paged_kv_append, pool_packing,
        quantize_kv, scales_to_stored, stored_shape, to_stored)
    from paddle_tpu.serving.kvpool import _DTYPES

    sz = smoke.sizes
    impl = sz.kernel_impl
    out = {"smoke_timings_s": {}, "max_abs_err": {}}

    # ---- flash: forward and backward, causal, bf16
    B, H, S, D = sz.flash
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(x, (B, H, S, D), jnp.bfloat16)
               for x in (kq, kk, kv))
    cot = jax.random.normal(kc, (B, H, S, D), jnp.float32)
    scale = float(D) ** -0.5

    def kern_loss(q, k, v, cot):
        o = flash_attention(q, k, v, causal=True, impl=impl)
        return jnp.sum(o.astype(jnp.float32) * cot)

    def ref_fwd(q, k, v):
        return _xla_attention(q, k, v, None, scale, True)

    def ref_loss(q, k, v, cot):
        return jnp.sum(ref_fwd(q, k, v) * cot)

    fwd = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl=impl)).lower(q, k, v).compile()
    bwd = jax.jit(jax.grad(kern_loss, argnums=(0, 1, 2))).lower(
        q, k, v, cot).compile()
    t0 = time.perf_counter()
    o = jax.block_until_ready(fwd(q, k, v))
    out["smoke_timings_s"]["flash_fwd"] = round(time.perf_counter() - t0, 4)
    t0 = time.perf_counter()
    grads = jax.block_until_ready(bwd(q, k, v, cot))
    out["smoke_timings_s"]["flash_fwd_bwd"] = round(
        time.perf_counter() - t0, 4)

    # the oracle materialises [rows, H, S, S] fp32 scores: two batch rows
    # of it (rows are independent) keep it inside one chip's memory
    n = min(2, B)
    q32, k32, v32 = (x[:n].astype(jnp.float32) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        ref_o = jax.jit(ref_fwd)(q32, k32, v32)
        ref_g = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(
            q32, k32, v32, cot[:n])
    # bf16 carries 8 mantissa bits and the kernel rounds the scaled q,
    # the probabilities and the output to it while the fp32 oracle
    # rounds nothing: 5e-2 is the bound this repository's own bf16
    # kernel test uses (tests/test_flash_attention.py,
    # test_bf16_single_block_path). A wrong mask or block moves outputs
    # by O(1).
    np.testing.assert_allclose(np.asarray(o[:n], np.float32),
                               np.asarray(ref_o), rtol=5e-2, atol=5e-2)
    out["max_abs_err"]["flash_fwd"] = _max_err(o[:n], ref_o)
    for name, g, r in zip(("dq", "dk", "dv"), grads, ref_g):
        err = _max_err(g[:n], r)
        top = float(np.max(np.abs(np.asarray(r))))
        out["max_abs_err"][f"flash_{name}"] = err
        # same reason; gradients span orders of magnitude across
        # positions, so the bound is taken against the largest entry
        assert err <= 5e-2 * top, (name, err, top)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
               for g in grads)

    # ---- the paged pair over the pool's STORED shape, every dtype
    # FLAGS_kv_cache_dtype accepts: one token appended a row, then read
    bs = int(flag("kv_block_size"))
    rows, positions = sz.paged_rows, sz.paged_positions
    nblk = positions // bs
    N = rows * nblk + 1
    rng = np.random.default_rng(0)
    qd = jnp.asarray(rng.normal(size=(rows, H, 1, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, H, bs, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, H, bs, D)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, rows, H, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, N)).reshape(rows, nblk), jnp.int32)
    pos = jnp.asarray(sz.paged_pos, jnp.int32)
    ids, offs = tables[jnp.arange(rows), pos // bs], pos % bs
    out["paged_block_size"] = bs
    out["pool_packing"] = pool_packing(D, bs)
    out["pool_stored_shape"] = list(stored_shape(N, H, bs, D))

    def step(qd, pk, pv, new_k, new_v, ids, offs, tables, pos, ks, vs,
             new_ks, new_vs):
        """A decode layer's pool work: both appends, then the read."""
        if ks is None:
            pk = paged_kv_append(pk, new_k, ids, offs,
                                 interpret=impl == "interpret")
            pv = paged_kv_append(pv, new_v, ids, offs,
                                 interpret=impl == "interpret")
        else:
            pk, ks = paged_kv_append(pk, new_k, ids, offs, ks, new_ks,
                                     interpret=impl == "interpret")
            pv, vs = paged_kv_append(pv, new_v, ids, offs, vs, new_vs,
                                     interpret=impl == "interpret")
        return paged_attention(qd, pk, pv, tables, pos, ks, vs, impl=impl,
                               kv_heads=H), pk, pv, ks, vs

    for kv_dtype in _DTYPES:
        if kv_dtype == "int8":
            (pk, ks), (pv, vs) = quantize_kv(kp), quantize_kv(vp)
            (nk, nks), (nv, nvs) = quantize_kv(new[0]), quantize_kv(new[1])
        else:
            dt = jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32
            pk, pv, nk, nv = (x.astype(dt) for x in (kp, vp, new[0], new[1]))
            ks = vs = nks = nvs = None
        # the oracle: the composite write and read on the logical shape
        want_k = pk.at[ids, :, offs, :].set(nk)
        want_v = pv.at[ids, :, offs, :].set(nv)
        want_ks = None if ks is None else ks.at[ids, :, offs].set(nks)
        want_vs = None if vs is None else vs.at[ids, :, offs].set(nvs)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a: _xla_paged_attention(*a, scale))(
                qd, want_k, want_v, tables, pos, want_ks, want_vs)
        args = (qd, to_stored(pk), to_stored(pv), nk, nv, ids, offs, tables,
                pos, None if ks is None else scales_to_stored(ks, D),
                None if vs is None else scales_to_stored(vs, D), nks, nvs)
        fn = jax.jit(step).lower(*args).compile()
        t0 = time.perf_counter()
        got, got_k, got_v, got_ks, got_vs = jax.block_until_ready(fn(*args))
        out["smoke_timings_s"][f"paged_{kv_dtype}"] = round(
            time.perf_counter() - t0, 4)
        # the append moves values, it computes none: every block but
        # the trash block (no row writes it here) is bit for bit the
        # composite's
        for a, b in ((got_k, to_stored(want_k)), (got_v, to_stored(want_v))):
            assert bool(jnp.all(a == b)), f"paged_kv_append {kv_dtype}"
        if ks is not None:
            for a, b in ((got_ks, want_ks), (got_vs, want_vs)):
                assert bool(jnp.all(a == scales_to_stored(b, D))), kv_dtype
        # kernel and oracle read the same stored values and both
        # accumulate in fp32, so only the multiply differs: the kernel's
        # dot_generals ask for no precision, which lets the MXU take
        # fp32 operands as bf16 passes (8 mantissa bits). Scores are
        # O(1) and outputs are convex combinations of values of
        # magnitude <= ~4, so rounding moves an output by at most a few
        # 1e-2; a wrong block, mask or scale moves it by O(1).
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=5e-2)
        out["max_abs_err"][f"paged_{kv_dtype}"] = _max_err(got, ref)
    out["paged_timed"] = _time_paged_decode(sz)
    out["paged_micro"] = _paged_microbench(sz)
    out["grouped_window"] = _grouped_window_kernels(sz)
    return out


def _grouped_window_kernels(sz):
    """The flash forward and the paged decode kernel with grouped
    queries, with and without a window, and the routed expert kernel at
    decode and prefill row counts, each against its oracle at
    ``sz.gqa``'s shapes (mellum2-12b-a2.5b's on the chip)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import (_xla_attention,
                                                    flash_attention)
    from paddle_tpu.kernels.moe_experts import routed_experts
    from paddle_tpu.kernels.paged_attention import (_xla_paged_attention,
                                                    paged_attention,
                                                    window_blocks)
    g, impl = sz.gqa, sz.kernel_impl
    hq, hkv, d, bs = g["hq"], g["hkv"], g["d"], g["block"]
    scale = float(d) ** -0.5
    rng = np.random.default_rng(1)
    err, secs = {}, {}

    def timed(name, fn, *args):
        fn = jax.jit(fn).lower(*args).compile()
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn(*args))
        secs[name] = round(time.perf_counter() - t0, 5)
        return got

    seq = g["seq"]
    q = jnp.asarray(rng.normal(size=(1, hq, seq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, hkv, seq, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, hkv, seq, d)), jnp.bfloat16)
    rows, positions = g["rows"], g["positions"]
    pos = jnp.asarray(np.exp(rng.uniform(
        np.log(max(positions // 6, 1)), np.log(positions - 1),
        rows)).astype(np.int32))
    qd = jnp.asarray(rng.normal(size=(rows, hq, 1, d)), jnp.float32)
    for window in (None, g["window"]):
        tag = "window" if window else "full"
        got = timed(f"flash_{tag}", lambda q, k, v: flash_attention(
            q, k, v, causal=True, impl=impl, window=window), q, k, v)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, k, v: _xla_attention(
                q, k, v, None, scale, True, window))(
                    *(x.astype(jnp.float32) for x in (q, k, v)))
        # as the flash check above: bf16 probabilities against an fp32
        # oracle; a wrong head group or window edge moves outputs by O(1)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref), rtol=5e-2, atol=5e-2)
        err[f"flash_{tag}"] = _max_err(got, ref)

        # a full table, or a ring one block wider than the window needs
        nblk = -(-positions // bs) if window is None \
            else window_blocks(window, bs) + 1
        n = rows * nblk + 1
        kp = jnp.asarray(rng.normal(size=(n, hkv, bs, d)), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(n, hkv, bs, d)), jnp.bfloat16)
        tables = jnp.asarray(rng.permutation(np.arange(1, n)).reshape(
            rows, nblk), jnp.int32)
        args = (qd, kp, vp, tables, pos)
        got = timed(f"paged_{tag}", lambda *a: paged_attention(
            *a, impl=impl, window=window), *args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a: _xla_paged_attention(
                *a, None, None, scale, window))(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=5e-2)
        err[f"paged_{tag}"] = _max_err(got, ref)

    h, f, e, top = g["hidden"], g["f"], g["experts"], g["top_k"]
    wg, wu = (jnp.asarray(0.02 * rng.normal(size=(e, h, f)), jnp.bfloat16)
              for _ in range(2))
    wd = jnp.asarray(0.02 * rng.normal(size=(e, f, h)), jnp.bfloat16)
    for tag, n in (("decode", rows), ("prefill", seq)):
        x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
        probs = jax.nn.softmax(jnp.asarray(rng.normal(size=(n, e)),
                                           jnp.float32))
        w, idx = jax.lax.top_k(probs, top)
        valid = jnp.asarray(np.arange(n) % 5 != 0)
        args = (x, idx, w / w.sum(-1, keepdims=True), wg, wu, wd, valid)
        got, counts = timed(f"experts_{tag}", lambda *a: routed_experts(
            *a[:6], valid=a[6], impl=impl), *args)
        # the oracle's ragged_dot takes the same bf16 operands (their
        # products are exact in float32; Mosaic refuses bf16 operands at
        # precision=highest) and accumulates in float32 as the kernel
        # does; sums of 8 weighted expert outputs of magnitude ~0.02
        ref, ref_counts = jax.jit(lambda *a: routed_experts(
            *a[:6], valid=a[6], impl="xla"))(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-2, atol=2e-3)
        assert np.array_equal(np.asarray(counts), np.asarray(ref_counts))
        err[f"experts_{tag}"] = _max_err(got, ref)
    return {"shape": g, "max_abs_err": err, "second_call_s": secs}


def _kernel_events(run, names):
    """``run()`` under a profiler trace: for each of ``names`` the device
    microseconds of its custom calls on the ``XLA Ops`` line, in the
    order they ran (empty without a device plane: a rehearsal)."""
    import glob
    import tempfile
    import jax
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            run()
        path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        events = sorted(
            ((ev.start_ns, ev.name, ev.duration_ns / 1e3)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/device:TPU")
             for line in plane.lines if line.name == "XLA Ops"
             for ev in line.events if "custom-call" in ev.name))
    return {name: [us for _, ev, us in events if name in ev]
            for name in names}


def _median(xs):
    import statistics
    return statistics.median(xs) if xs else None


def _time_paged_decode(sz, calls=20):
    """Device microseconds of one ``paged_attention_decode`` call and of
    one ``paged_kv_append`` call at ``sz.paged_timed`` over the stored
    pool, positions log-uniform over its range: each kernel alone, from
    the ``XLA Ops`` line of a profiler trace. A rehearsal runs the same
    calls and has no device time to report."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import (
        blocks_per_step, paged_attention, paged_kv_append, row_steps,
        stored_shape)
    t = sz.paged_timed
    B, H, nblk, bs, D = (t[k] for k in ("rows", "heads", "blocks", "block",
                                        "d_head"))
    rng = np.random.default_rng(0)
    pos = np.exp(rng.uniform(*np.log(t["pos"]), B)).astype(np.int32)
    live = pos // bs + 1
    N = B * nblk + 1
    tables = np.zeros((B, nblk), np.int32)
    tables[np.arange(nblk) < live[:, None]] = \
        rng.permutation(np.arange(1, N))[:live.sum()]
    shape = stored_shape(N, H, bs, D)
    q = jnp.asarray(rng.normal(size=(B, H, 1, D)), jnp.float32)
    pools = [jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
             for _ in range(2)]
    new = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    ids, offs = tables[jnp.arange(B), pos // bs], pos % bs
    interpret = sz.kernel_impl == "interpret"
    read = jax.jit(lambda q, k, v, tables, pos: paged_attention(
        q, k, v, tables, pos, impl=sz.kernel_impl, kv_heads=H))
    append = jax.jit(lambda pool, new, ids, offs: paged_kv_append(
        pool, new, ids, offs, interpret=interpret), donate_argnums=(0,))
    jax.block_until_ready(read(q, *pools, tables, pos))
    pools[0] = jax.block_until_ready(append(pools[0], new, ids, offs))

    def run():
        for _ in range(calls):
            jax.block_until_ready(read(q, *pools, tables, pos))
            pools[0] = jax.block_until_ready(
                append(pools[0], new, ids, offs))

    us = _kernel_events(run, ("paged_attention_decode", "paged_kv_append"))
    G = blocks_per_step(H, bs, D, jnp.bfloat16, nblk)
    return {"shape": {k: v for k, v in t.items() if k != "pos"},
            "stored_shape": list(shape),
            "positions": [int(pos.min()), int(pos.max())],
            "live_blocks": int(live.sum()), "table_blocks": B * nblk,
            "grid_steps": B, "blocks_per_step": G,
            "kernel_steps": int(row_steps(np.asarray(pos), bs, G,
                                          nblk)[2].sum()),
            "calls": len(us["paged_attention_decode"]),
            "device_us_per_call": _median(us["paged_attention_decode"]),
            "append_device_us_per_call": _median(us["paged_kv_append"])}


def _paged_microbench(sz, chain=8, reps=5):
    """``paged_attention_decode`` alone at each shape of
    ``sz.paged_micro`` (the serve cells' decode steps, bf16 pool) under
    three kinds of bank: every row a free slot (``pos`` 0, a table of
    trash), every row the table's whole width, and the cell's own mix of
    live rows and free slots. An executable runs ``chain`` calls back to
    back, as a decode step runs its layers' (each call's query is the
    last one's result added to the first query). A row a case: device
    microseconds a call (median over ``reps`` runs of the chain, from a
    profiler trace; None in a rehearsal) and of a chain's first call
    (the device was idle before it), microseconds a walked step (a step
    for every G live blocks of a row), the HBM time of the live blocks'
    bytes and their share of the call. The mix is also compared with
    the oracle over its live rows."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import (
        _xla_paged_attention, blocks_per_step, paged_attention, row_steps,
        to_stored)
    from paddle_tpu.observability import utilization
    rng = np.random.default_rng(0)
    cases, runs = [], []
    for t in sz.paged_micro:
        B, hq, hkv, D, bs, nblk = (t[k] for k in (
            "rows", "hq", "hkv", "d", "block", "table"))
        window = t.get("window")
        N = B * nblk + 1
        keys = jax.random.split(jax.random.PRNGKey(len(cases)), 3)
        kp, vp = (jax.random.normal(k, (N, hkv, bs, D), jnp.bfloat16)
                  for k in keys[:2])
        q = jax.random.normal(keys[2], (B, hq, 1, D), jnp.float32)
        ks, vs = to_stored(kp), to_stored(vp)
        def read(q, k, v, tables, pos, window=window, hkv=hkv):
            return paged_attention(q, k, v, tables, pos, impl=sz.kernel_impl,
                                   kv_heads=hkv, window=window)

        def chained(q, *args, read=read):
            out = read(q, *args)
            for _ in range(chain - 1):
                out = read(q + out, *args)
            return out

        read, chained = jax.jit(read), jax.jit(chained)
        G = blocks_per_step(hkv, bs, D, jnp.bfloat16, nblk, window)
        whole = max(t["mixes"].values(), key=lambda m: m[2])[2] \
            if window else nblk
        mixes = {"free": (0, 0, 0), "whole": (B, whole, whole), **t["mixes"]}
        for mix, (n_live, lo, hi) in mixes.items():
            ctx = np.zeros(B, np.int64)
            ctx[:n_live] = rng.integers(lo, hi + 1, n_live)
            pos = np.maximum(ctx * bs - 1 - rng.integers(0, bs, B), 0)
            pos[n_live:] = 0
            held = np.minimum(ctx, nblk)       # columns a row has blocks in
            tables = np.zeros((B, nblk), np.int32)
            tables[np.arange(nblk) < held[:, None]] = \
                rng.permutation(np.arange(1, N))[:held.sum()]
            _, live, steps = (np.where(ctx > 0, x, 0)
                              for x in row_steps(pos, bs, G, nblk, window))
            args = (q, ks, vs, jnp.asarray(tables),
                    jnp.asarray(pos, jnp.int32))
            got = jax.block_until_ready(read(*args))
            err = None
            if mix in t["mixes"]:
                with jax.default_matmul_precision("highest"):
                    ref = jax.jit(lambda q, k, v, tables, pos, window=window:
                                  _xla_paged_attention(
                                      q, k, v, tables, pos, None, None,
                                      float(D) ** -0.5, window))(
                        q, kp, vp, *args[3:])
                # bf16 MXU passes against a float32 oracle (phase_kernels)
                np.testing.assert_allclose(
                    np.asarray(got)[:n_live], np.asarray(ref)[:n_live],
                    rtol=0, atol=5e-2, err_msg=f"{t['name']} {mix}")
                err = _max_err(got[:n_live], ref[:n_live])
            nbytes = int(live.sum()) * 2 * hkv * bs * D * 2
            cases.append({
                "shape": t["name"], "mix": mix, "live_rows": int(n_live),
                "live_blocks": int(live.sum()), "blocks_per_step": G,
                "walked_steps": int(steps.sum()),
                "bytes": nbytes, "max_abs_err": err})
            jax.block_until_ready(chained(*args))
            runs.append((chained, args))

    def run():
        for chained, args in runs:
            for _ in range(reps):
                jax.block_until_ready(chained(*args))

    us = _kernel_events(run, ("paged_attention_decode",))[
        "paged_attention_decode"]
    peak = utilization.hbm_peak()
    calls = chain * reps
    if us:
        assert len(us) == calls * len(cases), (len(us), len(cases))
    for i, case in enumerate(cases):
        mine = us[i * calls:(i + 1) * calls]
        t = _median(mine)
        case["device_us_first_call"] = _median(mine[::chain])
        nbytes = case.pop("bytes")
        hbm_us = nbytes / peak * 1e6 if peak else None
        case.update(
            device_us_per_call=t,
            us_per_walked_step=None if not t or not case["walked_steps"]
            else round(t / case["walked_steps"], 3),
            hbm_us=None if hbm_us is None else round(hbm_us, 2),
            bandwidth_bound_share=None if not t or hbm_us is None
            else round(hbm_us / t, 4))
    cols = ("shape", "mix", "live_rows", "live_blocks", "blocks_per_step",
            "walked_steps", "device_us_per_call", "device_us_first_call",
            "us_per_walked_step", "hbm_us", "bandwidth_bound_share")
    print("\n".join(["paged_attention_decode alone:", "  ".join(cols)] + [
        "  ".join(str(case[c]) for c in cols) for case in cases]),
          file=sys.stderr, flush=True)
    return cases


# -------------------------------------------------------------------- train

def _build_train(sz):
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models import gpt
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        out = gpt.gpt_pretrain(sz.cfg, sz.batch, sz.seq)
        opt = fluid.optimizer.AdamOptimizer(1e-4)
        opt = mp.decorate(opt, init_loss_scaling=1.0,
                          use_dynamic_loss_scaling=False)
        opt.minimize(out["loss"])
    feed = gpt.random_batch(sz.cfg, sz.batch, sz.seq,
                            rng=np.random.default_rng(0))
    return main, startup, out["loss"].name, feed


def _check_losses(sz, losses):
    first = math.log(sz.cfg.vocab_size)
    assert all(np.isfinite(losses)), losses
    # random weights predict a uniform next token: ln(vocab)
    assert abs(losses[0] - first) < 0.5, (losses[0], first)
    assert losses[-1] < losses[0], losses


def phase_train(smoke):
    import jax
    import paddle_tpu as fluid
    sz = smoke.sizes
    main, startup, loss_name, feed = _build_train(sz)
    exe, scope = fluid.Executor(), fluid.Scope()
    losses, step_s = [], []
    with fluid.scope_guard(scope):
        exe.run(startup)
        compiles = None
        for _ in range(3):
            t0 = time.perf_counter()
            loss, = exe.run(main, feed=feed, fetch_list=[loss_name],
                            return_numpy=False)
            jax.block_until_ready(loss)
            step_s.append(round(time.perf_counter() - t0, 3))
            losses.append(float(np.asarray(loss).reshape(())))
            if compiles is None:
                compiles = exe.cache_stats()["compiles"]
        assert exe.cache_stats()["compiles"] == compiles, \
            "Executor.run recompiled after its first step"
        slab = {n: np.stack([a] * 4) for n, a in feed.items()}
        slab_s = []
        for i in range(2):
            t0 = time.perf_counter()
            out, = exe.run_steps(main, feed=slab, fetch_list=[loss_name],
                                 return_numpy=False)
            jax.block_until_ready(out)
            slab_s.append(round(time.perf_counter() - t0, 3))
            losses += [float(x) for x in np.asarray(out).reshape(-1)]
            if i == 0:
                compiles = exe.cache_stats()["compiles"]
        assert exe.cache_stats()["compiles"] == compiles, \
            "Executor.run_steps recompiled on its second slab"
        stats = exe.cache_stats()
    _check_losses(sz, losses)
    return {"losses": [round(x, 4) for x in losses],
            "executor_compiles": stats["compiles"],
            "executor_trace_s": round(stats["trace_ms"] / 1e3, 2),
            "executor_compile_s": round(stats["compile_ms"] / 1e3, 2),
            "smoke_timings_s": {"run_steps_incl_first": step_s,
                                "run_steps_k4_slabs_incl_first": slab_s},
            "peak_bytes_in_use": _peak_bytes()}


# -------------------------------------------------------------------- serve

def _startup_scope(cfg):
    """Startup-initialised GPT parameters in a fresh scope."""
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        gpt.gpt_logits(cfg)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    return scope


def _logits_check(sz, gen, reference=None, tol=5e-2):
    """Next-token logits after prefill plus a few paged decode steps
    against a full recompute of the same prefix (the program's own
    cache-less forward, or ``reference(seq)`` where one is given).
    Logits, not tokens: with random weights and reduced-precision fp32
    matmuls an argmax can flip on rounding with nothing wrong."""
    import jax
    cfg = gen.cfg
    rng = np.random.default_rng(1)
    seq = list(rng.integers(1, cfg.vocab_size, sz.check_prompt))
    key = jax.random.PRNGKey(0)
    pool = gen.new_pool(1, name="smoke_check")

    def recompute(seq):
        if reference is not None:
            return np.asarray(reference(np.asarray(seq, np.int32)))
        t, p, last = gen._pack_prompts([np.asarray(seq, np.int32)])
        logits, _ = gen._run_logits(t, p, last, key)
        return np.asarray(logits)[0]

    diffs, agree = [], []
    pool.alloc(0, len(seq))
    tokens, pos_ids, last = gen._pack_prompts([np.asarray(seq, np.int32)])
    logits, row_caches, _ = gen._run_prefill(tokens, pos_ids, last, key)
    pool.scatter_prefill([0], row_caches, tokens.shape[1])
    got = np.asarray(logits)[0]
    for step in range(sz.check_steps + 1):
        ref = recompute(seq)
        diffs.append(_max_err(got, ref))
        agree.append(bool(np.argmax(got) == np.argmax(ref)))
        assert np.all(np.isfinite(got))
        # fp32 weights, but fp32 matmuls on a TPU default to bf16
        # passes, and the cached path and the recompute contract in
        # different shapes and orders through every layer. Logits of a
        # startup-initialised GPT have a standard deviation of about
        # 0.5 (unit-variance hidden state against N(0, 0.02) tied
        # embeddings), so 5e-2 is a tenth of one: rounding stays under
        # it, a stale, misplaced or masked-out key does not.
        assert diffs[-1] <= tol, (step, diffs)
        if step == sz.check_steps:
            break
        tok = int(np.argmax(ref))
        pos = len(seq)
        seq.append(tok)
        pool.ensure(0, pos)
        logits, _ = gen._run_decode_paged(
            np.asarray([tok], np.int32), np.asarray([pos], np.int32),
            pool, key)
        got = np.asarray(logits)[0]
    pool.free_slot(0)
    return {"max_abs_diff_per_step": [round(d, 6) for d in diffs],
            "top1_agrees_per_step": agree,
            "logits_std": round(float(np.std(ref)), 4)}


def phase_serve(smoke):
    from paddle_tpu.models.generation import GPTGenerator
    from paddle_tpu.serving import Client, InferenceServer
    sz = smoke.sizes
    cfg = sz.cfg
    scope = _startup_scope(cfg)
    gen = GPTGenerator(cfg, scope, max_len=sz.max_len)
    # the loop watchdog "must exceed the worst-case first-shape compile"
    # (flags.py): a cold GPT-base prefill or decode compile is not a hung
    # chip call, and nothing here relies on a warmup having run
    server = InferenceServer(generator=gen, decode_slots=8,
                             loop_watchdog_s=600.0).start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in sz.prompt_lens]
    replies, errors, walls = {}, {}, {}

    def call(i):
        t0 = time.perf_counter()
        try:
            with Client(server.endpoint) as client:
                replies[i] = client.generate(
                    prompts[i], max_new_tokens=sz.new_tokens)
        except Exception as exc:  # noqa: BLE001 — reported below
            errors[i] = f"{type(exc).__name__}: {exc}"[:500]
        walls[i] = round(time.perf_counter() - t0, 2)

    try:
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, f"error replies: {errors}"
        for i, n in enumerate(sz.prompt_lens):
            toks = replies[i]
            assert toks.dtype == np.int32 and toks.shape == (
                sz.new_tokens,), (n, toks.shape)
            assert np.all((toks >= 0) & (toks < cfg.vocab_size)), n
        with Client(server.endpoint) as client:
            health = client.health()
        # a dying decode loop turns into "degraded" under the
        # LoopSupervisor while ping keeps answering: ask
        assert health["state"] == "serving", health
        assert health["breaker"] == "closed", health
        assert all(loop["alive"] and loop["restarts"] == 0
                   for loop in health["loops"].values()), health
        pool = server.gen_engine.pool
        leaked = pool.blocks_in_use()
        assert leaked == 0, pool.stats()
        stats = server.stats()
        # the pool keeps one layout from parameter to result: XLA:TPU
        # left no pool-sized copy in the decode step or in an admission
        # (the interpreter's loops on the CPU copy as they please); each
        # is keyed by its executable: the decode or prefill kind and the
        # pick inside it (decode_paged_fp32+sample_greedy, the
        # admission's prefill_bf16+sample_greedy with its scatter)
        relayouts = stats["pool_relayouts"]
        writers = [k for k in relayouts if k.startswith(
            (f"decode_paged_{pool.dtype}+", "prefill_"))]
        assert sz.rehearsal or (
            {k.partition("_")[0] for k in writers} == {"decode", "prefill"}
            and all(relayouts[k] == 0 for k in writers)), relayouts
        name, arr = next(iter(pool.arrays().items()))
        pool_format = f"{name} {arr.dtype.name}{list(arr.shape)} " \
                      f"{getattr(arr, 'format', None)}"
    finally:
        stopper = threading.Thread(target=server.stop)
        stopper.start()
        stopper.join(timeout=120)
        stopped = not stopper.is_alive()
    assert stopped, "server.stop() did not return within 120 s"
    out = {"prompt_lens": list(sz.prompt_lens),
           "new_tokens": sz.new_tokens,
           "replies": len(replies), "error_replies": len(errors),
           "health_state": health["state"], "breaker": health["breaker"],
           "blocks_in_use_after": leaked,
           "generator_compiles": int(stats.get("compiles", 0)),
           "decode_steps": int(stats.get("decode_steps", 0)),
           "decode_steps_ahead": int(stats.get("decode_steps_ahead", 0)),
           "kv_cache_dtype": pool.dtype,
           "pool_relayouts": relayouts, "pool_format": pool_format,
           "smoke_timings_s": {"request_walls_incl_compile": [
               walls[i] for i in range(len(prompts))]}}
    out["logits_check"] = _logits_check(sz, gen)
    out["peak_bytes_in_use"] = _peak_bytes()
    return out


# ------------------------------------------------------------------ prefill

def phase_prefill(smoke):
    """GPT's prefill at benchmark configuration ``gpt2-medium``'s widths
    (24 layers of 1,024, 16 heads of 64, a bf16 pool of 1,024
    positions) at the largest and the smallest shape the one-token cell
    warms: what XLA says the executable holds, the row caches it hands
    the scatter, one block of the pool against them, and no pool-sized
    copy in the scatter. The timings are smoke timings of a warm call."""
    import jax
    from benchmark.families import gpt as fam
    from paddle_tpu.flags import flag, set_flags
    sz = smoke.sizes
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs",
                           "gpt2-medium.json")) as fh:
        config = json.load(fh)
    fsz = fam.Sizes(config, rehearsal=sz.rehearsal)
    serve = dict(config["serve"],
                 **(config["rehearsal"]["serve"] if sz.rehearsal else {}))
    kv_dtype = flag("kv_cache_dtype")
    try:
        gen = fam.build_generator(fsz, serve, seed=37)
    finally:
        set_flags({"kv_cache_dtype": kv_dtype})
    rows_max = max(rows for rows, _ in sz.prefill_shapes)
    pool = gen.new_pool(rows_max, dtype=serve["kv_cache_dtype"],
                        name="smoke_prefill")
    kind = gen.arch.prefill_kind(pool.dtype)
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(37)
    shapes = {}
    for rows, seq in sz.prefill_shapes:
        # shorter than their bucket: the padding lands in the trash block
        lens = [seq - 3 - r for r in range(rows)]
        prompts = [rng.integers(1, fsz.vocab_size, n).astype(np.int32)
                   for n in lens]
        tokens, pos_ids, last = gen._pack_prompts(prompts)
        assert tokens.shape == (rows, seq), tokens.shape
        slots = list(range(rows))

        def once():
            for r, n in zip(slots, lens):
                pool.alloc(r, n)
            t0 = time.perf_counter()
            _, row_caches, _ = gen._run_prefill(
                tokens, pos_ids, last, key, kv_dtype=pool.dtype)
            t1 = time.perf_counter()
            pool.scatter_prefill(slots, row_caches, seq, lengths=lens)
            jax.block_until_ready(pool.arrays())
            return row_caches, t1 - t0, time.perf_counter() - t1

        row_caches, _, _ = once()                  # compiles
        k0 = row_caches["cache_k_0"]
        # row 0's first block is its first positions, cast the pool's way
        want = np.asarray(k0[0, :, :pool.block_size].astype(
            pool.arrays()["cache_pk_0"].dtype))
        got = pool.logical("cache_pk_0", pool.tables[0, :1])[0]
        assert np.array_equal(got.view(np.uint8), want.view(np.uint8))
        feed = {"tokens": tokens, "pos_ids": pos_ids, "last_pos": last}
        mem = gen.cache.get(gen._signature(kind, feed)).memory_analysis()
        warm = []
        for _ in range(3):
            for r in slots:
                pool.free_slot(r)
            warm.append(once()[1:])
        for r in slots:
            pool.free_slot(r)
        shapes[f"{rows}x{seq}"] = {
            "row_cache": f"{k0.dtype.name}{list(k0.shape)}",
            "cache_bytes": int(sum(a.nbytes for a in row_caches.values())),
            "prefill_bytes_counted": int(gen.arch.prefill_bytes(
                rows, seq, gen.max_len, k0.dtype.itemsize)),
            "memory_analysis": {
                name: int(getattr(mem, f"{name}_size_in_bytes"))
                for name in ("argument", "output", "temp", "alias")},
            "smoke_timings_ms": {
                "prefill_warm": round(1e3 * min(w[0] for w in warm), 2),
                "scatter_warm": round(1e3 * min(w[1] for w in warm), 2)}}
    relayouts = pool._scatter().relayouts
    assert sz.rehearsal or relayouts == 0, relayouts
    return {"kind": kind, "kv_cache_dtype": pool.dtype,
            "max_len": gen.max_len, "layers": fsz.n_layer,
            "shapes": shapes, "pool_relayouts": {"scatter": relayouts},
            "peak_bytes_in_use": _peak_bytes()}


# --------------------------------------------------------------------- loop

def phase_loop(smoke):
    """The looped block (``models/ouro.py``: a stack of layers run four
    times over shared weights, a cache a (pass, layer) pair, the passes
    of a weight layer in one pool array) through ``InferenceServer``:
    the decode step and the admissions leave no pool-sized copy though the
    append runs inside the loop of passes, and prefill then decode agree
    a step with the family's plain float32 reference."""
    import jax.numpy as jnp
    from benchmark.families import ouro as fam
    from paddle_tpu.flags import flag, set_flags
    from paddle_tpu.serving import InferenceServer
    sz, loop = smoke.sizes, smoke.sizes.loop
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs", "ouro-2.6b.json")) as fh:
        config = json.load(fh)
    if loop["layers"]:
        config["num_hidden_layers"] = loop["layers"]
    fsz = fam.Sizes(config, rehearsal=sz.rehearsal)
    kv_dtype = flag("kv_cache_dtype")
    try:
        gen = fam.build_generator(
            fsz, {"kv_cache_dtype": "bf16",
                  "max_len": 64 if sz.rehearsal else 320}, seed=33)
        server = InferenceServer(generator=gen, decode_slots=loop["slots"],
                                 loop_watchdog_s=600.0)
        server.start(serve_network=False)
        try:
            rng = np.random.default_rng(0)
            prompts = [rng.integers(1, fsz.vocab_size, n).astype(np.int32)
                       for n in loop["prompt_lens"]]
            reqs = [server.submit_generate(
                p, max_new_tokens=loop["new_tokens"]) for p in prompts]
            outs = [r.wait(timeout=1200)[0] for r in reqs]
            stats = server.stats()
            pool = server.gen_engine.pool
            assert pool.blocks_in_use() == 0, pool.stats()
        finally:
            server.stop()
        assert all(o.shape == (loop["new_tokens"],) for o in outs)
        assert stats["kv_cache_layers"] == fsz.cache_layers \
            == pool.passes * pool.num_arrays, stats["kv_cache_layers"]
        relayouts = stats["pool_relayouts"]
        writers = [k for k in relayouts if k.startswith(
            (f"decode_paged_{pool.dtype}+", "prefill_"))]
        assert sz.rehearsal or (
            {k.partition("_")[0] for k in writers} == {"decode", "prefill"}
            and all(relayouts[k] == 0 for k in writers)), relayouts
        params = fam.init_params(fsz, 33)

        def reference(seq):
            return fam.reference_logits(fsz, params,
                                        jnp.asarray(seq)[None])[0, -1]

        # bfloat16 weights and activations against float32 at
        # precision=highest through 8 block applications: some 1e-2 of
        # logits whose standard deviation is 0.9; a stale or misplaced
        # key, or a pass reading another pass's cache, moves them by
        # tenths
        check = _logits_check(sz, gen, reference=reference, tol=0.1)
    finally:
        set_flags({"kv_cache_dtype": kv_dtype})
    return {"layers": fsz.num_hidden_layers, "ut_steps": fsz.total_ut_steps,
            "kv_cache_layers": int(stats["kv_cache_layers"]),
            "pool_arrays": pool.num_arrays,
            "loop_passes": int(stats.get("loop_passes", 0)),
            "decode_steps": int(stats.get("decode_steps", 0)),
            "replies": len(outs), "pool_relayouts": relayouts,
            "logits_check": check, "peak_bytes_in_use": _peak_bytes()}


# -------------------------------------------------------------------- state

def phase_state(smoke):
    """The hybrid block (``models/jamba.py``: Mamba-1 layers whose
    recurrent state and convolution tail live a slot in the pool beside
    the attention layers' paged keys and values) through
    ``InferenceServer``: the decode step and the admissions leave no copy
    of a block array or of the slot bank, and prefill (the scan kernel
    over a padded bucket) then decode agree a step with the family's
    plain float32 reference."""
    import jax.numpy as jnp
    from benchmark.families import jamba as fam
    from paddle_tpu.flags import flag, set_flags
    from paddle_tpu.serving import InferenceServer
    sz, state = smoke.sizes, smoke.sizes.state
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs", "jamba2-3b.json")) as fh:
        config = json.load(fh)
    if state["layers"]:
        config.update(num_hidden_layers=state["layers"],
                      attn_layer_period=state["period"],
                      attn_layer_offset=state["offset"])
    fsz = fam.Sizes(config, rehearsal=sz.rehearsal)
    kv_dtype = flag("kv_cache_dtype")
    try:
        gen = fam.build_generator(
            fsz, {"kv_cache_dtype": "bf16",
                  "max_len": 64 if sz.rehearsal else 640}, seed=36)
        server = InferenceServer(generator=gen, decode_slots=state["slots"],
                                 loop_watchdog_s=600.0)
        server.start(serve_network=False)
        try:
            rng = np.random.default_rng(0)
            prompts = [rng.integers(1, fsz.vocab_size, n).astype(np.int32)
                       for n in state["prompt_lens"]]
            reqs = [server.submit_generate(
                p, max_new_tokens=state["new_tokens"]) for p in prompts]
            outs = [r.wait(timeout=1200)[0] for r in reqs]
            stats = server.stats()
            pool = server.gen_engine.pool
            assert pool.blocks_in_use() == 0, pool.stats()
        finally:
            server.stop()
        assert all(o.shape == (state["new_tokens"],) for o in outs)
        assert stats["kvpool_state_layers"] == fsz.mamba_layers \
            == pool.state_layers, stats["kvpool_state_layers"]
        assert stats["kv_cache_layers"] == fsz.attention_layers
        relayouts = stats["pool_relayouts"]
        writers = [k for k in relayouts if k.startswith(
            (f"decode_paged_{pool.dtype}+", "prefill_"))]
        assert sz.rehearsal or (
            {k.partition("_")[0] for k in writers} == {"decode", "prefill"}
            and all(relayouts[k] == 0 for k in writers)), relayouts
        params = fam.init_params(fsz, 36)

        def reference(seq):
            return fam.reference_logits(fsz, params,
                                        jnp.asarray(seq)[None])[0, -1]

        # bfloat16 weights and activations against float32 at
        # precision=highest through 6 layers whose mixers' outputs are
        # ten times the embedding's scale: some 2e-2 of logits whose
        # standard deviation is 1; a state taken at the bucket's end, a
        # stale tail or a dropped inner norm moves them by tenths
        check = _logits_check(sz, gen, reference=reference, tol=0.1)
    finally:
        set_flags({"kv_cache_dtype": kv_dtype})
    return {"layers": fsz.num_hidden_layers,
            "state_layers": int(stats["kvpool_state_layers"]),
            "kv_cache_layers": int(stats["kv_cache_layers"]),
            "state_bytes_per_slot": int(
                stats["kvpool_state_bytes_per_slot"]),
            "state_slot_writes": int(stats.get("state_slot_writes", 0)),
            "scan_tokens": int(stats.get("scan_tokens", 0)),
            "decode_steps": int(stats.get("decode_steps", 0)),
            "replies": len(outs), "pool_relayouts": relayouts,
            "logits_check": check, "peak_bytes_in_use": _peak_bytes()}


# --------------------------------------------------------------------- mesh

def _assert_per_shard(texts, global_shape, what, expect_kernel):
    """The compiled per-device HLO must never hold the GLOBAL shape of
    an attention operand: were a Pallas custom call fed by an
    all-gather (of the batch, or of the head-sharded pool), that shape
    is what the all-gather would produce."""
    pat = re.compile(r"\[" + ",".join(str(d) for d in global_shape) + r"\]")
    calls = 0
    for text in texts:
        assert not pat.search(text), \
            f"{what}: global shape {global_shape} in the per-device HLO"
        calls += text.count('custom_call_target="tpu_custom_call"')
    if expect_kernel:
        assert calls > 0, f"{what}: no Pallas custom call in the HLO"
    return calls


def phase_mesh(smoke):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.flags import flag
    from paddle_tpu.models.generation import GPTGenerator
    from paddle_tpu.parallel.mesh import default_mesh, set_mesh
    sz = smoke.sizes
    cfg = sz.cfg
    on_chip = not sz.rehearsal
    ndev = len(jax.devices())
    out = {"smoke_timings_s": {}}

    def in_use():
        stats = [d.memory_stats() for d in jax.devices()]
        return [m["bytes_in_use"] for m in stats] if all(stats) else None

    # ---- data-parallel train step over every chip
    main, startup, loss_name, feed = _build_train(sz)
    mesh = default_mesh()
    before = in_use()       # chip 0 still holds what earlier phases left
    exe, scope = fluid.Executor(), fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss_name, mesh=mesh)
        for _ in range(3):
            t0 = time.perf_counter()
            loss, = exe.run(compiled, feed=feed, fetch_list=[loss_name],
                            return_numpy=False)
            jax.block_until_ready(loss)
            losses.append(float(np.asarray(loss).reshape(())))
        out["smoke_timings_s"]["dp_step"] = round(
            time.perf_counter() - t0, 3)
        texts = [v[0].as_text() for k, v in exe._cache.items()
                 if k[0] == main._uid]
    _check_losses(sz, losses)
    heads, d_head = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    out["dp_devices"] = ndev
    out["dp_losses"] = [round(x, 4) for x in losses]
    out["dp_attention_custom_calls"] = _assert_per_shard(
        texts, (sz.batch, heads, sz.seq, d_head), "dp train step", on_chip)
    out["dp_all_gathers"] = sum(
        len(re.findall(r"\ball-gather(?:-start)?\(", t)) for t in texts)
    if before is not None:
        after = in_use()
        grew = [a - b for a, b in zip(after, before)]
        out["dp_bytes_in_use_per_device"] = after
        out["dp_bytes_grown_per_device"] = grew
        out["dp_peak_bytes_per_device"] = [
            d.memory_stats().get("peak_bytes_in_use") for d in jax.devices()]
        # every chip holds the same replicated parameters and optimizer
        # state and nothing else outlives a step: a chip that grew by
        # several times another's bytes is holding state for all of them
        assert min(grew) > 0 and max(grew) <= 2 * min(grew), grew
    del exe, scope, compiled
    gc.collect()

    # ---- tensor-parallel paged generation
    scope = _startup_scope(cfg)
    gen = GPTGenerator(cfg, scope, max_len=sz.max_len, tp=2)
    try:
        rng = np.random.default_rng(2)
        prompt = rng.integers(1, cfg.vocab_size,
                              sz.check_prompt).astype(np.int32)
        t0 = time.perf_counter()
        toks, = gen.generate([prompt], max_new_tokens=sz.new_tokens)
        out["smoke_timings_s"]["tp2_generate_incl_compile"] = round(
            time.perf_counter() - t0, 2)
        assert toks.shape == (sz.new_tokens,), toks.shape
        assert np.all((toks >= 0) & (toks < cfg.vocab_size))
        texts = [v.as_text() for _, v in gen.cache.items()]
        nblocks = gen._paged_pools[
            (1, flag("kv_cache_dtype"), int(flag("kv_block_size")))
        ].num_blocks
        out["tp2_attention_custom_calls"] = _assert_per_shard(
            texts, (nblocks, heads, int(flag("kv_block_size")), d_head),
            "tp=2 paged decode", on_chip)
        out["tp2_new_tokens"] = int(toks.size)
    finally:
        set_mesh(None)      # GPTGenerator(tp=) installs its mesh as ambient
    return out


# --------------------------------------------------------------------- main

_PHASES = {"kernels": phase_kernels, "train": phase_train,
           "serve": phase_serve, "prefill": phase_prefill,
           "loop": phase_loop, "state": phase_state,
           "mesh": phase_mesh}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="dry run on the CPU at GPTConfig.tiny() with interpreted "
             "kernels; every line says rehearsal and none says pass")
    ap.add_argument(
        "phases", nargs="*", metavar="phase",
        help=f"run these alone, of {', '.join(_PHASES)} (default: all; "
             f"mesh needs four devices)")
    args = ap.parse_args(argv)
    if set(args.phases) - set(_PHASES):
        ap.error(f"no such phase: {sorted(set(args.phases) - set(_PHASES))}")

    import jax
    platform = jax.devices()[0].platform
    if args.rehearse_cpu:
        if platform != "cpu":
            print(f"chip_smoke: --rehearse-cpu is for the CPU, JAX found "
                  f"{platform!r}; run without it", file=sys.stderr)
            return 2
    elif platform != "tpu":
        print(f"chip_smoke: needs a TPU and JAX found platform "
              f"{platform!r} ({jax.devices()[0].device_kind}); nothing "
              f"was run. --rehearse-cpu dry-runs the script on the CPU.",
              file=sys.stderr)
        return 2

    from paddle_tpu.kernels import _dispatch
    from paddle_tpu.observability import utilization
    from paddle_tpu.utils import compile_cache
    cache = compile_cache.enable()
    sizes = Sizes(args.rehearse_cpu)
    if args.rehearse_cpu:
        # what a TPU would resolve to, through the Pallas interpreter
        _dispatch.auto_impl = lambda: "interpret"
    elif utilization.peak_flops() is None or utilization.hbm_peak() is None:
        print(f"chip_smoke: device kind "
              f"{jax.devices()[0].device_kind!r} is not in "
              f"observability/utilization.py's peak tables; add it with "
              f"its source before measuring on it", file=sys.stderr)
        return 2

    smoke = Smoke(sizes, cache)
    print(json.dumps({"phase": "start", **smoke.device, **smoke.versions,
                      "rehearsal_not_a_chip_run": sizes.rehearsal,
                      "compile_cache_dir": cache.directory}), flush=True)
    for name, fn in _PHASES.items():
        if name in (args.phases or _PHASES) and (
                name != "mesh" or smoke.device["count"] >= 4):
            smoke.run_phase(name, fn)

    failed = sorted(n for n, s in smoke.results.items() if s == "fail")
    if sizes.rehearsal:
        print(json.dumps({"ok": None, "rehearsal_not_a_chip_run": True,
                          "phases": smoke.results}), flush=True)
        return 1 if failed else 0
    if failed:
        print(json.dumps({"ok": False, "device": smoke.device,
                          "failed": failed}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
