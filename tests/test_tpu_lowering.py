"""The Pallas kernels must lower for the TPU platform, checked on the CPU.

``jax.jit(f).trace(*specs).lower(lowering_platforms=("tpu",))`` runs the
Pallas-to-Mosaic lowering with no TPU and no libtpu: block-shape rules,
scalar-store rules and unsupported ops all fail here, in seconds. Every
other kernel test runs ``impl="interpret"`` or ``"xla"``, which checks
the arithmetic and none of those rules. The full Mosaic + XLA:TPU
compile against a compile-only v5e topology is the ``slow`` test at the
bottom.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import _dispatch
from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.kernels.paged_attention import (paged_attention,
                                                paged_kv_append,
                                                quantize_kv, stored_shape)
from paddle_tpu.serving.kvpool import (_DTYPES, _np_pool_dtype,
                                       count_pool_relayouts)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# GPT-base and BERT-base share the head shape (12 heads of 64); what
# differs is the mask (causal against a key bias) and the lengths run
H, D = 12, 64
FLASH_CASES = [
    # (label, S, causal, key bias)
    ("gpt_base_prefill_bucket", 32, True, False),
    ("gpt_base_train", 2048, True, False),
    ("bert_base", 128, False, True),
    ("bert_base_long", 2048, False, True),
]


def _spec(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash_fns(causal):
    def fwd(q, k, v, bias=None):
        return flash_attention(q, k, v, bias, causal=causal, impl="pallas")

    def loss(q, k, v, bias=None):
        return jnp.sum(fwd(q, k, v, bias).astype(jnp.float32))

    return fwd, jax.grad(loss, argnums=(0, 1, 2))


def _flash_specs(seq, with_bias, dtype, sharding=None, batch=2):
    qkv = _spec((batch, H, seq, D), dtype, sharding)
    specs = [qkv, qkv, qkv]
    if with_bias:
        specs.append(_spec((batch, 1, 1, seq), jnp.float32, sharding))
    return specs


def _paged_specs(kv_dtype, block, rows=8, positions=2048, sharding=None,
                 heads=H):
    nblk = positions // block
    pool = _spec((rows * nblk + 1, heads, block, D),
                 _np_pool_dtype(kv_dtype), sharding)
    specs = [_spec((rows, heads, 1, D), jnp.float32, sharding), pool, pool,
             _spec((rows, nblk), jnp.int32, sharding),
             _spec((rows,), jnp.int32, sharding)]
    if kv_dtype == "int8":
        scale = _spec((rows * nblk + 1, heads, block), jnp.float32,
                      sharding)
        specs += [scale, scale]
    return specs


def _paged_fn(q, k, v, tables, pos, k_scale=None, v_scale=None):
    return paged_attention(q, k, v, tables, pos, k_scale, v_scale,
                           impl="pallas")


@pytest.mark.parametrize("label,seq,causal,with_bias", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_lowers_for_tpu(label, seq, causal, with_bias, dtype):
    fwd, grad = _flash_fns(causal)
    specs = _flash_specs(seq, with_bias, dtype)
    for fn in (fwd, grad):
        text = jax.jit(fn).trace(*specs).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", _DTYPES)
@pytest.mark.parametrize("block", [16, 128])
def test_paged_decode_lowers_for_tpu(kv_dtype, block):
    """Every dtype FLAGS_kv_cache_dtype accepts, at the default block
    size and at a lane-wide one. Failed on the seed in every dtype:
    scalar stores to VMEM (fp32, bf16) and an illegal scale block
    (int8)."""
    text = jax.jit(_paged_fn).trace(*_paged_specs(kv_dtype, block)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_paged_decode_call_at_the_serve_cell_shapes():
    """What ``paged_attention_roofline.serve`` finds the kernel by in a
    device trace (benchmark/layer_metrics): the one
    ``paged_attention_decode`` call of gpt2-medium's 32 slots over 64
    blocks of 16 is a ``tpu_custom_call`` with an ``s32[32,64]`` block
    table among its operands."""
    import re
    lowered = jax.jit(_paged_fn).trace(*_paged_specs(
        "bf16", 16, rows=32, positions=1024, heads=16)).lower(
        lowering_platforms=("tpu",))
    assert re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()) == [
        "paged_attention_decode"]
    calls = [line for line in lowered.compiler_ir(
        dialect="hlo").as_hlo_text().splitlines() if "custom-call(" in line
        and 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert "s32[32,64]" in calls[0]


def _kernel_names(fn, specs):
    import re
    text = jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).as_text()
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


@pytest.mark.parametrize("name", [
    "flash_attention_fwd", "flash_attention_bwd", "flash_attention_bwd_dq",
    "flash_attention_bwd_dkv", "paged_attention_decode", "paged_kv_append"])
def test_each_kernel_lowers_under_its_own_name(name):
    """``pl.pallas_call(name=...)`` becomes ``kernel_name`` in the
    ``tpu_custom_call`` config, so a device trace can tell the flash
    forward from its backward calls, both from the paged kernel, and the
    pool's writer from its reader."""
    if name == "paged_attention_decode":
        found = _kernel_names(_paged_fn, _paged_specs("bf16", 16))
    elif name == "paged_kv_append":
        found = _kernel_names(_decode_layer(), _decode_layer_specs(
            8, H, H, D, 128, 1025))
        assert found == {"paged_attention_decode", "paged_kv_append"}
    else:
        # the short bucket takes the fused backward, the training length
        # the dq and dkv pair
        seq = 32 if name == "flash_attention_bwd" else 2048
        fwd, grad = _flash_fns(causal=True)
        fn = fwd if name == "flash_attention_fwd" else grad
        found = _kernel_names(fn, _flash_specs(seq, False, jnp.bfloat16))
    assert name in found
    assert not found & {"kern", "dq_kern", "dkv_kern"}


def _decode_layer(window=None, kv_heads=None):
    """What one layer of a paged decode step does to the pool: both
    appends, then the read, over the STORED arrays."""
    def layer(pk, pv, q, k, v, tables, pos):
        bs = pk.shape[1] * pk.shape[2] // (k.shape[1] * k.shape[2])
        col = pos // bs
        if window is not None:
            col = col % tables.shape[1]
        ids, offs = tables[jnp.arange(tables.shape[0]), col], pos % bs
        pk = paged_kv_append(pk, k.astype(pk.dtype), ids, offs)
        pv = paged_kv_append(pv, v.astype(pv.dtype), ids, offs)
        return paged_attention(q, pk, pv, tables, pos, impl="pallas",
                               window=window, kv_heads=kv_heads), pk, pv
    return layer


def _decode_layer_specs(rows, hq, hkv, d, nblk, blocks, sharding=None,
                        block=16):
    pool = _spec(stored_shape(blocks, hkv, block, d), jnp.bfloat16, sharding)
    new = _spec((rows, hkv, d), jnp.float32, sharding)
    return [pool, pool, _spec((rows, hq, 1, d), jnp.float32, sharding), new,
            new, _spec((rows, nblk), jnp.int32, sharding),
            _spec((rows,), jnp.int32, sharding)]


# (rows, query heads, KV heads, head width, table width, blocks, window)
_SERVE_LAYERS = {
    "gpt2-medium": (32, 16, 16, 64, 64, 2049, None),
    "mellum_full": (32, 32, 4, 128, 512, 16385, None),
    "mellum_window": (32, 32, 4, 128, 65, 2081, 1024)}


# test_benchmark.py reads a process-wide counter, and pytest-xdist hands
# out files largest first: this file stays smaller than it (30 cases), so
# it never runs ahead of it on the same worker
@pytest.mark.parametrize("kv_dtype,d_head", [
    ("fp32", 64), ("bf16", 64), ("int8", 64), ("bf16", 128)])
def test_paged_kv_append_lowers_for_tpu(kv_dtype, d_head):
    """The pool's one-token writer in every pool type packed two slots a
    lane row (D = 64), and a row a slot (D = 128, Mellum's bf16), under
    its own name and with no table among its operands."""
    import re
    rows, heads, block, blocks = 8, 12, 16, 65
    shape = stored_shape(blocks, heads, block, d_head)
    dt = _np_pool_dtype(kv_dtype)
    specs = [_spec(shape, dt), _spec((rows, heads, d_head), dt),
             _spec((rows,), jnp.int32), _spec((rows,), jnp.int32)]
    if kv_dtype == "int8":
        specs += [_spec((blocks, shape[2] // d_head, shape[1]), jnp.float32),
                  _spec((rows, heads), jnp.float32)]
    lowered = jax.jit(paged_kv_append).trace(*specs).lower(
        lowering_platforms=("tpu",))
    assert re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()) == [
        "paged_kv_append"]
    call, = [line for line in lowered.compiler_ir(
        dialect="hlo").as_hlo_text().splitlines() if "custom-call(" in line
        and 'custom_call_target="tpu_custom_call"' in line]
    assert not re.search(r"s32\[\d+,\d+\]", call)


def test_decode_layer_keeps_one_call_with_the_serve_cells_table():
    """``paged_attention_roofline.serve`` finds the decode kernel by
    ``tpu_custom_call`` and the ``s32[32,64]`` table among its operands:
    in a whole decode layer at gpt2-medium's shapes exactly one call has
    it, and it is not an append."""
    import re
    rows, hq, hkv, d, nblk, blocks, window = _SERVE_LAYERS["gpt2-medium"]
    lowered = jax.jit(_decode_layer(window, hkv)).trace(
        *_decode_layer_specs(rows, hq, hkv, d, nblk, blocks)).lower(
        lowering_platforms=("tpu",))
    # the kernels are jitted, so the module holds each body once and the
    # layer calls the append's twice (XLA inlines them; the compile-only
    # v5e test counts the inlined calls)
    text = lowered.as_text()
    assert sorted(re.findall(r'kernel_name = "([^"]+)"', text)) == [
        "paged_attention_decode", "paged_kv_append"]
    assert text.count("call @paged_kv_append(") == 2
    calls = [line for line in lowered.compiler_ir(
        dialect="hlo").as_hlo_text().splitlines() if "custom-call(" in line
        and 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    with_table = [c for c in calls if "s32[32,64]" in c]
    assert len(with_table) == 1
    # the decode call reads the appends' results: two stored pools
    assert with_table[0].count("bf16[2049,128,128]") >= 2


def test_composite_fallbacks_are_counted(monkeypatch):
    """Where the kernel would run (a TPU), more than one query per row
    and a general bias still take the composite, and the counter says
    why."""
    monkeypatch.setattr(_dispatch, "auto_impl", lambda: "interpret")
    before = _dispatch.resolved_counts()

    def grew(op, impl, reason):
        key = (op, impl, reason)
        return _dispatch.resolved_counts().get(key, 0) - before.get(key, 0)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 3, 8)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(4, 2, 8, 8)), jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    pos = jnp.asarray([5], jnp.int32)
    paged_attention(q, pool, pool, tables, pos)
    assert grew("paged_attention", "xla", "multi_query") == 1
    paged_attention(q[:, :, :1], pool, pool, tables, pos)
    assert grew("paged_attention", "interpret", "backend") == 1

    kv = jnp.asarray(rng.normal(size=(1, 2, 8, 8)), jnp.float32)
    flash_attention(kv, kv, kv, bias=jnp.zeros((1, 2, 8, 8)))
    assert grew("flash_attention", "xla", "general_bias") == 1
    flash_attention(kv, kv, kv, causal=True)
    assert grew("flash_attention", "interpret", "backend") == 1


def test_kernels_run_per_shard_under_a_mesh():
    """Under a mesh the kernels go through shard_map (GSPMD cannot
    partition a Pallas custom call): same numbers as the unsharded call,
    batch over dp for flash, heads over tp for paged."""
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(4, 2, 16, 8)), jnp.float32)
               for _ in range(3))
    mesh = make_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
    ref = flash_attention(q, k, v, causal=True, impl="interpret")
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="interpret", mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    assert "shard_map" in str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="interpret", mesh=mesh))(q, k, v))

    pool_k, pool_v = (rng.normal(size=(5, 2, 8, 8)).astype(np.float32)
                      for _ in range(2))
    qk, ks = quantize_kv(jnp.asarray(pool_k))
    qv, vs = quantize_kv(jnp.asarray(pool_v))
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([5, 12], jnp.int32)
    qd = jnp.asarray(rng.normal(size=(2, 2, 1, 8)), jnp.float32)
    ref = paged_attention(qd, qk, qv, tables, pos, ks, vs, impl="interpret")
    out = jax.jit(lambda *a: paged_attention(
        *a, impl="interpret", mesh=mesh))(qd, qk, qv, tables, pos, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_selective_scan_lowers_for_tpu_under_its_own_name():
    """The chunked scan at the published width of the state-space
    configuration (5,120 channels, 16 states; a 2,048 bucket): Mosaic
    takes its blocks, its dynamic sublane slices and its masked row
    assembly, and a device trace finds it as ``selective_scan_fwd``."""
    from paddle_tpu.kernels.selective_scan import selective_scan
    rows, seq, ch, n = 2, 2048, 5120, 16
    tokens = _spec((rows, seq, ch), jnp.float32)
    maps = _spec((rows, seq, n), jnp.float32)
    specs = [tokens, tokens, tokens, maps, maps,
             _spec((ch, n), jnp.float32), _spec((ch,), jnp.float32),
             _spec((ch,), jnp.float32), _spec((rows, n, ch), jnp.float32),
             _spec((rows,), jnp.int32)]
    found = _kernel_names(
        lambda *a: selective_scan(*a, impl="pallas"), specs)
    assert found == {"selective_scan_fwd"}


def test_chip_smoke_refuses_the_cpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` names the platform it
    found, prints no result line and exits non-zero."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode != 0
    assert "cpu" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.slow
def test_kernels_compile_for_a_compile_only_v5e():
    """Mosaic and XLA:TPU, no chip: libtpu's compile-only topology gives
    ``TPU v5 lite`` devices to lower against. One process at a time may
    hold libtpu, so this cannot share a machine with a second copy of
    itself."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no compile-only TPU topology here: {exc}")
    on = SingleDeviceSharding(topo.devices[0])
    fwd, grad = _flash_fns(True)
    specs = _flash_specs(2048, False, jnp.bfloat16, on, batch=8)
    jax.jit(fwd).lower(*specs).compile()
    jax.jit(grad).lower(*specs).compile()
    fwd, grad = _flash_fns(False)
    specs = _flash_specs(2048, True, jnp.bfloat16, on, batch=8)
    jax.jit(grad).lower(*specs).compile()
    for kv_dtype in _DTYPES:
        for block in (16, 128):
            jax.jit(_paged_fn).lower(
                *_paged_specs(kv_dtype, block, sharding=on)).compile()
    # one decode layer at each serve configuration's pool shapes, the
    # pool donated: the stored layout is the runtime's, the append's and
    # the reader's, so XLA:TPU leaves no copy of a whole pool array
    for label, (rows, hq, hkv, d, nblk, blocks, window) in \
            _SERVE_LAYERS.items():
        specs = _decode_layer_specs(rows, hq, hkv, d, nblk, blocks, on)
        text = jax.jit(_decode_layer(window, hkv), donate_argnums=(0, 1)) \
            .lower(*specs).compile().as_text()
        assert text.count("paged_kv_append") >= 2, label
        assert count_pool_relayouts(
            text, {int(np.prod(specs[0].shape))}) == 0, label
        assert "bf16[%d,%d,%d]{2,1,0" % specs[0].shape in text, label
