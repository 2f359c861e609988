"""``KVBlockPool`` with layer groups: the window group's ring, both
groups in allocation, admission, the leak sweep and the counts, and the
prefix cache declining."""
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.serving.kvpool import (KVBlockPool, KVPoolExhaustedError,
                                       _WindowGroup)

GROUPS = [{"name": "full", "window": None, "layers": [3]},
          {"name": "window", "window": 16, "layers": [0, 1, 2]}]


def _pool(**kw):
    kw.setdefault("slots", 4)
    kw.setdefault("num_layers", 4)
    kw.setdefault("num_heads", 2)
    kw.setdefault("d_head", 8)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("block_size", 4)
    kw.setdefault("dtype", "bf16")
    kw.setdefault("name", "groups")
    kw.setdefault("groups", GROUPS)
    return KVBlockPool(**kw)


def test_a_window_slot_never_holds_more_than_its_ring_and_recycles():
    pool = _pool()
    ring = pool.window.ring
    assert ring == 16 // 4 + 1
    pool.alloc(0, 10)
    assert pool.blocks_in_use_by_group() == {"full": 3, "window": 3}
    first = pool.window.tables[0].copy()
    for pos in range(10, 100):
        pool.ensure(0, pos)
        assert pool.window.held[0] <= ring
    assert pool.blocks_in_use_by_group() == {"full": 25, "window": ring}
    # logical block b lives in column b % ring: the blocks are the same
    # five, reused 20 times
    assert sorted(pool.window.tables[0]) == sorted(
        list(first[:3]) + list(pool.window.tables[0][3:]))
    assert pool.window.recycled == 25 - ring
    assert pool.stats()["window_blocks_recycled"] == 20
    assert pool.blocks_in_use() == 25 + ring
    assert pool.free_slot(0) == 25 + ring
    assert pool.blocks_in_use() == 0
    assert not pool.window.tables.any() and not pool.tables.any()


def test_arrays_are_sized_by_their_layer_group():
    pool = _pool()
    shapes = {n: pool.logical(n).shape for n in pool.arrays()}
    assert shapes["cache_pk_3"] == (4 * 32 + 1, 2, 4, 8)
    for i in (0, 1, 2):
        assert shapes[f"cache_pv_{i}"] == (4 * 5 + 1, 2, 4, 8)
    # stored: [N, H * bs // f, f * D]; D = 8 would pack 16 slots a lane
    # row, which a block of 4 does not hold, so a row a slot
    assert pool.arrays()["cache_pk_3"].shape == (4 * 32 + 1, 8, 8)
    assert pool.capacity_blocks == 4 * 32 + 4 * 5
    st = pool.stats()
    assert st["bytes_capacity"] == (128 * 1 + 20 * 3) * 2 * 2 * 4 * 8 * 2


@pytest.mark.parametrize("short", ["full", "window"])
def test_admission_and_allocation_count_both_groups(short):
    """Either group running out refuses the request, typed, with nothing
    held half."""
    kw = {"num_blocks": 6} if short == "full" else {}
    pool = _pool(slots=2, **kw)
    if short == "window":
        pool.window = _WindowGroup(2, [0, 1, 2], 16, 4, num_blocks=3)
    with pytest.raises(KVPoolExhaustedError):
        pool.admission_check(24)
    with pytest.raises(KVPoolExhaustedError):
        pool.alloc(0, 24)
    assert pool.blocks_in_use() == 0 and pool.holders() == {}
    pool.admission_check(8)
    pool.alloc(0, 8)
    with pytest.raises(KVPoolExhaustedError):
        pool.admission_check(8, pending_tokens=[8])
    assert pool.blocks_in_use_by_group() == {"full": 2, "window": 2}


def test_check_fits_leak_sweep_and_reset_cover_both_groups():
    pool = _pool()
    with pytest.raises(serving.BadRequestError, match="never"):
        pool.check_fits(4 * 128 + 1)
    pool.check_fits(128)
    pool.alloc(0, 40)
    pool.alloc(1, 7)
    assert pool.reclaim_leaks(live_slots=[1]) == 10 + 5
    assert pool.blocks_in_use_by_group() == {"full": 2, "window": 2}
    pool.reset()
    assert pool.blocks_in_use() == 0
    assert len(pool.window.free) == pool.window.capacity


def test_prefix_cache_declines_with_a_window_group():
    """A cached prefix's window layers would hold the end of the prompt
    it was cut from, so nothing is matched or deposited."""
    pool = _pool(prefix_cache=True)
    assert not pool.prefix_enabled
    prompt = np.arange(1, 20, dtype=np.int32)
    pool.alloc(0, prompt.size)
    assert pool.prefix_insert(prompt, 0) == 0
    assert pool.match_prefix(prompt) is None
    assert _pool(groups=None, prefix_cache=True).prefix_enabled
    with pytest.raises(serving.BadRequestError, match="window group"):
        pool.export_slot(0)
    with pytest.raises(ValueError, match="int8"):
        _pool(dtype="int8")


def test_scatter_keeps_the_windows_last_blocks():
    import jax.numpy as jnp
    pool = _pool(slots=2)
    lengths = [30, 6]
    rng = np.random.default_rng(0)
    rows = {f"cache_{k}_{i}": jnp.asarray(
        rng.normal(size=(2, 2, 32, 8)), jnp.bfloat16)
        for i in range(4) for k in ("k", "v")}
    for slot, n in enumerate(lengths):
        pool.alloc(slot, n)
    pool.scatter_prefill([0, 1], rows, 32, lengths=lengths)
    arrays = {n: pool.logical(n).astype(np.float32)
              for n in ("cache_pk_3", "cache_pv_1")}
    for slot, n in enumerate(lengths):
        last = (n - 1) // 4
        for blk in range(last + 1):
            src = np.asarray(rows["cache_k_3"][slot, :, blk * 4:blk * 4 + 4]
                             .astype(jnp.float32))
            got = arrays["cache_pk_3"][pool.tables[slot, blk]]
            np.testing.assert_array_equal(got, src)
            if blk > last - pool.window.ring:
                src = np.asarray(rows["cache_v_1"][
                    slot, :, blk * 4:blk * 4 + 4].astype(jnp.float32))
                got = arrays["cache_pv_1"][
                    pool.window.tables[slot, blk % pool.window.ring]]
                np.testing.assert_array_equal(got, src)
    with pytest.raises(ValueError, match="lengths"):
        pool.scatter_prefill([0, 1], rows, 32)


# ----------------------------------------------------- the state group

STATE_GROUPS = [
    {"name": "full", "window": None, "layers": [0, 1]},
    {"name": "state", "state": True, "layers": [0, 1, 2],
     "arrays": {"c": ((3 * 128,), "float32"), "s": ((8, 128), "float32")}}]
STATE_NAMES = [f"cache_s{t}_{m}" for t in "cs" for m in range(3)]


def _state_pool(**kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("groups", STATE_GROUPS)
    kw.setdefault("name", "stateful")
    return _pool(**kw)


def test_a_state_groups_arrays_live_a_slot_beside_the_blocks():
    from paddle_tpu.serving.kvpool import (pool_element_counts,
                                           state_array_specs)
    pool = _state_pool()
    assert list(state_array_specs(STATE_GROUPS)) == STATE_NAMES
    assert state_array_specs(GROUPS) == state_array_specs(None) == {}
    # KV cache layers and state layers are counted apart
    assert (pool.num_layers, pool.num_arrays, pool.state_layers) == (2, 2, 3)
    assert pool.feed_names() == ["cache_pk_0", "cache_pk_1", "cache_pv_0",
                                 "cache_pv_1"] + STATE_NAMES
    arrays = pool.arrays()
    assert sorted(arrays) == sorted(pool.feed_names())
    for m in range(3):
        assert arrays[f"cache_sc_{m}"].shape == (4, 384)
        assert arrays[f"cache_ss_{m}"].shape == (4, 8, 128)
        assert str(arrays[f"cache_ss_{m}"].dtype) == "float32"
        assert not np.asarray(arrays[f"cache_ss_{m}"]).any()
    # the relayout count looks for the state arrays too
    assert pool_element_counts(arrays) >= {4 * 384, 4 * 8 * 128}
    st = pool.stats()
    assert st["state_layers"] == 3
    assert st["state_bytes_per_slot"] == 3 * 4 * (384 + 8 * 128)
    blocks = (4 * 32 + 1) * 2 * 2 * 2 * 4 * 8 * 2
    assert st["pool_bytes"] == blocks + 4 * st["state_bytes_per_slot"] \
        == sum(a.size * a.dtype.itemsize for a in arrays.values())
    assert pool.state_attrs() == {"state_layers": 3}
    # a pool without one says nothing of it
    plain = _pool()
    assert plain.state_layers == 0 and plain.state_attrs() == {}
    assert plain.stats()["state_bytes_per_slot"] == 0
    assert plain.feed_names() == sorted(plain.arrays(), key=lambda n: (
        n[:8], int(n.rsplit("_", 1)[1])))
    with pytest.raises(ValueError, match="one state group"):
        _state_pool(groups=STATE_GROUPS + [STATE_GROUPS[1]])


def test_scatter_writes_the_admitted_rows_states_into_their_slots():
    import jax.numpy as jnp
    pool = _state_pool()
    rng = np.random.default_rng(1)
    rows = {f"cache_{k}_{i}": jnp.asarray(
        rng.normal(size=(2, 2, 16, 8)), jnp.bfloat16)
        for i in range(2) for k in ("k", "v")}
    for name in STATE_NAMES:
        shape = (2, 384) if "_sc_" in name else (2, 8, 128)
        rows[name] = jnp.asarray(rng.normal(size=shape), jnp.float32)
    for slot, n in ((3, 9), (1, 14)):
        pool.alloc(slot, n)
    pool.scatter_prefill([3, 1], rows, 16, lengths=[9, 14])
    for name in STATE_NAMES:
        got = np.asarray(pool.arrays()[name])
        np.testing.assert_array_equal(got[3], np.asarray(rows[name][0]))
        np.testing.assert_array_equal(got[1], np.asarray(rows[name][1]))
        assert not got[0].any() and not got[2].any()
    # the keys and values went through the table in the same call
    np.testing.assert_array_equal(
        pool.logical("cache_pk_1", pool.tables[3, :3]).astype(np.float32)
        .transpose(1, 0, 2, 3).reshape(2, 12, 8)[:, :9],
        np.asarray(rows["cache_k_1"][0, :, :9].astype(jnp.float32)))
    assert pool.relayouts() == {"scatter": 0}
    # a freed slot's state stays where it lies; an admission overwrites it
    pool.free_slot(3)
    assert np.asarray(pool.arrays()["cache_ss_0"])[3].any()
    assert pool.blocks_in_use() == 4
    pool.alloc(3, 5)
    pool.scatter_prefill([3], {n: a[1:] for n, a in rows.items()}, 16,
                         lengths=[5])
    np.testing.assert_array_equal(
        np.asarray(pool.arrays()["cache_ss_0"])[3],
        np.asarray(rows["cache_ss_0"][1]))


def test_a_state_group_resets_drops_and_declines_with_the_blocks():
    pool = _state_pool(prefix_cache=True)
    assert not pool.prefix_enabled
    prompt = np.arange(1, 20, dtype=np.int32)
    pool.alloc(0, prompt.size)
    assert pool.prefix_insert(prompt, 0) == 0
    assert pool.match_prefix(prompt) is None
    for refuse in (lambda: pool.export_slot(0),
                   lambda: pool.import_slot(1, {})):
        with pytest.raises(serving.BadRequestError, match="state group"):
            refuse()
    import jax.numpy as jnp
    held = dict(pool.arrays())
    held["cache_ss_1"] = jnp.ones_like(held["cache_ss_1"])
    pool.update_arrays(held)
    assert np.asarray(pool.arrays()["cache_ss_1"]).all()
    pool.drop_device()                      # the bank goes with the blocks
    assert not np.asarray(pool.arrays()["cache_ss_1"]).any()
    assert pool.blocks_in_use() == 5        # host accounting survives
    pool.update_arrays(held)
    pool.reset()
    assert pool.blocks_in_use() == 0
    assert not np.asarray(pool.arrays()["cache_ss_1"]).any()
    with pytest.raises(ValueError, match="int8"):
        _pool(dtype="int8")
