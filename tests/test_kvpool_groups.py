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
