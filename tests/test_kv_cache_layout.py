"""The four KV-cache ops against numpy, over every stored layout.

A cache declared ``(num_slots, max_len, *inner)`` is STORED with an
inner shape of rank >= 2 flattened into one lane-dense minor axis
(ops/kv_cache_ops.stored_shape); the ops' contract — what an append
takes, what a gather returns — is the declared shape whatever the
storage. These cases hold the ops to a plain numpy model of that
contract, and look at the store entry itself.
"""

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import parallel
from simple_tensorflow_tpu.ops import kv_cache_ops as kvc

SLOTS, PAGE_LEN, ROWS = 6, 4, 3


@pytest.fixture(autouse=True)
def _fresh_graph():
    stf.reset_default_graph()
    yield
    stf.reset_default_graph()


def _np_append(cache, value, slots, positions):
    for b, (s, p) in enumerate(zip(slots, positions)):
        cache[s, p:p + value.shape[1]] = value[b]


def _np_gather(cache, idx):
    rows = cache[idx]
    if idx.ndim == 2:       # page table: pages concatenated in table order
        rows = rows.reshape((idx.shape[0], idx.shape[1] * cache.shape[1])
                            + cache.shape[2:])
    return rows


def _store_entry(sess, cache):
    return sess._variable_store.values[cache.name]


@pytest.mark.parametrize("table", [False, True], ids=["slots", "page_table"])
@pytest.mark.parametrize("width", [1, PAGE_LEN], ids=["w1", "wpage"])
@pytest.mark.parametrize("inner", [(), (8,), (4, 16), (16, 64)],
                         ids=["scalar", "r1", "h4d16", "h16d64"])
def test_cache_ops_match_numpy(inner, width, table):
    rng = np.random.RandomState(len(inner) * 7 + width)
    c = kvc.kv_cache("lay_kv/c", SLOTS, PAGE_LEN, inner, stf.float32,
                     paged=table)
    alloc = c.alloc()
    val = stf.placeholder(stf.float32, (ROWS, width) + inner, "val")
    slots = stf.placeholder(stf.int32, [ROWS], "slots")
    pos = stf.placeholder(stf.int32, [ROWS], "pos")
    idx_shape = [ROWS, 2] if table else [ROWS]
    idx = stf.placeholder(stf.int32, idx_shape, "idx")
    appended = c.append(val, slots, pos)
    with stf.control_dependencies([appended.op]):
        after_append = c.gather(idx)
    gathered = c.gather(idx, name="lay_gather")
    dst = stf.placeholder(stf.int32, [2], "dst")
    src = stf.placeholder(stf.int32, [2], "src")
    copied = c.copy_pages(dst, src)
    # what the graph sees is the declared shape, whatever the storage
    assert appended.shape.as_list() == list(c.shape)
    assert after_append.shape.as_list() == \
        [ROWS, PAGE_LEN * (2 if table else 1)] + list(inner)

    want = np.zeros(c.shape, np.float32)
    flat = int(np.prod(inner)) if len(inner) >= 2 else None
    stored = (SLOTS, PAGE_LEN) + ((flat,) if flat else inner)
    assert c.stored_shape == stored
    with stf.Session() as sess:
        sess.run(alloc.op)
        assert _store_entry(sess, c).shape == stored
        # two appends, the second overwriting part of the first
        for slot_ids in ([4, 0, 2], [1, 4, 5]):
            v = rng.randn(ROWS, width, *inner).astype(np.float32)
            s = np.asarray(slot_ids, np.int32)
            p = rng.randint(0, PAGE_LEN - width + 1, ROWS).astype(np.int32)
            i = (np.stack([s, s[::-1]], 1) if table else s).astype(np.int32)
            got = sess.run(after_append,
                           {val: v, slots: s, pos: p, idx: i})
            _np_append(want, v, s, p)
            np.testing.assert_array_equal(got, _np_gather(want, i))
        d, s = np.asarray([3, 0], np.int32), np.asarray([4, 5], np.int32)
        sess.run(copied.op, {dst: d, src: s})
        want[d] = want[s]
        everything = (np.arange(SLOTS, dtype=np.int32).reshape(ROWS, 2)
                      if table else np.asarray([3, 0, 4], np.int32))
        np.testing.assert_array_equal(
            sess.run(gathered, {idx: everything}),
            _np_gather(want, everything))
        entry = np.asarray(_store_entry(sess, c))
        assert entry.shape == stored
        np.testing.assert_array_equal(entry, want.reshape(stored))


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_head_sharded_store_holds_whole_heads_per_shard(tp):
    """``"tp:heads"`` declares dim 2 of (slots, len, heads, head_dim);
    stored, dim 2 is heads*head_dim, and sharding THAT over tp gives
    each device the same contiguous heads/tp heads: a shard's rows,
    seen as (heads/tp, head_dim), are the matching head slice of the
    gather."""
    heads, hd = 8, 4
    rng = np.random.RandomState(tp)
    with parallel.Mesh({"tp": tp}):
        c = kvc.kv_cache("lay_tp/c", SLOTS, PAGE_LEN, (heads, hd),
                         stf.float32, sharding="tp:heads")
        alloc = c.alloc()
        val = stf.placeholder(stf.float32, [ROWS, PAGE_LEN, heads, hd],
                              "val")
        slots = stf.placeholder(stf.int32, [ROWS], "slots")
        out = c.append_and_gather(val, slots,
                                  stf.constant(np.zeros(ROWS, np.int32)))
        with stf.Session() as sess:
            sess.run(alloc.op)
            v = rng.randn(ROWS, PAGE_LEN, heads, hd).astype(np.float32)
            s = np.asarray([5, 1, 2], np.int32)
            got = sess.run(out, {val: v, slots: s})
            np.testing.assert_array_equal(got, v)
            entry = _store_entry(sess, c)
            assert entry.shape == (SLOTS, PAGE_LEN, heads * hd)
            per = heads // tp
            shards = sorted(entry.addressable_shards,
                            key=lambda sh: sh.index[2].start or 0)
            assert len(shards) == tp
            for k, sh in enumerate(shards):
                data = np.asarray(sh.data)
                assert data.shape == (SLOTS, PAGE_LEN, per * hd)
                np.testing.assert_array_equal(
                    data[s].reshape(ROWS, PAGE_LEN, per, hd),
                    got[:, :, k * per:(k + 1) * per, :])
