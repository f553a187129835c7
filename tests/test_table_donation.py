"""The stateful cache donates its table to each batched call.

``MultiStepLRUCache`` owns its table, so its batched call donates it and the
engine writes the new table into the old buffer instead of copying it.  The
CPU backend honours donation, so a donated buffer reads ``is_deleted()``
afterwards: that is the check that the mechanism engages.  ``cache.table``
is a value: an array read or assigned through it is never donated.  The
functional ``make_batched_engine`` run keeps its caller's table valid.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (MSLRUConfig, MultiStepLRUCache, OP_ACCESS, OP_DELETE,
                        OP_GET, OP_LOOKUP, make_batched_engine)
from repro.serving.prefix_cache import PrefixCache

CFG = MSLRUConfig(num_sets=32, m=2, p=4, value_planes=2)
BATCH = 128


def _batches(n, seed=3):
    """Mixed-op batches over a key space a few times the table's capacity,
    so sets chain, evict and delete."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        keys = rng.integers(1, 4 * CFG.capacity, BATCH).astype(np.int32)
        vals = rng.integers(0, 2**20, (BATCH, CFG.value_planes)).astype(
            np.int32)
        ops = rng.choice([OP_ACCESS, OP_ACCESS, OP_GET, OP_LOOKUP, OP_DELETE],
                         BATCH).astype(np.int32)
        out.append((keys, vals, ops))
    return out


def _assert_same(a, b):
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_donation_engages_and_is_bit_identical(use_kernel):
    cache = MultiStepLRUCache(CFG, engine="onepass", use_kernel=use_kernel)
    run = make_batched_engine(CFG, engine="onepass", use_kernel=use_kernel)
    table = jnp.copy(cache._table)
    for keys, vals, ops in _batches(4):
        before = cache._table
        res = cache.access(keys, vals, ops)
        assert before.is_deleted()
        new_table, ref = run(table, jnp.asarray(keys)[:, None],
                             jnp.asarray(vals), ops)
        assert not table.is_deleted()          # the functional run keeps it
        _assert_same(res, ref)
        table = new_table
    np.testing.assert_array_equal(np.asarray(cache.table), np.asarray(table))


def test_read_table_stays_valid():
    cache = MultiStepLRUCache(CFG, engine="onepass")
    (k0, v0, o0), (k1, v1, o1) = _batches(2)
    cache.access(k0, v0, o0)
    lent = cache.table
    kept = np.asarray(lent).copy()
    cache.access(k1, v1, o1)
    assert not lent.is_deleted()
    np.testing.assert_array_equal(np.asarray(lent), kept)
    assert not np.array_equal(np.asarray(cache.table), kept)


def test_assigned_table_is_read_and_stays_valid():
    cache = MultiStepLRUCache(CFG, engine="onepass")
    run = make_batched_engine(CFG, engine="onepass")
    (k0, v0, o0), (k1, v1, o1) = _batches(2)
    start, _ = run(cache.table, jnp.asarray(k0)[:, None], jnp.asarray(v0), o0)
    kept = np.asarray(start).copy()
    cache.table = start
    res = cache.access(k1, v1, o1)
    assert not start.is_deleted()
    np.testing.assert_array_equal(np.asarray(start), kept)
    want_table, want = run(start, jnp.asarray(k1)[:, None], jnp.asarray(v1),
                           o1)
    _assert_same(res, want)
    np.testing.assert_array_equal(np.asarray(cache.table),
                                  np.asarray(want_table))


def test_occupancy_does_not_lend_the_table():
    cache = MultiStepLRUCache(CFG, engine="onepass")
    (k0, v0, o0), (k1, v1, o1) = _batches(2)
    cache.access(k0, v0, o0)
    assert cache.occupancy > 0
    before = cache._table
    cache.access(k1, v1, o1)
    assert before.is_deleted()


def test_prefix_cache_chain_call_donates():
    pc = PrefixCache(num_sets=32, m=2, p=4)
    chains = [[(2 * i + 1) * 1000 + 2 * t + 1 for t in range(3)]
              for i in range(4)]
    staged = [[10 * i + t for t in range(3)] for i in range(4)]
    first, _ = pc.serve_chains(chains, staged)
    assert [r.hitlen for r in first] == [0, 0, 0, 0]
    before = pc.cache._table
    again, _ = pc.serve_chains(chains, staged)
    assert before.is_deleted()
    assert [r.hitlen for r in again] == [3, 3, 3, 3]
    assert [r.pages for r in again] == staged
