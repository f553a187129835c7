"""Core multi-step LRU cache library (the paper's contribution).

Public API:
    MSLRUConfig      — static cache geometry (S sets × M vectors × P lanes)
    MultiStepLRUCache — convenient stateful wrapper (host-side driver)
    row/engine functions — composable JAX building blocks (see multistep.py,
                           engine.py, sharded.py)
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.multistep import (  # noqa: F401
    AccessResult,
    MSLRUConfig,
    init_table,
    row_access,
    row_apply,
    row_delete,
    row_get,
    row_lookup,
    row_put,
    set_index_for,
)
from repro.core.engine import (  # noqa: F401
    OP_ACCESS,
    OP_CHAIN_GET,
    OP_CHAIN_PUT,
    OP_DELETE,
    OP_GET,
    OP_LOOKUP,
    make_batched_engine,
    make_chunked_stream_runner,
    make_sequential_engine,
)
from repro.core.invector import EMPTY_KEY  # noqa: F401

__all__ = [
    "MSLRUConfig",
    "MultiStepLRUCache",
    "AccessResult",
    "OP_ACCESS",
    "OP_GET",
    "OP_DELETE",
    "OP_LOOKUP",
    "OP_CHAIN_GET",
    "OP_CHAIN_PUT",
    "init_table",
    "EMPTY_KEY",
]


class MultiStepLRUCache:
    """Stateful host-side wrapper around the JAX cache engines.

    >>> cache = MultiStepLRUCache(MSLRUConfig(num_sets=1024, m=2, p=4))
    >>> res = cache.access(np.array([42]))

    The wrapper owns its table and donates it to each batched call, so the
    call writes the new table in place instead of copying the old one.
    ``cache.table`` is a value: reading or assigning it lends that array
    out, and the next call then donates a copy of it, never the array
    itself.
    """

    def __init__(self, cfg: MSLRUConfig, engine: str = "onepass",
                 use_kernel: bool = False):
        self.cfg = cfg
        self._table = init_table(cfg)
        self._lent = False
        self._seq = make_sequential_engine(cfg, with_ops=True)
        # one-pass conflict resolution (bit-exact with the rounds engine,
        # one HBM gather/scatter per batch); the jnp chain is the default —
        # ``use_kernel=True`` routes it through the Pallas kernel
        self._batched = make_batched_engine(cfg, engine=engine,
                                            use_kernel=use_kernel).donating

    @property
    def table(self):
        """The (S, A, C) table; it stays valid after later calls."""
        self._lent = True
        return self._table

    @table.setter
    def table(self, table):
        self._table = table
        self._lent = True

    # -- batched high-throughput path ----------------------------------------
    def access(self, keys: np.ndarray, vals: np.ndarray | None = None,
               ops: np.ndarray | None = None,
               chain_ids: np.ndarray | None = None,
               costs: np.ndarray | None = None):
        """Batched mixed-op call. keys (B,) or (B, KP); vals (B, V); ops (B,)
        per-query opcodes (OP_* in this module; None = all OP_ACCESS);
        chain_ids (B,) segment ids for CHAIN_GET/CHAIN_PUT rows (the fused
        serving tick — see the chain contract in engine.py); costs (B,)
        per-query insert costs (needs ``cfg.cost_planes`` — see the cost
        plane contract in engine.py).  The call is the host span
        ``msl.access`` in a profiler trace."""
        with jax.profiler.TraceAnnotation("msl.access"):
            keys = self._canon_keys(keys)
            if vals is None:
                vals = np.zeros((keys.shape[0], self.cfg.value_planes),
                                np.int32)
            if ops is not None:
                ops = jnp.asarray(ops, jnp.int32)
            if costs is not None:
                costs = jnp.asarray(costs, jnp.int32)
            # a lent table is never donated: the call takes a copy of it
            table = jnp.copy(self._table) if self._lent else self._table
            self._table, res = self._batched(table, keys,
                                             jnp.asarray(vals, jnp.int32),
                                             ops, chain_ids, costs)
            self._lent = False
            return res

    # -- exact sequential path -------------------------------------------------
    def access_seq(self, keys: np.ndarray, vals: np.ndarray | None = None,
                   ops=None, chain_ids=None, costs=None):
        keys = self._canon_keys(keys)
        n = keys.shape[0]
        if vals is None:
            vals = np.zeros((n, self.cfg.value_planes), np.int32)
        if ops is None:
            ops = np.full((n,), OP_ACCESS, np.int32)
        if costs is not None:
            costs = jnp.asarray(costs, jnp.int32)
        self._table, out = self._seq(
            self._table, keys, jnp.asarray(vals, jnp.int32),
            jnp.asarray(ops, jnp.int32), chain_ids, costs)
        return out

    def _canon_keys(self, keys):
        keys = jnp.asarray(keys, jnp.int32)
        if keys.ndim == 1:
            keys = keys[:, None]
        assert keys.shape[-1] == self.cfg.key_planes
        return keys

    @property
    def occupancy(self) -> float:
        valid = np.asarray(self._table[:, :, 0] != EMPTY_KEY)
        return float(valid.mean())
