"""jit'd public wrappers for the msl_cache kernels.

``msl_access`` routes between the Pallas kernel (TPU target; interpret mode
on CPU so the kernel body is exercised everywhere) and the pure-jnp oracle.

``onepass_update`` is the single-pass, conflict-aware batched update (the
performance path): an XLA prologue sorts the batch by set id once and derives
the duplicate-chain metadata, the table is gathered **once** (one live row
per distinct set; duplicate-chain members read the dummy row), the chain is
resolved on-chip (Pallas kernel, or an identical jnp loop when
``use_kernel=False``), and one scatter epilogue commits each chain's tail
row.  The optional ``ops`` vector rides the same sort, so one pass may mix
LOOKUP/GET/ACCESS/DELETE freely, plus the chain-segmented
CHAIN_GET/CHAIN_PUT ops of the fused serving tick — their per-row execute
mask (``chain_live``, the device-side segmented longest-prefix scan
computed by ``engine.chain_live_mask``) is one more sorted kernel operand
(opcode table in core/engine.py).
Contract: bit-exact with ``engine.batched_rounds_update`` — same
(table, AccessResult, served) for any (valid, max_rounds, ops) — while
touching HBM exactly twice per batch instead of twice per conflict round.

``kernel_rounds_update`` is the legacy rounds path with the kernel as the
row transition, kept as the bit-exactness oracle for the one-pass engine;
it now carries the same ``valid``/``max_rounds`` semantics as the XLA
rounds engine (they previously diverged on capped/padded streams).

The gather/scatter around the kernels stays in XLA, which is the intended
TPU decomposition (dynamic row indexing is an XLA strength; the dense lane
arithmetic is the kernel's job).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.multistep import (AccessResult, MSLRUConfig, set_columns,
                                  set_index_for, set_rows)
from repro.core.engine import (batched_rounds_update, make_batched_engine,
                               sorted_group_ranks)
from repro.core.invector import EMPTY_KEY
from repro.kernels.msl_cache import (
    _chain_body,
    _chain_state0,
    _kernel_block,
    cols_to_planes,
    msl_access_kernel_call,
    msl_onepass_kernel_call,
    split_planes,
)
from repro.kernels.ref import msl_access_ref

__all__ = [
    "msl_access",
    "onepass_update",
    "kernel_rounds_update",
    "make_kernel_batched_engine",
]


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def msl_access(rows, qkeys, qvals, *, cfg: MSLRUConfig, ops=None,
               chain_live=None, costs=None, use_kernel: bool = True,
               block_b: int = 2048, interpret: bool | None = None):
    """Mixed-op transition on pre-gathered rows; kernel or oracle backend."""
    if not use_kernel:
        return msl_access_ref(rows, qkeys, qvals, cfg, ops, chain_live, costs)
    if interpret is None:
        interpret = _on_cpu()
    return msl_access_kernel_call(
        rows, qkeys, qvals, ops, chain_live, costs, cfg=cfg, block_b=block_b,
        interpret=interpret)


# ---------------------------------------------------------------------------
# One-pass conflict-aware update
# ---------------------------------------------------------------------------

def _chain_resolve_xla(cfg: MSLRUConfig, planes, qk, qv, ops, lrank, served,
                       n_rounds, chain_live=None, costs=None):
    """jnp mirror of the one-pass kernel: the same ``_chain_body`` loop, in
    the kernel's plane layout, run in XLA over the whole sorted batch (no
    blocks, so no carry needed).

    planes (C, A, B) sorted-by-set gathered rows; ops (B,) sorted opcodes;
    lrank (B,) chain rank; served (B,) bool; n_rounds: dynamic trip count
    (max chain length); chain_live (B,) optional sorted execute mask for
    the CHAIN_GET/CHAIN_PUT rows; costs (B,) optional sorted insert costs.
    Returns (planes_after, hit_i32, pos, value, ev) like the kernel.
    """
    v = cfg.value_planes

    def row(x):
        return None if x is None else x[None, :]

    _, after, h, po, va, ev = jax.lax.fori_loop(
        0, n_rounds,
        _chain_body(cfg, split_planes(cols_to_planes(qk)),
                    split_planes(cols_to_planes(qv)), row(ops), row(lrank),
                    row(served), row(chain_live), row(costs)),
        _chain_state0(cfg, split_planes(planes)))
    return (jnp.stack(after), h[0], po[0],
            jnp.concatenate(va, axis=0).T[:, :v],
            jnp.concatenate(ev, axis=0).T)


def onepass_update(cfg: MSLRUConfig, table, gsid, valid, qkeys, qvals,
                   max_rounds: int | None = None, use_kernel: bool = True,
                   block_b: int = 2048, interpret: bool | None = None,
                   ops=None, chain_live=None, costs=None):
    """Single-pass exact multi-query update (one HBM gather + one scatter).

    Same contract as ``engine.batched_rounds_update``: table (S, A, C);
    gsid (B,) set id per query (``valid`` False entries are ignored);
    ``ops`` (B,) optional per-query opcodes (None = all OP_ACCESS);
    ``chain_live`` (B,) optional execute mask for CHAIN_GET/CHAIN_PUT rows
    (the fused serving tick — computed in batch order by
    ``engine.chain_live_mask`` and sorted here alongside the queries);
    returns (table, AccessResult, served).  Bit-exact w.r.t. processing the
    valid queries sequentially in batch order; ``max_rounds`` drops queries
    whose within-set rank exceeds the cap (res.hit=False, served=False),
    matching the rounds engine.  Unlike the rounds engine the cap does not
    shorten the wall-clock pass: dropped queries ride the on-chip chain as
    identities so the chain tail still commits the right row.
    """
    s = table.shape[0]
    b = gsid.shape[0]
    kp, v = cfg.key_planes, cfg.value_planes
    if ops is not None:  # None stays None: ACCESS-only specialization
        ops = jnp.asarray(ops, jnp.int32)
    if chain_live is not None:
        chain_live = jnp.asarray(chain_live, jnp.int32)
    if costs is not None:
        costs = jnp.asarray(costs, jnp.int32)

    # --- prologue: pad, sort by set id, derive duplicate-chain metadata ---
    bb = _kernel_block(b, block_b) if use_kernel else b
    pad = (-b) % bb
    bp = b + pad
    if pad:
        gsid = jnp.concatenate([gsid, jnp.zeros((pad,), gsid.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
        qkeys = jnp.concatenate([qkeys, jnp.zeros((pad, kp), jnp.int32)])
        qvals = jnp.concatenate([qvals, jnp.zeros((pad, v), jnp.int32)])
        if ops is not None:
            ops = jnp.concatenate([ops, jnp.zeros((pad,), jnp.int32)])
        if chain_live is not None:
            chain_live = jnp.concatenate(
                [chain_live, jnp.zeros((pad,), jnp.int32)])
        if costs is not None:
            costs = jnp.concatenate([costs, jnp.zeros((pad,), jnp.int32)])

    i = jnp.arange(bp, dtype=jnp.int32)
    sid_key = jnp.where(valid, gsid, s).astype(jnp.int32)  # invalid -> dummy
    order = jnp.argsort(sid_key, stable=True)
    ssid = sid_key[order]
    svalid = valid[order]
    sqk = qkeys[order]
    sqv = qvals[order]
    sops = None if ops is None else ops[order]
    slive = None if chain_live is None else chain_live[order]
    sqc = None if costs is None else costs[order]

    firsts, offset = sorted_group_ranks(ssid)   # chain heads + chain ranks
    n_valid_rounds = jnp.max(jnp.where(svalid, offset, -1)) + 1
    n_rounds = (jnp.minimum(n_valid_rounds, max_rounds)
                if max_rounds is not None else n_valid_rounds)
    served_s = svalid & (offset < n_rounds)
    # block-local chain rank: a chain crossing a block boundary restarts at
    # rank 0 there and is re-seeded from the kernel's cross-block carry
    lrank = jnp.where(svalid, jnp.minimum(offset, i % bb), 0)

    # --- one gather: a live row per *distinct* set (chain heads), as
    # columns of the table's plane view (see set_columns) straight into the
    # kernel layout; everyone else reads index S (zeros) and is resolved
    # on-chip --------------------------------------------------------------
    a, c = cfg.assoc, cfg.planes
    view = set_columns(table)
    planes_in = jnp.take(view, jnp.where(firsts, ssid, s), axis=1,
                         mode="fill", fill_value=0).reshape(c, a, bp)

    # --- resolve chains on-chip -------------------------------------------
    if use_kernel:
        if interpret is None:
            interpret = _on_cpu()
        nrounds_blocks = lrank.reshape(bp // bb, bb).max(axis=1).astype(jnp.int32) + 1
        # does each block's first query continue the previous block's chain?
        heads = ssid[::bb]
        cont = jnp.concatenate([jnp.zeros((1,), bool),
                                heads[1:] == ssid[bb - 1:-1:bb]])
        planes_after, hit, pos, val, ev = msl_onepass_kernel_call(
            planes_in, sqk, sqv, sops, lrank.astype(jnp.int32),
            served_s.astype(jnp.int32), nrounds_blocks,
            cont.astype(jnp.int32), slive, sqc,
            cfg=cfg, block_b=bb, interpret=interpret)
    else:
        planes_after, hit, pos, val, ev = _chain_resolve_xla(
            cfg, planes_in, sqk, sqv, sops, lrank, served_s, n_valid_rounds,
            slive, sqc)

    # --- one scatter: each chain's tail commits its set's final row -------
    lasts = jnp.concatenate([ssid[:-1] != ssid[1:], jnp.ones((1,), bool)])
    scatter_sid = jnp.where(lasts, ssid, s)     # non-tails: index S, dropped
    view = view.at[:, scatter_sid].set(planes_after.reshape(c * a, bp),
                                        mode="drop")
    table = set_rows(view, a, c)

    # --- unsort outputs; unserved queries report like the rounds engine ---
    inv = jnp.zeros((bp,), jnp.int32).at[order].set(i)

    def unsort(x):
        return x[inv][:b]

    served = unsort(served_s)
    hit_u, pos_u, val_u, ev_u = unsort(hit), unsort(pos), unsort(val), unsort(ev)
    res = AccessResult(
        hit=(hit_u != 0) & served,
        value=jnp.where(served[:, None], val_u, 0) if v else val_u,
        pos=jnp.where(served, pos_u, -1),
        evicted_key=jnp.where(served[:, None], ev_u[:, :kp], 0),
        evicted_val=jnp.where(served[:, None], ev_u[:, kp:kp + v], 0),
        evicted_valid=served & (ev_u[:, 0] != EMPTY_KEY),
    )
    return table, res, served


# ---------------------------------------------------------------------------
# Rounds path with the kernel as the row transition (bit-exactness oracle)
# ---------------------------------------------------------------------------

def kernel_rounds_update(cfg: MSLRUConfig, table, gsid, valid, qkeys, qvals,
                         max_rounds: int | None = None, use_kernel: bool = True,
                         block_b: int = 2048, interpret: bool | None = None,
                         ops=None, chain_live=None, costs=None):
    """``engine.batched_rounds_update`` with ``msl_access`` as the row op.

    Re-gathers/scatters all B rows from HBM once per conflict round — the
    O(rounds × B) behaviour the one-pass path eliminates.  The conflict
    serialization loop itself (valid masking, ``max_rounds`` capping, dummy
    row scatter) is the one in core/engine.py — only the row transition
    differs, so the two rounds engines cannot drift.
    """
    kp, v = cfg.key_planes, cfg.value_planes

    def row_op(rows, qk, qv, row_ops, live, qc):
        live = None if live is None else jnp.asarray(live, jnp.int32)
        new_rows, hit, pos, val, ev = msl_access(
            rows, qk, qv, cfg=cfg, ops=row_ops, chain_live=live, costs=qc,
            use_kernel=use_kernel, block_b=block_b, interpret=interpret)
        res = AccessResult(
            hit=hit.astype(bool), value=val, pos=pos,
            evicted_key=ev[:, :kp],
            evicted_val=ev[:, kp:kp + v],
            evicted_valid=(ev[:, 0] != EMPTY_KEY),
        )
        return new_rows, res

    return batched_rounds_update(cfg, table, gsid, valid, qkeys, qvals,
                                 max_rounds, row_op=row_op, ops=ops,
                                 chain_live=chain_live, costs=costs)


def make_kernel_batched_engine(cfg: MSLRUConfig, use_kernel: bool = True,
                               block_b: int = 2048, interpret: bool | None = None,
                               engine: str = "onepass",
                               max_rounds: int | None = None):
    """Batched engine with the row transition done by the Pallas kernel.

    ``engine="onepass"`` (default) delegates to the one factory in
    core/engine.py (single-pass conflict-aware pipeline, kernel-backed);
    ``engine="rounds"`` runs the shared serialization loop with
    ``msl_access`` as the row op.  Both are bit-exact w.r.t.
    ``make_sequential_engine`` for any ``max_rounds`` and any opcode mix.
    """
    assert engine in ("onepass", "rounds"), engine
    if engine == "onepass":
        return make_batched_engine(cfg, max_rounds, engine="onepass",
                                   use_kernel=use_kernel, block_b=block_b,
                                   interpret=interpret)

    @jax.jit
    def run_ops(table, qkeys, qvals, ops, costs):
        sids = set_index_for(cfg, qkeys)
        valid = jnp.ones(sids.shape, bool)
        table, res, _served = kernel_rounds_update(
            cfg, table, sids, valid, qkeys, qvals, max_rounds,
            use_kernel, block_b, interpret, ops=ops, costs=costs)
        return table, res

    @jax.jit
    def run_chain(table, qkeys, qvals, ops, chain_ids, costs):
        from repro.core.engine import chain_live_mask

        sids = set_index_for(cfg, qkeys)
        valid = jnp.ones(sids.shape, bool)
        live = chain_live_mask(cfg, table, qkeys, ops, chain_ids)
        table, res, _served = kernel_rounds_update(
            cfg, table, sids, valid, qkeys, qvals, max_rounds,
            use_kernel, block_b, interpret, ops=ops,
            chain_live=live.astype(jnp.int32), costs=costs)
        return table, res

    def run(table, qkeys, qvals, ops=None, chain_ids=None, costs=None):
        if ops is not None:
            ops = jnp.asarray(ops, jnp.int32)
        if costs is not None:
            costs = jnp.asarray(costs, jnp.int32)
        if chain_ids is not None:
            assert ops is not None, "chain_ids requires an ops vector"
            return run_chain(table, qkeys, qvals, ops,
                             jnp.asarray(chain_ids, jnp.int32), costs)
        return run_ops(table, qkeys, qvals, ops, costs)

    return run
