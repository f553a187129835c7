"""Query-stream engines for the multi-step LRU cache.

Two execution models, both built on the row ops in multistep.py:

* ``sequential`` — `lax.scan`, one query at a time.  Bit-exact oracle
  semantics (matches the pure-Python reference in policies.py); used for all
  hit-ratio science, mirroring the paper's single-thread measurements.

* ``batched`` — B queries per step, SPMD over the batch.  This is the TPU
  analogue of the paper's multi-core fine-grained locking: queries to
  *different* sets are independent (the set-associative property), so they
  process in parallel with no coordination.  Queries that collide on a set
  are serialized, and both conflict-resolution schemes are **bit-exact**
  w.r.t. the sequential engine:

  - ``engine="rounds"`` — round r applies the r-th query of every set (a
    bounded retry loop, the paper's spin-lock made data-parallel).  Every
    round is one full-width gather → row_access → scatter, so the work is
    O(rounds × B) HBM traffic; kept as the bit-exactness oracle.

  - ``engine="onepass"`` — the single-pass conflict-aware pipeline in
    kernels/ops.py: sort the batch by set id once, gather each distinct
    set's row once, resolve the intra-set duplicate chain on-chip (Pallas
    kernel or jnp mirror), scatter once.  O(B) HBM traffic regardless of
    the conflict structure — the hot path.

Opcodes
-------
Every engine takes an optional per-query ``ops`` vector (int32 OP_* codes;
omitted = all OP_ACCESS) and applies the selected operation branch-free —
a batch may freely mix the paper's §III.B operation set.  One normalized
result contract holds across the sequential, rounds, one-pass (jnp and
Pallas), and sharded engines, bit-for-bit:

    op            hit path mutation     miss path mutation   result fields
    ------------  --------------------  -------------------  ------------------
    OP_ACCESS     promote / upgrade     insert; may evict    hit, pos, value;
                                        the set-LRU victim   evicted_{key,val,
                                                             valid} on eviction
    OP_GET        promote / upgrade     none (no-op)         hit, pos, value
    OP_LOOKUP     none (read-only)      none                 hit, pos, value
    OP_DELETE     invalidate in place   none                 hit; pos = -1,
                  (no compaction)                            value = 0
    OP_CHAIN_GET  while the chain is all-hits: OP_GET.       hit = query is
                  Past the chain's first miss the row is     inside the
                  *downgraded*: no mutation, and it reports  longest-hit
                  a plain miss (hit False, pos -1, value 0)  prefix; value =
                  even if its key is resident.               its stored page
    OP_CHAIN_PUT  the mirror image: a no-op while its chunk index is inside
                  the chain's hit prefix, an OP_ACCESS (insert; may evict,
                  may absorb as a duplicate hit) past it.  Downgraded rows
                  report a plain miss.

Chain segments
--------------
``OP_CHAIN_GET``/``OP_CHAIN_PUT`` queries carry a ``chain_ids`` operand: a
(B,) int32 segment id in [0, B).  Chain rows with one id must form
contiguous runs in batch order — first the chain's CHAIN_GET run (its chunk
keys, prefix order), later (optionally) its CHAIN_PUT run (a *prefix* of
the same chunk keys, same order, with the staged value planes).  The engine
computes each chain's longest-hit prefix on device with a segmented
cumulative AND over the CHAIN_GET membership probes (``chain_exec_from_hits``)
and derives every row's execute mask from it; the i-th CHAIN_PUT row of a
chain pairs with the i-th CHAIN_GET row.  The probes observe the table *as
of the start of the batch*, so all membership-mutating rows (ACCESS,
DELETE, CHAIN_PUT) must come after every CHAIN_GET row in batch order —
GET/LOOKUP/downgraded rows never change membership, which is what makes the
batch-start probe exact.  One batch then performs the whole serving tick:
LOOKUP + longest-prefix scan + GET promotion + conditional inserts, with
bit-identical mutations and stats to issuing the LOOKUP/GET/ACCESS batches
separately.  (Lone divergence, by design: a chain whose every chunk hits
issues no tail re-insert, where the split path's host re-publish was
absorbed as one extra duplicate-hit promote.)

``value`` is the stored value planes of the hit item (on a miss it carries
the same deterministic garbage in every engine — the probed row's lane-0
value — so differential tests can compare outputs bitwise; downgraded chain
rows zero it).  For served queries ``evicted_key`` is the EMPTY_KEY
sentinel whenever nothing was evicted; queries dropped by a ``max_rounds``
cap (``served`` False) report all-zero evicted fields — test
``evicted_valid``, which is authoritative in both cases.

Cost plane and victim choice
----------------------------
With ``cfg.cost_planes = 1`` the table carries one extra int32 plane — the
item's re-prefill *cost* — and every engine accepts one extra per-query
operand:

    operand   shape  dtype  semantics
    --------  -----  -----  ------------------------------------------------
    costs     (B,)   int32  cost stored with the item if this query inserts
                            (OP_ACCESS / live CHAIN_PUT miss).  Ignored by
                            every other op; ``None`` inserts cost 0.

The cost plane rides the same rotate_insert as the key/value planes (a hit
promotes the item with its stored cost; nothing is recomputed in-table), so
the SIMD shuffle-only structure and the paper's zero-LRU-metadata property
are preserved — recency is still pure lane order.  The ONLY behavioural
change is the full-set victim choice in the put path: instead of blindly
evicting lane A-1, the engines evict the minimum-cost lane of the
eviction-candidate segment — the last vector, lanes [(M-1)*P, A-1] (the
whole set under ``set_lru``).  Tie-break rule: among equal-minimum lanes
the DEEPEST (highest) lane wins, which yields two guarantees relied on by
the differential tests:

* **Uniform-cost degeneration**: an all-equal cost plane (including the
  all-zero plane produced by ``costs=None``) picks exactly lane A-1 — the
  hit/pos/value/evicted streams are bit-identical to a ``cost_planes=0``
  run of the same queries, and the tables agree on every key/value plane.
* ``cfg.cost_planes = 0`` (the default) compiles literally the pre-cost
  code: no extra plane, no extra operand, no victim scan.

Eviction-candidate scope note: restricting the scan to the last vector (not
the whole set) keeps the paper's promotion ladder intact — an expensive item
only survives eviction pressure while its recency keeps it out of the last
vector, bounding how long a stale-but-expensive item can squat.

Sheds and canonical ordering (the sharded engine)
-------------------------------------------------
The sharded engine (core/sharded.py) adds two refinements to this
contract:

* ``served=False`` additionally marks queries SHED by a bounded per-peer
  all_to_all buffer (``cap``) — a shed query performs no mutation and
  reports a plain miss with zero evicted fields, exactly like a
  ``max_rounds`` drop.  A shed CHAIN_GET row breaks its chain's hit
  prefix (conservative under-serving, never a hole); a shed CHAIN_PUT row
  never inserts.  The serving tier does NOT fold sheds into misses: the
  ``ShardedCacheClient`` sheds whole chains atomically and
  ``PrefixCache``/``ServeEngine`` carry them into the next tick through a
  retry queue, counting ``shed``/``retried`` in the cache stats.

* **Canonical ordering guarantee**: with the optional ``order`` operand
  (caller-order ranks riding the all_to_all payload) the sharded engine
  stably sorts routed rows before the per-shard update, so the mutation
  order — including which of two same-tick duplicate inserts from
  DIFFERENT devices gets the inserted vs absorbed role — is exactly the
  sequential engine's.  Sharded tables are then bit-equal to this
  module's engines, not merely hit/miss-equivalent, and differential
  tests may compare tables across device counts.

* **Fragment placement** (``placement="split"``, the default under a
  bounded cap): a chain whose rows exceed any single slab's budget is
  decomposed into chunk FRAGMENTS packed greedily across healthy slabs
  (largest extent first, ties to the emptiest slab) against the same
  per-(slab, owner) load mirror the atomic pre-check uses.  Each
  fragment carries a fresh slab-local chain id and its rows stay a
  contiguous caller-order block, so ``chain_exec_from_hits``'s
  segmented prefix scan and global PUT pairing see ordinary
  independent chains — the contract above needs NO new engine
  semantics.  Only the un-placeable chunk SUFFIX sheds (consistently
  in both the GET and PUT islands), keeping served fragments
  prefix-closed: the serve tier reads the first shed row as the
  fragment boundary (``ChainServe.served_len``), serves the prefix
  this tick, and re-runs only the tail inserts at the next tick
  boundary.  Canonical caller-order ranks still ride every fragment,
  so tables remain bit-equal to the sequential engine under ANY
  placement — split is purely a shed-rate/goodput knob.  With fewer
  than 2 healthy slabs (or an unbounded cap and no faults) split
  degenerates to the atomic whole-chain protocol.

* **Owner-aware admission throttling**: the client folds each tick's
  admitted per-(slab, owner) counts into a per-home-shard pressure EWMA
  (owners implicated in capacity/degraded sheds pin to 1.0), exposed as
  ``chain_pressure(chain)``.  ``ServeEngine`` may consult it at
  admission (``throttle_threshold``) to defer NEW chains homing on a
  saturated shard in favour of requests servable now — never retries or
  fallbacks, starvation-exempt after ``max_throttle_ticks`` skips, and
  an all-hot queue still admits its front request, so throttling only
  REORDERS admissions and every request completes.

Elasticity (drain / re-insert and degraded shards)
--------------------------------------------------
The same two primitives carry the elastic operations, so resilience needs
no new table semantics:

* **Live resharding** (``ShardedCacheClient.reshard(D')``): every chain in
  the client's registry is drained from the old mesh with batched
  OP_CHAIN_GET sweeps — each chain survives as its longest-hit PREFIX
  (an evicted shallow chunk orphans the deeper resident chunks; their
  pages are returned for pool release, the entries are dropped) — and the
  surviving prefixes are re-inserted into a freshly initialised D' table
  with OP_CHAIN_PUT batches in canonical caller order.  Because
  ``num_sets`` is unchanged, every set receives at most its associativity
  of previously co-resident entries: the rebuild can never evict, and the
  rebuilt table is bit-equal to a COLD sequential engine fed the recorded
  canonical stream (``last_drain_stream``) — the same oracle relation as
  the per-tick ordering guarantee, lifted to whole-table rebuilds.
  ``num_sets`` need not divide D': the table tail is padded with EMPTY
  sets (``sets_per_shard`` = ceil) that no key can hash into.

* **Degraded shards** (``ShardedCacheClient.mark_degraded(s)``): a lost
  shard's sets are wiped to EMPTY host-side and the shard is excluded
  from placement; any chain that still homes a chunk there sheds — the
  SAME shed protocol as a capacity overflow (whole-chain under atomic
  placement; from the dead-homed chunk onward under split, since
  degraded slabs are excluded from fragment packing), feeding the same
  serve-tier retry queue, so the serving invariants (no holes, no
  partial mutations) carry over unchanged.  Orphaned pages are reported
  once for pool release.  A chain that keeps shedding past
  ``max_shed_retries`` (permanently homed on a dead shard) is served as
  a PLAIN prefill — counted in ``fallbacks`` with its latency charged
  from the ORIGINAL submit tick — never dropped.

Megastep decode (the serving tier's launch amortization)
--------------------------------------------------------
The serving tier (serving/engine.py) amortizes its per-token host
round-trip the same way this module amortizes per-item bookkeeping:
``ServeEngine(decode_mode="megastep")`` fuses K pure-decode ticks into
ONE jitted ``lax.scan`` — tokens accumulate in a (K, slots) device
buffer, per-row EOS/max_new masks freeze finished rows on-chip, and the
host resyncs once per window.  The contract pieces the cache engine
relies on:

* **Window-safety invariant**: a window opens only on a tick with no
  admissions, borrower waves, pending tail inserts, or due fault events,
  and K never exceeds the smallest horizon at which a host-visible event
  COULD occur — min over (per-slot remaining budgets when the queue
  waits, ticks until the next scheduled ``FaultEvent``, the
  ``max_window`` compile cap).  Cache-engine calls (admission serve/
  insert batches) therefore land on exactly the oracle's tick
  boundaries: a fused window never reorders, merges, or delays a cache
  mutation.

* **Oracle equivalence**: tokens, tick counts, service percentiles,
  ``fault_log`` stamps and the prefix cache's hit/evict streams are
  bit-identical to per-tick ``decode_mode="inflight"`` (kept as the
  equivalence baseline; CI gates parity via serve_bench --check and
  tests/test_megastep_decode.py).

* **Stats glossary**: ``megastep_windows`` / ``mean_window`` (fused
  windows and their mean tick span), ``host_syncs`` (host<->device
  barriers; one per window vs one per tick), ``launches_per_token``
  (active rows per emitted token — falls toward 1/K), and the
  ``drain_*`` mirrors restricted to ticks where nothing queues (the
  regime long windows live in).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.multistep import (  # noqa: F401  (OP_* re-exported)
    MSLRUConfig,
    OP_ACCESS,
    OP_CHAIN_GET,
    OP_CHAIN_PUT,
    OP_DELETE,
    OP_GET,
    OP_LOOKUP,
    row_access,
    row_apply,
    row_lookup,
    set_columns,
    set_index_for,
    set_rows,
)

__all__ = [
    "OP_ACCESS",
    "OP_GET",
    "OP_DELETE",
    "OP_LOOKUP",
    "OP_CHAIN_GET",
    "OP_CHAIN_PUT",
    "SeqOutputs",
    "make_sequential_engine",
    "make_batched_engine",
    "make_chunked_stream_runner",
    "make_conflict_update",
    "chain_exec_from_hits",
    "chain_live_mask",
    "group_offsets",
    "sorted_group_ranks",
    "batched_rounds_update",
]


class SeqOutputs(NamedTuple):
    hit: jnp.ndarray            # (N,) bool
    pos: jnp.ndarray            # (N,) int32 flat lane of hit (-1 miss); //P = vector (Fig. 12)
    value: jnp.ndarray          # (N, V) value of the hit item (garbage on miss)
    evicted_key: jnp.ndarray    # (N, KP)
    evicted_val: jnp.ndarray    # (N, V) value planes of the evicted item
    evicted_valid: jnp.ndarray  # (N,) bool


def make_sequential_engine(cfg: MSLRUConfig, with_ops: bool = False):
    """Returns jit'd run(table, qkeys (N,KP), qvals (N,V) [, opcodes (N,)]).

    Scans the query stream one element at a time; each step touches exactly
    one set row (dynamic_slice / dynamic_update_slice), the JAX rendering of
    the paper's single-threaded loop.  ``with_ops=True`` adds the per-query
    opcode argument (OP_ACCESS/OP_GET/OP_DELETE/OP_LOOKUP, plus the chain
    ops when the optional ``chain_ids`` argument is passed — the chain
    execute mask is precomputed against the scan's start table, matching
    the batch-start probe semantics of the batched engines).
    """
    a, c = cfg.assoc, cfg.planes

    def one(view, qkey, qval, op, live, cost):
        # the scan carries the (C*A, S) column view (see set_columns), so
        # each step reads and writes one set column in the table's layout
        sid = set_index_for(cfg, qkey[None])[0]
        col = jax.lax.dynamic_slice(view, (0, sid), (c * a, 1))
        rows = set_rows(col, a, c)
        # row_apply is the single op-dispatch used by every engine, so the
        # sequential oracle and the batched paths cannot drift per-op.
        new_rows, res = row_apply(cfg, rows, qkey[None], qval[None], op[None],
                                  chain_live=live[None], costs=cost[None])
        view = jax.lax.dynamic_update_slice(view, set_columns(new_rows),
                                            (0, sid))
        return view, (res.hit[0], res.pos[0], res.value[0],
                       res.evicted_key[0], res.evicted_val[0],
                       res.evicted_valid[0])

    def scan(table, qkeys, qvals, opcodes, live, costs):
        if costs is None:
            costs = jnp.zeros(qkeys.shape[0], jnp.int32)

        def step(tbl, xs):
            k, v, op, lv, cc = xs
            return one(tbl, k, v, op, lv, cc)
        view, outs = jax.lax.scan(
            step, set_columns(table), (qkeys, qvals, opcodes, live, costs))
        return set_rows(view, a, c), SeqOutputs(*outs)

    if with_ops:
        @jax.jit
        def run_ops(table, qkeys, qvals, opcodes, costs):
            live = jnp.ones(opcodes.shape, bool)
            return scan(table, qkeys, qvals, opcodes, live, costs)

        @jax.jit
        def run_chain(table, qkeys, qvals, opcodes, chain_ids, costs):
            live = chain_live_mask(cfg, table, qkeys, opcodes, chain_ids)
            return scan(table, qkeys, qvals, opcodes, live, costs)

        def run(table, qkeys, qvals, opcodes, chain_ids=None, costs=None):
            if costs is not None:
                costs = jnp.asarray(costs, jnp.int32)
            if chain_ids is not None:
                return run_chain(table, qkeys, qvals, opcodes,
                                 jnp.asarray(chain_ids, jnp.int32), costs)
            return run_ops(table, qkeys, qvals, opcodes, costs)
    else:
        @jax.jit
        def run(table, qkeys, qvals):
            ones = jnp.ones(qkeys.shape[0], bool)
            ops0 = jnp.full(qkeys.shape[0], OP_ACCESS, jnp.int32)
            return scan(table, qkeys, qvals, ops0, ones, None)

    return run


def sorted_group_ranks(sorted_ids: jnp.ndarray):
    """(firsts, offset) for an already-sorted id array.

    firsts[i] marks group heads; offset[i] is the rank within the group.
    Shared core of ``group_offsets`` and the one-pass prologue in
    kernels/ops.py — one implementation of the rank derivation, two sorts.
    """
    b = sorted_ids.shape[0]
    i = jnp.arange(b, dtype=jnp.int32)
    firsts = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    group_start = jax.lax.cummax(jnp.where(firsts, i, -1))
    return firsts, (i - group_start).astype(jnp.int32)


def group_offsets(ids: jnp.ndarray) -> jnp.ndarray:
    """offset[i] = #{j < i : ids[j] == ids[i]} (rank within its id group)."""
    b = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    _, off_sorted = sorted_group_ranks(ids[order])
    return jnp.zeros((b,), jnp.int32).at[order].set(off_sorted)


def chain_exec_from_hits(ops, chain_ids, raw_hit, valid=None):
    """(B,) bool execute mask for CHAIN_GET/CHAIN_PUT rows (see module doc).

    raw_hit (B,) bool: batch-start membership of each query's key (any
    value for non-chain rows).  CHAIN_GET row i executes iff every
    CHAIN_GET row at or before i in its (contiguous) chain run was a raw
    hit — the segmented cumulative AND, i.e. the longest-hit prefix.  The
    o-th CHAIN_PUT row of a chain executes iff o >= the chain's hit length
    (the insert half of a fused serving tick).  ``chain_ids`` must lie in
    [0, B).  An INVALID chain row (``valid`` False — e.g. overflow-dropped
    in the sharded engine) counts as a miss: it breaks its chain's hit
    prefix, so nothing past a dropped row can promote or report a hit
    (conservative under-serving, never a hole in the prefix); invalid
    CHAIN_PUT rows still occupy their pairing slot but never execute.
    Pure jnp on (B,)-vectors — no table access — so the sharded engine can
    run it on the query-owning device from routed-back probes.
    """
    b = ops.shape[0]
    is_get = ops == OP_CHAIN_GET
    is_put = ops == OP_CHAIN_PUT
    if valid is None:
        valid = jnp.ones(ops.shape, bool)
    idx = jnp.arange(b, dtype=jnp.int32)
    # non-chain rows break segment runs (unique negative ids); invalid
    # chain rows keep their id so the run is NOT split around them
    cid = jnp.where(is_get | is_put, chain_ids, -1 - idx)
    firsts = jnp.concatenate([jnp.ones((1,), bool), cid[1:] != cid[:-1]])
    bad = jnp.where(is_get & ~(raw_hit & valid), idx, b).astype(jnp.int32)

    def seg_min(a, c):
        fa, va = a
        fc, vc = c
        return fa | fc, jnp.where(fc, vc, jnp.minimum(va, vc))

    _, run_min = jax.lax.associative_scan(seg_min, (firsts, bad))
    get_exec = is_get & valid & (run_min > idx)   # no miss at or before me

    cid_c = jnp.clip(chain_ids, 0, b - 1)
    hitlen = jnp.zeros((b,), jnp.int32).at[cid_c].add(
        jnp.where(get_exec, 1, 0))
    occ = group_offsets(jnp.where(is_put, cid_c, b + idx))
    put_exec = is_put & valid & (occ >= hitlen[cid_c])
    return get_exec | put_exec


def chain_live_mask(cfg: MSLRUConfig, table, qkeys, ops, chain_ids,
                    valid=None):
    """Device-side longest-prefix scan: probe + ``chain_exec_from_hits``.

    Probes every query's key against ``table`` (one (B, A, C) row read —
    membership only, no mutation) and reduces the chain-row hits to the
    per-row execute mask.  Exact because CHAIN_GET rows precede every
    membership-mutating row (module contract), so the batch-start
    membership equals the at-execution membership for all of them.
    """
    sid = set_index_for(cfg, qkeys)
    rows = jnp.take(table, sid, axis=0)
    raw_hit, _, _ = row_lookup(cfg, rows, qkeys)
    return chain_exec_from_hits(ops, chain_ids, raw_hit, valid)


def batched_rounds_update(cfg: MSLRUConfig, table, gsid, valid, qkeys, qvals,
                          max_rounds: int | None = None, row_op=None,
                          ops=None, chain_live=None, costs=None):
    """Exact multi-query update: serialize same-set queries across rounds.

    table: (S, A, C); gsid: (B,) set id per query (entries with ``valid`` False
    are ignored); ``ops`` (B,) optional per-query opcodes (default all
    OP_ACCESS); ``chain_live`` (B,) optional execute mask for
    CHAIN_GET/CHAIN_PUT rows (precomputed by ``chain_live_mask``); returns
    (table, AccessResult, served).  Bit-exact w.r.t. processing the valid
    queries sequentially in batch order, because queries to distinct sets
    commute and round r applies exactly the r-th query of each set.
    ``max_rounds`` bounds latency; excess queries are dropped (reported via
    res.hit=False and the served mask = offset < rounds).

    ``row_op(rows, qkeys, qvals, ops, chain_live, costs) -> (new_rows,
    AccessResult)`` is the batch row transition; defaults to ``row_apply``
    (``row_access`` when ``ops`` is None — the ACCESS-only fast path
    compiles no op selects).  kernels/ops.py passes the Pallas kernel here
    so both backends share this serialization loop.  ``costs`` (B,) is the
    optional per-query insert-cost operand (see "Cost plane and victim
    choice" in the module docstring).
    """
    if row_op is None:
        if ops is None:
            def row_op(rows, qk, qv, _ops, _live, qc):
                return row_access(cfg, rows, qk, qv, costs=qc)
        else:
            def row_op(rows, qk, qv, row_ops, live, qc):
                return row_apply(cfg, rows, qk, qv, row_ops, chain_live=live,
                                 costs=qc)
    s = cfg.num_sets if table.shape[0] == cfg.num_sets else table.shape[0]
    b = gsid.shape[0]
    gsid = jnp.where(valid, gsid, s)                  # sentinel group
    offset = group_offsets(jnp.where(valid, gsid, s + 1 + jnp.arange(b)))
    # (invalid queries get unique ids so they never occupy a real rank)
    n_rounds = jnp.max(jnp.where(valid, offset, -1)) + 1
    if max_rounds is not None:
        n_rounds = jnp.minimum(n_rounds, max_rounds)

    padded = jnp.concatenate([table, jnp.zeros((1,) + table.shape[1:], table.dtype)])
    res0 = AccessResultZero(cfg, b)

    def cond(carry):
        r, _, _ = carry
        return r < n_rounds

    def body(carry):
        r, padded, acc = carry
        rows = jnp.take(padded, gsid, axis=0)
        new_rows, res = row_op(rows, qkeys, qvals, ops, chain_live, costs)
        sel = (offset == r) & valid
        scatter_id = jnp.where(sel, gsid, s)          # losers pile onto dummy row
        padded = padded.at[scatter_id].set(new_rows)
        acc = jax.tree.map(
            lambda a, n: jnp.where(sel.reshape((b,) + (1,) * (n.ndim - 1)), n, a), acc, res)
        return r + 1, padded, acc

    _, padded, acc = jax.lax.while_loop(cond, body, (jnp.int32(0), padded, res0))
    served = valid & (offset < n_rounds)
    acc = acc._replace(hit=acc.hit & served, evicted_valid=acc.evicted_valid & served)
    return padded[:-1], acc, served


def AccessResultZero(cfg: MSLRUConfig, b: int):
    from repro.core.multistep import AccessResult
    return AccessResult(
        hit=jnp.zeros((b,), bool),
        value=jnp.zeros((b, cfg.value_planes), jnp.int32),
        pos=jnp.full((b,), -1, jnp.int32),
        evicted_key=jnp.zeros((b, cfg.key_planes), jnp.int32),
        evicted_val=jnp.zeros((b, cfg.value_planes), jnp.int32),
        evicted_valid=jnp.zeros((b,), bool),
    )


def make_conflict_update(cfg: MSLRUConfig, engine: str = "rounds",
                         max_rounds: int | None = None,
                         use_kernel: bool = False, block_b: int = 2048,
                         interpret: bool | None = None):
    """Bind the chosen conflict scheme to ``update(table, gsid, valid,
    qkeys, qvals, ops=None, chain_live=None, costs=None) -> (table,
    AccessResult, served)``.

    The single dispatch point for the ``engine`` switch — the batched and
    sharded engines both resolve through here so the option set, the
    deferred kernels import, and the rounds-is-XLA-only guard live once.
    """
    assert engine in ("rounds", "onepass"), engine
    if engine == "onepass":
        from repro.kernels.ops import onepass_update  # deferred: kernels -> core

        def update(table, gsid, valid, qkeys, qvals, ops=None,
                   chain_live=None, costs=None):
            return onepass_update(cfg, table, gsid, valid, qkeys, qvals,
                                  max_rounds, use_kernel, block_b, interpret,
                                  ops=ops, chain_live=chain_live, costs=costs)
    else:
        assert not use_kernel, (
            "engine='rounds' here is XLA-only; the kernel-backed rounds path "
            "lives in repro.kernels.ops.make_kernel_batched_engine")

        def update(table, gsid, valid, qkeys, qvals, ops=None,
                   chain_live=None, costs=None):
            return batched_rounds_update(cfg, table, gsid, valid, qkeys,
                                         qvals, max_rounds, ops=ops,
                                         chain_live=chain_live, costs=costs)
    return update


def make_batched_engine(cfg: MSLRUConfig, max_rounds: int | None = None,
                        engine: str = "rounds", use_kernel: bool = False,
                        block_b: int = 2048, interpret: bool | None = None):
    """Returns run(table, qkeys (B,KP), qvals (B,V), ops=None,
    chain_ids=None) -> (table, result).

    Exact (sequential-equivalent) unless ``max_rounds`` caps the conflict
    serialization.  ``engine`` selects the conflict scheme: ``"rounds"``
    (per-round gather/scatter, the oracle) or ``"onepass"`` (single
    gather/scatter with on-chip chain resolution; ``use_kernel`` routes the
    chain loop through the Pallas kernel instead of its jnp mirror).
    ``ops`` is an optional (B,) opcode vector (see the module docstring);
    omitted means all OP_ACCESS.  ``chain_ids`` (B,) enables the fused
    chain ops (CHAIN_GET/CHAIN_PUT): the longest-prefix scan runs on device
    inside the same jit'd call — one engine invocation per serving tick.
    """
    update = make_conflict_update(cfg, engine, max_rounds, use_kernel,
                                  block_b, interpret)

    @jax.jit
    def run_ops(table, qkeys, qvals, ops, costs):
        # ops=None is a distinct (static) pytree structure: the ACCESS-only
        # specialization compiles with no opcode operand at all (likewise
        # costs=None compiles no cost operand).
        sids = set_index_for(cfg, qkeys)
        valid = jnp.ones(sids.shape, bool)
        table, res, _served = update(table, sids, valid, qkeys, qvals, ops,
                                     costs=costs)
        return table, res

    @jax.jit
    def run_chain(table, qkeys, qvals, ops, chain_ids, costs):
        sids = set_index_for(cfg, qkeys)
        valid = jnp.ones(sids.shape, bool)
        live = chain_live_mask(cfg, table, qkeys, ops, chain_ids)
        table, res, _served = update(table, sids, valid, qkeys, qvals, ops,
                                     chain_live=live, costs=costs)
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


def make_chunked_stream_runner(cfg: MSLRUConfig, batch: int,
                               engine: str = "rounds", **engine_kwargs):
    """Throughput driver: scan the batched engine over a (N//batch, batch) stream."""
    run_batch = make_batched_engine(cfg, engine=engine, **engine_kwargs)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_stream(table, qkeys, qvals, ops, costs):
        # ops=None (a static pytree structure) scans the ACCESS-only path
        n = qkeys.shape[0] // batch * batch
        qk = qkeys[:n].reshape(-1, batch, qkeys.shape[-1])
        qv = qvals[:n].reshape(-1, batch, qvals.shape[-1])
        qo = None if ops is None else ops[:n].reshape(-1, batch)
        qc = None if costs is None else costs[:n].reshape(-1, batch)

        def step(tbl, xs):
            k, v, o, cc = xs
            tbl, res = run_batch(tbl, k, v, o, costs=cc)
            return tbl, jnp.sum(res.hit)

        table, hits = jax.lax.scan(step, table, (qk, qv, qo, qc))
        return table, jnp.sum(hits)

    def run(table, qkeys, qvals, ops=None, costs=None):
        if ops is not None:
            ops = jnp.asarray(ops, jnp.int32)
        if costs is not None:
            costs = jnp.asarray(costs, jnp.int32)
        return run_stream(table, qkeys, qvals, ops, costs)

    return run
