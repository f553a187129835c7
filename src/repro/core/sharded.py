"""Distributed multi-step LRU cache: sets sharded across mesh devices.

The paper parallelizes across cores with per-set locks.  The SPMD analogue:
shard the set table over devices and route each query to the device that owns
its set, via ``all_to_all`` — the same fixed-capacity dispatch pattern as MoE
token routing (GShard).  Different shards never contend — precisely the
set-associative independence argument the paper makes for its fine-grained
locks, lifted from cores to chips.

Capacity semantics: each device sends at most ``cap`` queries to each peer
per step.  Overflow queries (hash-hot shards) are *shed for this step* and
reported via the ``served`` mask — the shed-load analogue of a busy memcached
shard.  Shed queries are NOT silent forced misses at the serving tier: the
``ShardedCacheClient`` sheds whole chains atomically (host-side capacity
pre-check mirroring the device route ranks) and the serving tier carries
them into the next tick through a retry queue (``PrefixCache`` /
``ServeEngine``); the shed rate vs buffer-memory vs hit-ratio trade-off is a
benchmark output (benchmarks/sharded_bench.py -> BENCH_sharded.json).

Canonical cross-shard ordering: queries arrive at their owner shard in
(source-device, send-slot) order, which for the plain engine equals global
batch order (slabs are contiguous).  When the caller's packing permutes
that order (``ShardedCacheClient`` deals whole chains round-robin onto
slabs), an optional ``order`` operand carries each query's caller-order
rank as one extra all_to_all plane and ``local_fn`` stably sorts the routed
rows by it before the table update — so same-tick duplicate inserts from
different devices always resolve their absorbed/inserted roles exactly as
the sequential engine would, and sharded tables are *bit-equal* to the
local engine, not merely equivalent.

The routing/update pipeline per device:
  1. hash local queries -> (owner shard, slot within send buffer)
  2. all_to_all send buffers (D, cap, planes)
  3. batched update on the local table shard (padded queries masked) — the
     conflict scheme is selectable: ``engine="rounds"`` re-gathers the shard
     per conflict round; ``engine="onepass"`` sorts once and resolves
     duplicate chains on-chip (kernels/ops.onepass_update), one
     gather/scatter per step
  4. all_to_all results back; unpack by (owner, slot)

Chain ops (the fused serving tick) add a membership pre-phase: the keys are
routed once, each owner shard answers a read-only probe, the hits route
back, and the *query-owning* device runs the segmented longest-prefix scan
over its local chains (``engine.chain_exec_from_hits``) — chains never
straddle devices, so the scan is local.  The derived execute mask then
rides the normal phase-2 payload as one extra int32 plane next to the
opcode, and the evicted key/value planes ride the result payload back (the
serving tier recycles evicted KV pages).  Everything happens inside ONE
jit'd call: four all_to_alls, zero host round-trips.
``ShardedCacheClient`` packages this as a host-side drop-in backend for
``serving.prefix_cache.PrefixCache``: it repacks a tick's chains into
per-device slabs (whole chains per slab, slab-local chain ids) and unpacks
the results back to request order.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.engine import chain_exec_from_hits, make_conflict_update
from repro.core.invector import EMPTY_KEY
from repro.core.multistep import (AccessResult, MSLRUConfig, OP_ACCESS,
                                  OP_CHAIN_GET, OP_CHAIN_PUT, OP_LOOKUP,
                                  init_table, row_lookup, set_index_for)
from repro.launch.mesh import shard_map_compat as _shard_map

__all__ = ["make_sharded_engine", "shard_table", "ShardedCacheClient",
           "per_peer_cap", "sets_per_shard"]

_INT32_MAX = np.int32(2**31 - 1)


def sets_per_shard(num_sets: int, ndev: int) -> int:
    """Sets owned by each shard: ``ceil(num_sets / ndev)``.

    ``num_sets`` is a power of two but an elastic mesh is whatever survived
    — 7 hosts own ``ceil(S/7)`` sets each and the table is padded with
    EMPTY sets up to ``ndev * s_local`` rows (``shard_table``).  The route
    math is unchanged: ``owner = sid // s_local`` and ``local = sid %
    s_local`` are exact for ``sid = owner * s_local + local``, and no key
    ever hashes into the padded tail (``set_index_for`` yields sids below
    ``num_sets``)."""
    return -(-num_sets // ndev)


def per_peer_cap(cap, q_local: int, ndev: int) -> int:
    """Resolve the per-peer send-buffer depth for a local slab of
    ``q_local`` queries — the single source of truth shared by the engine's
    route and the ``ShardedCacheClient`` host-side shed pre-check.

    ``cap`` semantics:
      * ``"full"`` — the whole slab (no shed possible; unbounded buffers),
      * ``float``  — multiplier over the *expected* per-peer load
        ``q_local / ndev`` (uniform hashing), e.g. ``2.0`` = 2×expected,
      * ``int``    — a fixed per-peer depth,
      * ``None``   — the legacy default, 2×expected.
    """
    if cap == "full":
        return q_local
    if cap is None:
        return max(1, (2 * q_local) // ndev)
    if isinstance(cap, float):
        return max(1, math.ceil(cap * q_local / ndev))
    return max(1, int(cap))


def shard_table(table, mesh, axis: str = "cache"):
    """Place a (S, A, C) table with sets sharded over ``axis``.

    When ``ndev`` does not divide S (elastic meshes — e.g. 7 survivors of
    8), the table is padded with EMPTY sets to ``ndev * ceil(S/ndev)`` rows
    so every shard owns the same row count; the padded sets live on the
    last shard and are unreachable (no key hashes there).  Host-side reads
    must slice back to ``[:num_sets]``."""
    ndev = mesh.shape[axis]
    s = table.shape[0]
    pad = ndev * sets_per_shard(s, ndev) - s
    if pad:
        empty = jnp.zeros((pad,) + table.shape[1:], table.dtype)
        empty = empty.at[:, :, 0].set(EMPTY_KEY)
        table = jnp.concatenate([jnp.asarray(table), empty])
    return jax.device_put(
        table, jax.NamedSharding(mesh, P(axis, None, None)))


def make_sharded_engine(cfg: MSLRUConfig, mesh, axis: str = "cache", cap: int | None = None,
                        max_rounds: int | None = None, engine: str = "rounds",
                        use_kernel: bool = False, block_b: int = 2048,
                        interpret: bool | None = None):
    """Build run(table, qkeys, qvals, ops=None, chain_ids=None).

    table: (S, A, C) sharded over sets on ``axis``.
    qkeys: (Q, KP), qvals: (Q, V) sharded over queries on ``axis``.
    ops:   (Q,) optional per-query opcodes; the opcode rides the all_to_all
           payload as one extra int32 plane.  ``None`` routes the ACCESS-only
           specialization (no ops plane, no opcode selects — the legacy
           hot path, compiled separately).
    chain_ids: (Q,) optional chain segment ids for CHAIN_GET/CHAIN_PUT rows
           (requires ``ops``).  Ids must be *device-local*: in [0, Q/ndev),
           with every chain's rows confined to one device's query slab (see
           ``ShardedCacheClient``).  Chain mode adds the membership
           pre-phase + the execute-mask plane, and extends the result with
           the evicted value planes.
    order: (Q,) optional int32 caller-order rank per query (requires
           ``ops``).  One extra int32 plane rides the all_to_all payload
           and the routed rows are stably sorted by it before the local
           update, making the cross-shard mutation order canonical: the
           sharded table is then bit-equal to the sequential engine fed the
           queries in ``order`` rank order, regardless of how the caller
           packed them into slabs.  ``None`` keeps the natural
           (source-device, slot) arrival order — already canonical when
           slabs are contiguous caller-order blocks.
    costs: (Q,) optional int32 per-query insert costs (requires
           ``cfg.cost_planes``); one extra int32 all_to_all plane riding
           between the execute mask and the order rank.  Stored into the
           cost plane when the query inserts and read back by the
           cost-aware victim choice (see core/engine.py, "Cost plane and
           victim choice").  ``None`` inserts cost 0.
    cap:   per-peer send-buffer depth (see ``per_peer_cap``): ``"full"``
           sizes it to the whole local slab (no shed possible), a float is
           a multiplier over the expected per-peer load ``Q/ndev²``, an int
           a fixed depth, ``None`` = 2×expected.  Sizing heuristic: for
           uniformly hashed keys the per-peer load is ≈Binomial(q, 1/ndev),
           so ``cap=2.0`` (2×expected) sheds <0.1% of uniform traffic while
           shrinking the all_to_all buffers ndev/2×; skewed traffic
           (same-tick duplicate chains concentrate on one home shard)
           sheds more — measure with benchmarks/sharded_bench.py, and rely
           on the serving tier's retry queue to convert sheds into next-tick
           service instead of forced misses.
    Returns (table, hit, val, served) — chain mode appends
    (evicted_val (Q, max(V,1)), evicted_valid (Q,)).
    hit:   (Q,) bool — False for misses AND overflow-shed queries.
    served:(Q,) bool — False only for overflow-shed queries.
    engine: per-shard conflict scheme — "rounds" (gather/scatter per round)
    or "onepass" (sort once, on-chip chains; ``use_kernel`` additionally
    routes the chain loop through the Pallas kernel).
    """
    update = make_conflict_update(cfg, engine, max_rounds, use_kernel,
                                  block_b, interpret)
    ndev = mesh.shape[axis]
    # elastic meshes: ndev need not divide num_sets — shards own
    # ceil(S/ndev) sets each and shard_table pads the tail with EMPTY sets
    s_local = sets_per_shard(cfg.num_sets, ndev)
    kp, v = cfg.key_planes, cfg.value_planes
    ve = max(v, 1)

    def _k_for(q_local):
        return per_peer_cap(cap, q_local, ndev)

    def _route(qkeys, extra_planes, k):
        """Pack queries into (ndev, k, pc) send buffers and all_to_all them.

        Returns (routed rows (ndev*k, pc), didx, sidx, served) — didx/sidx
        address the slot each local query landed in, for the result unpack.
        """
        with jax.named_scope("msl.route"):
            sid = set_index_for(cfg, qkeys)                 # (q,) set id
            owner = sid // s_local                          # destination shard
            # slot within the per-destination send buffer = rank among
            # same-owner queries
            onehot = (owner[:, None] == jnp.arange(ndev)[None, :])
            rank = jnp.cumsum(onehot, axis=0)               # 1-based rank
            slot = jnp.sum(jnp.where(onehot, rank - 1, 0), axis=1)
            served = slot < k                               # overflow -> shed

            payload = jnp.concatenate([qkeys] + extra_planes, axis=-1)
            pc = payload.shape[-1]
            # one SACRIFICIAL column (k) catches every overflow row's
            # scatter: clamping overflow to a real slot would clobber the
            # admitted row that legitimately occupies it (silently dropping
            # its op while it reports served=True) — the dump column is
            # sliced off before the all_to_all, so duplicate overflow
            # scatters there are harmless
            send = jnp.full((ndev, k + 1, pc), EMPTY_KEY, jnp.int32)
            send = send.at[owner, jnp.where(served, slot, k)].set(payload)
            didx = owner
            sidx = jnp.where(served, slot, k - 1)           # clamp: unpack read
            recv = jax.lax.all_to_all(send[:, :k], axis, split_axis=0,
                                      concat_axis=0, tiled=True)
            return recv.reshape(ndev * k, pc), didx, sidx, served

    def _route_back(planes, didx, sidx, k):
        """all_to_all per-routed-row result planes back to the sources."""
        with jax.named_scope("msl.route"):
            back = jax.lax.all_to_all(
                jnp.concatenate(planes, axis=-1).reshape(ndev, k, -1),
                axis, split_axis=0, concat_axis=0, tiled=True)
            # back[d, j] = result of the query I sent to shard d in slot j
            return back[didx, sidx]

    def local_fn(table, qkeys, qvals, ops=None, chain_ids=None, order=None,
                 costs=None):
        # table (s_local, A, C); qkeys (q_local, KP); qvals (q_local, V)
        q_local = qkeys.shape[0]
        k = _k_for(q_local)
        chain_mode = chain_ids is not None

        live_planes = []
        if chain_mode:
            # membership pre-phase: owners answer a read-only probe, the
            # query-owning device runs the segmented longest-prefix scan
            # over its (local) chains.  No mutation happens before phase 2,
            # so the probe is the batch-start membership the chain
            # contract requires, globally.  (Read-only, so the canonical
            # ``order`` sort is not needed here.)
            rq, didx, sidx, served = _route(qkeys, [], k)
            with jax.named_scope("msl.order"):
                p_keys = rq[:, :kp]
                p_valid = p_keys[:, 0] != EMPTY_KEY
                p_rows = jnp.take(table,
                                  set_index_for(cfg, p_keys) % s_local,
                                  axis=0)
                p_hit, _, _ = row_lookup(cfg, p_rows, p_keys)
                p_planes = [(p_hit & p_valid).astype(jnp.int32)[:, None]]
            hit_home = _route_back(p_planes, didx, sidx, k)
            with jax.named_scope("msl.order"):
                raw_hit = (hit_home[:, 0] != 0) & served
                live = chain_exec_from_hits(ops, chain_ids, raw_hit,
                                            valid=served)
                live_planes = [live.astype(jnp.int32)[:, None]]

        with jax.named_scope("msl.route"):
            planes = ([qvals] + ([] if ops is None else [ops[:, None]])
                      + live_planes
                      + ([] if costs is None else [costs[:, None]])
                      + ([] if order is None else [order[:, None]]))
        rq, didx, sidx, served = _route(qkeys, planes, k)
        with jax.named_scope("msl.route"):
            r_keys, r_vals = rq[:, :kp], rq[:, kp: kp + v]
            valid = r_keys[:, 0] != EMPTY_KEY
            r_ops = (None if ops is None
                     else jnp.where(valid, rq[:, kp + v], OP_ACCESS))
            r_live = (jnp.where(valid, rq[:, kp + v + 1], 0)
                      if chain_mode else None)
            cost_col = (kp + v + (0 if ops is None else 1)
                        + (1 if chain_mode else 0))
            r_cost = (None if costs is None
                      else jnp.where(valid, rq[:, cost_col], 0))

        with jax.named_scope("msl.order"):
            lsid = set_index_for(cfg, r_keys) % s_local
        if order is not None:
            # canonical arrival order: stably sort the routed rows by their
            # caller-order rank before the update, so same-set duplicate
            # chains resolve exactly as the sequential engine would no
            # matter which source device each row came from; unsort the
            # results so the route-back addressing stays (didx, sidx).
            with jax.named_scope("msl.order"):
                ord_col = cost_col + (0 if costs is None else 1)
                r_ord = jnp.where(valid, rq[:, ord_col], _INT32_MAX)
                perm = jnp.argsort(r_ord, stable=True)
                inv = jnp.argsort(perm)
                rows = [None if x is None else x[perm] for x in
                        (lsid, valid, r_keys, r_vals, r_ops, r_live, r_cost)]
            table, res, _served = update(table, *rows[:5],
                                         chain_live=rows[5], costs=rows[6])
            with jax.named_scope("msl.order"):
                res = jax.tree.map(lambda a: a[inv], res)
        else:
            # exact local update (same conflict schemes as the batched
            # engine); arrival order (source-device, slot) is already the
            # caller's slab-major order
            table, res, _served = update(table, lsid, valid, r_keys, r_vals,
                                         r_ops, chain_live=r_live,
                                         costs=r_cost)

        with jax.named_scope("msl.route"):
            back = [(res.hit & valid).astype(jnp.int32)[:, None],
                    (res.value if v else
                     jnp.zeros((res.value.shape[0], 1), jnp.int32))]
            if chain_mode:
                back += [(res.evicted_val if v else
                          jnp.zeros((res.value.shape[0], 1), jnp.int32)),
                         (res.evicted_valid & valid).astype(jnp.int32)[:, None]]
        home = _route_back(back, didx, sidx, k)
        with jax.named_scope("msl.route"):
            # shed rows' unpack reads a clamped slot (another row's result):
            # zero every plane for them — the contract is a plain miss with
            # all-zero fields when served is False
            zero = served[:, None]
            my_hit = home[:, 0].astype(bool) & served
            if chain_mode:
                return (table, my_hit,
                        jnp.where(zero, home[:, 1: 1 + ve], 0), served,
                        jnp.where(zero, home[:, 1 + ve: 1 + 2 * ve], 0),
                        (home[:, 1 + 2 * ve] != 0) & served)
            return table, my_hit, jnp.where(zero, home[:, 1:], 0), served

    out_specs = (P(axis, None, None), P(axis), P(axis, None), P(axis))
    out_specs_chain = out_specs + (P(axis, None), P(axis))
    base_in = (P(axis, None, None), P(axis, None), P(axis, None))
    # jit'd shard_map variants built lazily, keyed by which optional
    # operands (ops / chain_ids / order / costs) are present — each key is
    # its own compiled specialization with exactly those all_to_all planes
    variants: dict = {}

    def _variant(has_ops, has_chain, has_order, has_cost):
        key = (has_ops, has_chain, has_order, has_cost)
        fn = variants.get(key)
        if fn is None:
            names = (["ops"] if has_ops else []) \
                + (["chain_ids"] if has_chain else []) \
                + (["costs"] if has_cost else []) \
                + (["order"] if has_order else [])

            def wrapped(t, qk, qv, *extra, _names=tuple(names)):
                return local_fn(t, qk, qv, **dict(zip(_names, extra)))

            fn = jax.jit(_shard_map(
                wrapped, mesh=mesh,
                in_specs=base_in + (P(axis),) * len(names),
                out_specs=out_specs_chain if has_chain else out_specs))
            variants[key] = fn
        return fn

    def run(table, qkeys, qvals, ops=None, chain_ids=None, order=None,
            costs=None):
        if order is not None:
            assert ops is not None, "order requires an ops vector"
        if chain_ids is not None:
            assert ops is not None, "chain_ids requires an ops vector"
        with jax.profiler.TraceAnnotation("msl.access"):
            fn = _variant(ops is not None, chain_ids is not None,
                          order is not None, costs is not None)
            extra = [jnp.asarray(x, jnp.int32)
                     for x in (ops, chain_ids, costs, order) if x is not None]
            return fn(table, qkeys, qvals, *extra)

    return run


class ShardedCacheClient:
    """Host-side driver exposing the sharded engine with the local
    ``MultiStepLRUCache`` access contract, so ``PrefixCache`` can serve a
    multi-host-shaped cache unchanged (one fused chain call per tick).

    Repacking: the sharded run splits the query batch into ``ndev``
    contiguous slabs, and the chain scan is device-local — so ``access``
    deals whole chains round-robin onto slabs, renumbers chain ids
    slab-locally, pads every slab to the common pow2 length with provable
    no-op LOOKUP rows on key 0, and unpacks the outputs back to caller
    order.  Each packed row also carries its caller index as the engine's
    canonical ``order`` rank, so the sharded table stays *bit-equal* to a
    local ``MultiStepLRUCache`` fed the same batch even though the dealing
    permutes slab order (``pos`` is not routed back — it is reported -1).

    Bounded caps and the shed protocol: with ``cap != "full"`` the client
    runs a host-side capacity pre-check that mirrors the device route ranks
    exactly (same per-(slab, owner) counting in slab order) and sheds WHOLE
    groups — a chain is never partially routed, so a shed never leaves a
    half-mutated chain behind.  Shed rows come back as plain misses with
    ``last_shed`` marking them in caller order; the engine-side ``served``
    mask is asserted all-True for the admitted rows (a regression check
    that the host mirror and the device ranks agree).  ``PrefixCache`` /
    ``ServeEngine`` turn ``last_shed`` into a retry next tick.

    Load-aware shed placement: a chain stresses exactly the per-peer
    buffers of its chunks' HOME shards, and the pre-check already counts
    per-(slab, owner) loads — so with ``placement="load"`` (the default
    under a bounded cap) each group is placed greedily on the slab where
    its peak resulting per-owner depth is smallest (ties: fewer total rows,
    then lower slab index) instead of dealt round-robin.  Same-home-shard
    chains (Zipfian duplicates) then spread across slabs instead of
    stacking one slab's buffer for that owner, cutting the shed rate at a
    given cap; the canonical ``order`` ranks keep the table bit-equal to
    the sequential engine under ANY placement, so this is purely a
    shed-rate knob.  ``placement="roundrobin"`` keeps the legacy dealing
    (the committed BENCH_sharded baseline); with ``cap="full"`` nothing
    can shed, so the round-robin deal is kept regardless.

    Split-chain placement: hashing is per-chunk, so whole-chain atomicity
    is a *placement* choice, not a table constraint.  ``placement="split"``
    (the default under a bounded cap) packs each chain as one or more
    contiguous chunk-run FRAGMENTS onto different slabs, judged on the
    same per-(slab, owner) load mirror the shed pre-check uses, and sheds
    only the un-placeable SUFFIX of chunks: the placed fragments are
    prefix-closed, so ``serve_chains``' longest-hit-prefix contract and
    the canonical caller-order ranks both survive — a partial placement
    serves the chain up to its fragment boundary and the serving tier
    re-queues only the tail.  Each fragment gets its own slab-local chain
    id (``chain_exec_from_hits`` scans it as an independent prefix
    segment; a fragment's GET and PUT island rows stay paired because
    both carry the fragment's id).  A chunk homed on a degraded shard, or
    whose owner's per-peer buffer is full on every healthy slab, starts
    the shed suffix.  Split needs >= 2 healthy slabs; on one slab it
    degenerates to the whole-chain load deal (1-device clients keep the
    atomic shed protocol).  Counters: ``split_chains`` (chains placed as
    >= 2 fragments), ``partial_sheds`` (suffix-only sheds with a served
    prefix), ``slab_occupancy_peak`` (max per-(slab, owner) buffer fill
    observed), and ``slab_pressure`` — a per-HOME-shard EWMA of buffer
    utilization, pinned to 1.0 for owners implicated in capacity or
    degraded sheds — which ``chain_pressure`` exposes as the
    ``ServeEngine`` admission-throttle signal.
    """

    batch_multiple = 1  # access() repacks internally; any B works
    self_padding = True  # callers need not pow2-pad; slabs are padded here

    def __init__(self, cfg: MSLRUConfig, mesh, axis: str = "cache",
                 engine: str = "onepass", use_kernel: bool = False,
                 block_b: int = 2048, interpret: bool | None = None,
                 cap="full", placement: str | None = None):
        # the slab repacking below is written for 32-bit chunk hashes; the
        # sharded ENGINE itself handles key_planes=2, the client does not
        assert cfg.key_planes == 1, (
            "ShardedCacheClient packs 1-plane keys (chunk hashes); "
            "key_planes=2 is not supported here")
        if placement is None:
            # split only matters when sheds can happen; with cap="full" the
            # load deal is kept (nothing to split around)
            placement = "split" if cap != "full" else "load"
        assert placement in ("load", "roundrobin", "split"), placement
        self.cfg = cfg
        self.cap = cap
        self.placement = placement
        # engine ctor args, kept so reshard() can rebuild on a new mesh
        self._axis = axis
        self._engine_kwargs = dict(engine=engine, use_kernel=use_kernel,
                                   block_b=block_b, interpret=interpret)
        self._bind_mesh(mesh)
        self.table = shard_table(init_table(cfg), mesh, axis)
        self.sheds = 0          # total rows shed by the capacity pre-check
        self.shed_groups = 0    # total groups (chains / plain rows) shed
        self.last_shed = None   # (n,) bool, caller order, of the last access
        self.route_shape = None  # (q, k_depth, payload planes) of last call
        # -- split placement / pressure observability ----------------------
        self.split_chains = 0   # chains placed as >= 2 fragments
        self.partial_sheds = 0  # suffix-only sheds (a prefix was served)
        self.slab_occupancy_peak = 0.0  # max per-(slab, owner) fill seen
        self._pressure_alpha = 0.4      # slab_pressure EWMA weight
        # -- elasticity / fault state -------------------------------------
        self.degraded: set[int] = set()   # shards treated as lost: every
        #   group with a chunk HOMED there (or packed onto that slab) sheds
        self.degraded_sheds = 0           # groups shed because of degraded
        self.fault_sheds = 0              # groups shed by injected faults
        self._transient_fail = None       # [calls_left, frac, rng]
        # chain registry: tuple(chain hashes) -> last-touch counter.  The
        # serving tier notes every chain it serves (``note_chain``) so a
        # live reshard can drain the table chain-by-chain — the table
        # itself stores bare chunk->page entries with no chain structure.
        self._chain_registry: dict[tuple, int] = {}
        self._touch = 0
        self.last_drain_stream: list[dict] = []   # reshard()'s canonical
        #   re-insert batches (the sequential-oracle replay stream)

    def _bind_mesh(self, mesh):
        """(Re)bind the routing engine to ``mesh`` — used by __init__ and
        by ``reshard`` when the device count changes."""
        self.mesh = mesh
        self.ndev = mesh.shape[self._axis]
        self._s_local = sets_per_shard(self.cfg.num_sets, self.ndev)
        # per-home-shard pressure EWMA (admission-throttle signal); a new
        # mesh starts cold — reshard() assumes the new shards are healthy
        self.slab_pressure = np.zeros(self.ndev)
        self._run = make_sharded_engine(self.cfg, mesh, axis=self._axis,
                                        cap=self.cap, **self._engine_kwargs)
        # full-cap engine for control-plane sweeps (drain); built lazily
        self._full_run = self._run if self.cap == "full" else None

    def access(self, keys, vals=None, ops=None, chain_ids=None, costs=None):
        keys = np.asarray(keys, np.int32).reshape(-1)
        n = keys.shape[0]
        v = self.cfg.value_planes
        if vals is None:
            vals = np.zeros((n, v), np.int32)
        vals = np.asarray(vals, np.int32).reshape(n, v)
        if ops is None:
            ops = np.full(n, OP_ACCESS, np.int32)
        ops = np.asarray(ops, np.int32)
        chain_ids = (np.zeros(n, np.int32) if chain_ids is None
                     else np.asarray(chain_ids, np.int32))
        if costs is not None:
            costs = np.asarray(costs, np.int32).reshape(-1)

        # deal whole chains (contiguous runs of one chain id among chain
        # rows; plain rows are singleton groups) round-robin onto slabs
        groups: list[list[int]] = []
        is_chain = (ops == OP_CHAIN_GET) | (ops == OP_CHAIN_PUT)
        prev = None
        for i in range(n):
            key = ("c", int(chain_ids[i])) if is_chain[i] else ("p", i)
            if key != prev:
                groups.append([])
                prev = key
            groups[-1].append(i)
        # chains appear as two runs (GET island, PUT island) of one id —
        # merge them so both land on the same slab
        merged: dict = {}
        order: list = []
        for g in groups:
            gk = ("c", int(chain_ids[g[0]])) if is_chain[g[0]] else ("p", g[0])
            if gk in merged:
                merged[gk].extend(g)
            else:
                merged[gk] = list(g)
                order.append(gk)
        # degraded shards neither host query slabs (a dead device sends
        # nothing) nor answer routed probes (any group homing a chunk there
        # is shed for re-prefill) — see mark_degraded
        healthy = [d for d in range(self.ndev) if d not in self.degraded]
        assert healthy, "every shard degraded; reshard() to a live mesh"
        owners = None
        if self.cap != "full" or self.degraded or self._transient_fail:
            owners = np.asarray(
                set_index_for(self.cfg, jnp.asarray(keys[:, None]))
            ) // self._s_local
        placement = self.placement
        if placement == "split" and (owners is None or len(healthy) < 2):
            # split needs >= 2 live slabs to fragment across (and a reason
            # to shed at all); degenerate to the whole-chain load deal —
            # which itself degenerates to round-robin on one slab — so
            # 1-device clients keep the atomic shed protocol
            placement = "load"

        tf = self._transient_fail
        shed = np.zeros(n, bool)
        # slab-local chain ids segment on ``seg``: the caller's chain id for
        # whole-chain groups, a unique fragment id under split placement
        seg = chain_ids.astype(np.int64, copy=True)
        counts2d = None     # admitted per-(slab, owner) rows, for pressure
        hot = np.zeros(self.ndev, bool)   # owners implicated in sheds
        if placement == "split":
            slabs, q, k_depth, counts2d = self._place_split(
                order, merged, is_chain, keys, owners, n, healthy, tf,
                shed, seg, hot)
            self.sheds += int(shed.sum())
        else:
            slabs, q, k_depth, counts2d = self._place_whole(
                order, merged, is_chain, owners, n, healthy, placement, tf,
                shed, hot)
        self.last_shed = shed
        if tf is not None:
            tf[0] -= 1
            if tf[0] <= 0:
                self._transient_fail = None
        if owners is not None and counts2d is not None:
            self._note_pressure(counts2d,
                                k_depth if self.cap != "full" else q, hot)
        bp = q * self.ndev
        k = np.zeros(bp, np.int32)
        vv = np.zeros((bp, v), np.int32)
        oo = np.full(bp, OP_LOOKUP, np.int32)          # padding: no-op probe
        cc = np.zeros(bp, np.int32)
        cst = None if costs is None else np.zeros(bp, np.int32)
        od = n + np.arange(bp, dtype=np.int32)         # padding ranks: last
        src = np.full(bp, -1, np.int64)                # row -> caller index
        for d, slab in enumerate(slabs):
            # renumber chain segments slab-locally: first-row index of the
            # segment — a whole chain, or one fragment under split
            # placement (fragments of one chain carry distinct ``seg`` ids,
            # so each scans as an independent prefix segment)
            local_first: dict = {}
            for r, i in enumerate(slab):
                row = d * q + r
                k[row] = keys[i]
                vv[row] = vals[i]
                oo[row] = ops[i]
                od[row] = i                            # caller-order rank
                src[row] = i
                if cst is not None:
                    cst[row] = costs[i]
                if is_chain[i]:
                    sk = int(seg[i])
                    local_first.setdefault(sk, r)
                    cc[row] = local_first[sk]
        # key+val+op+live[+cost]+order
        self.route_shape = (q, k_depth,
                            1 + v + 3 + (0 if costs is None else 1))

        self.table, hit, val, served, ev_val, ev_ok = self._run(
            self.table, jnp.asarray(k[:, None]), jnp.asarray(vv),
            jnp.asarray(oo), jnp.asarray(cc), order=jnp.asarray(od),
            costs=None if cst is None else jnp.asarray(cst))
        # the pre-check guarantees every admitted row fits its per-peer
        # buffer; a violation means the host mirror and device ranks drifted
        assert bool(np.asarray(served)[src >= 0].all()), "client overflow"

        sel = src >= 0
        rows = np.nonzero(sel)[0]
        idx = src[rows]
        hit_u = np.zeros(n, bool)
        hit_u[idx] = np.asarray(hit)[rows]
        val_u = np.zeros((n, v), np.int32)
        if v:
            val_u[idx] = np.asarray(val)[rows][:, :v]
        ev_ok_u = np.zeros(n, bool)
        ev_ok_u[idx] = np.asarray(ev_ok)[rows]
        ev_val_u = np.zeros((n, v), np.int32)
        if v:
            ev_val_u[idx] = np.asarray(ev_val)[rows][:, :v]
        ev_key = np.where(ev_ok_u[:, None], 0,
                          EMPTY_KEY).astype(np.int32)
        ev_key = np.broadcast_to(ev_key, (n, self.cfg.key_planes))
        return AccessResult(
            hit=hit_u,
            value=val_u,
            pos=np.full(n, -1, np.int32),
            evicted_key=ev_key,
            evicted_val=ev_val_u,
            evicted_valid=ev_ok_u,
        )

    # -- placement --------------------------------------------------------

    def _place_whole(self, order, merged, is_chain, owners, n, healthy,
                     placement, tf, shed, hot):
        """Whole-group placement (``load``/``roundrobin``) plus the
        host-side shed pre-check: mirror the device's per-(slab, owner)
        rank counting in slab order, at GROUP granularity — if any row of
        a group would overflow its owner's per-peer depth, the whole group
        is shed (atomically) and retried by the serving tier.
        Degraded-owner groups and injected transient route failures shed
        through the same path: whole groups, retried next tick, never a
        half-mutated chain.  Mutates ``shed``/``hot`` in place; returns
        ``(slabs, q, k_depth, counts2d)`` with ``slabs[d]`` the admitted
        caller rows of slab ``d`` and ``counts2d`` the admitted
        per-(slab, owner) row counts (``None`` when no pre-check ran)."""
        slab_groups: list[list[list[int]]] = [[] for _ in range(self.ndev)]
        if owners is not None and placement == "load" and len(healthy) > 1:
            # greedy load-aware deal: place each group on the slab where
            # its peak resulting per-owner depth stays smallest — judged
            # on exactly the per-(slab, owner) counts the shed pre-check
            # mirrors below, so placement optimizes the quantity that
            # triggers sheds.  Ties fall to the slab with fewer rows, then
            # the lowest index (deterministic).  A slab row cap at the
            # pow2 ceiling of the balanced load keeps q — and with it the
            # per-peer depth and all_to_all buffer bytes — the same as an
            # even deal's: lower sheds must come from smarter placement,
            # not quietly larger buffers.  (Soft cap: if no slab fits, the
            # group goes to the emptiest one and q grows a step.)
            counts = np.zeros((self.ndev, self.ndev), np.int64)
            rows_ct = np.zeros(self.ndev, np.int64)
            balanced = (n + len(healthy) - 1) // len(healthy)
            cap_rows = 1 << max(0, balanced - 1).bit_length()
            for gk in order:
                g = merged[gk]
                gcnt = np.bincount(owners[g], minlength=self.ndev)
                touched = np.nonzero(gcnt)[0]
                if touched.size:
                    peaks = (counts[:, touched] + gcnt[touched]).max(axis=1)
                else:
                    peaks = np.zeros(self.ndev, np.int64)
                cands = [d for d in healthy
                         if rows_ct[d] + len(g) <= cap_rows]
                if not cands:
                    cands = healthy
                best = min(cands,
                           key=lambda d: (int(peaks[d]), int(rows_ct[d]), d))
                counts[best] += gcnt
                rows_ct[best] += len(g)
                slab_groups[best].append(g)
        else:
            for j, gk in enumerate(order):
                slab_groups[healthy[j % len(healthy)]].append(merged[gk])

        # q (and hence the per-peer depth) is fixed from the un-shed packing
        # so the shapes the engine compiles for do not depend on shed luck
        q = max(1, max(sum(len(g) for g in gs) for gs in slab_groups))
        q = 1 << (q - 1).bit_length()
        k_depth = per_peer_cap(self.cap, q, self.ndev)

        slabs: list[list[int]] = []
        counts2d = None
        dg = (np.array(sorted(self.degraded), np.int64)
              if self.degraded else None)
        if owners is not None:
            counts2d = np.zeros((self.ndev, self.ndev), np.int64)
            for di, gs in enumerate(slab_groups):
                counts = counts2d[di]          # accumulated in place
                rows: list[int] = []
                for g in gs:
                    gcnt = np.bincount(owners[g], minlength=self.ndev)
                    if dg is not None and gcnt[dg].any():
                        shed[g] = True
                        self.shed_groups += 1
                        self.degraded_sheds += 1
                        hot[dg[gcnt[dg] > 0]] = True
                        continue
                    if tf is not None and tf[2].random() < tf[1]:
                        shed[g] = True
                        self.shed_groups += 1
                        self.fault_sheds += 1
                        continue
                    if self.cap != "full" and np.any(counts + gcnt > k_depth):
                        shed[g] = True
                        self.shed_groups += 1
                        hot |= counts + gcnt > k_depth
                        continue
                    counts += gcnt
                    rows.extend(g)
                slabs.append(rows)
            self.sheds += int(shed.sum())
        else:
            slabs = [[i for g in gs for i in g] for gs in slab_groups]
        return slabs, q, k_depth, counts2d

    def _place_split(self, order, merged, is_chain, keys, owners, n,
                     healthy, tf, shed, seg, hot):
        """Greedy fragment packing (``placement="split"``): each chain
        becomes one or more contiguous chunk-run fragments, placed on the
        slab that extends the run furthest (ties: smallest resulting
        per-owner peak, fewer slab rows, lowest index) against the same
        per-(slab, owner) depth mirror the whole-group pre-check counts.
        Only the un-placeable SUFFIX of chunks sheds — a chunk homed on a
        degraded shard, or whose owner's buffer is full on every healthy
        slab, truncates the chain there; everything before it is served.
        Placement is judged against the even deal's pow2 row budget (same
        ``cap_rows`` as the load deal) so q — and the all_to_all buffer
        bytes — match a whole-chain tick's; the soft row-cap fallback can
        grow q a step, which only ever RAISES the engine's actual per-peer
        depth, so the mirror stays conservative.  Mutates ``shed`` (suffix
        rows), ``seg`` (fragment ids — every placed chain row gets one, so
        each fragment is an independent slab-local chain segment), and
        ``hot``.  Returns ``(slabs, q, k_depth, counts2d)``."""
        nh = len(healthy)
        counts2d = np.zeros((self.ndev, self.ndev), np.int64)
        rows_ct = np.zeros(self.ndev, np.int64)
        balanced = (n + nh - 1) // nh
        cap_rows = 1 << max(0, balanced - 1).bit_length()
        k_depth = per_peer_cap(self.cap, cap_rows, self.ndev)
        slabs: list[list[int]] = [[] for _ in range(self.ndev)]
        next_seg = 0

        for gk in order:
            g = merged[gk]
            if tf is not None and tf[2].random() < tf[1]:
                shed[g] = True
                self.shed_groups += 1
                self.fault_sheds += 1
                continue
            if not is_chain[g[0]]:
                o = int(owners[g[0]])
                if o in self.degraded:
                    shed[g] = True
                    self.shed_groups += 1
                    self.degraded_sheds += 1
                    hot[o] = True
                    continue
                cands = [d for d in healthy
                         if counts2d[d, o] + len(g) <= k_depth
                         and rows_ct[d] + len(g) <= cap_rows]
                if not cands:
                    cands = [d for d in healthy
                             if counts2d[d, o] + len(g) <= k_depth]
                if not cands:
                    shed[g] = True
                    self.shed_groups += 1
                    hot[o] = True
                    continue
                best = min(cands, key=lambda d: (int(counts2d[d, o]),
                                                 int(rows_ct[d]), d))
                counts2d[best, o] += len(g)
                rows_ct[best] += len(g)
                slabs[best].extend(g)
                continue

            # chunk decomposition: row -> chunk index by first occurrence
            # of its key (the GET island fixes the chunk order; PUT rows
            # pair with their chunk by key), so a shed boundary cuts the
            # SAME suffix out of both islands
            key_ord: dict[int, int] = {}
            for i in g:
                key_ord.setdefault(int(keys[i]), len(key_ord))
            nch = len(key_ord)
            ch_of = {i: key_ord[int(keys[i])] for i in g}
            ch_rows: list[list[int]] = [[] for _ in range(nch)]
            for i in g:
                ch_rows[ch_of[i]].append(i)
            ch_owner = [int(owners[ch_rows[t][0]]) for t in range(nch)]
            ch_n = [len(ch_rows[t]) for t in range(nch)]

            def extent(d, t, respect_rows):
                """Longest chunk run [t, e) that fits slab ``d``; returns
                (e, peak per-owner depth after placing it)."""
                add: dict[int, int] = {}
                radd = 0
                e = t
                while e < nch:
                    o_e = ch_owner[e]
                    if o_e in self.degraded:
                        break
                    if counts2d[d, o_e] + add.get(o_e, 0) + ch_n[e] \
                            > k_depth:
                        break
                    if respect_rows and rows_ct[d] + radd + ch_n[e] \
                            > cap_rows:
                        break
                    add[o_e] = add.get(o_e, 0) + ch_n[e]
                    radd += ch_n[e]
                    e += 1
                peak = max((int(counts2d[d, o]) + a
                            for o, a in add.items()), default=0)
                return e, peak

            nfrag = 0
            t = 0
            while t < nch:
                if ch_owner[t] in self.degraded:
                    break                       # suffix from t sheds
                best = None
                for soft in (True, False):      # soft row cap only if stuck
                    for d in healthy:
                        e, peak = extent(d, t, soft)
                        if e == t:
                            continue
                        cand = ((-(e - t), peak, int(rows_ct[d]), d), d, e)
                        if best is None or cand[0] < best[0]:
                            best = cand
                    if best is not None:
                        break
                if best is None:
                    break                       # owner full on every slab
                _, d, e = best
                frag = [i for i in g if t <= ch_of[i] < e]
                for t2 in range(t, e):
                    counts2d[d, ch_owner[t2]] += ch_n[t2]
                rows_ct[d] += len(frag)
                seg[frag] = next_seg
                next_seg += 1
                slabs[d].extend(frag)
                nfrag += 1
                t = e
            if nfrag > 1:
                self.split_chains += 1
            if t < nch:
                rest = [i for i in g if ch_of[i] >= t]
                shed[rest] = True
                hot[ch_owner[t]] = True
                if ch_owner[t] in self.degraded:
                    self.degraded_sheds += 1
                if t == 0:
                    self.shed_groups += 1
                else:
                    self.partial_sheds += 1

        # q covers both the estimate the mirror packed against and the
        # actual max slab (the soft row-cap fallback can exceed cap_rows);
        # a float/"full" cap's engine depth then only grows past the
        # mirror's k_depth — admitted rows still fit, sheds stay final
        q = max(cap_rows, max((len(s) for s in slabs), default=1), 1)
        q = 1 << (q - 1).bit_length()
        k_depth = per_peer_cap(self.cap, q, self.ndev)
        return slabs, q, k_depth, counts2d

    def _note_pressure(self, counts2d, depth, hot) -> None:
        """Fold one tick's admitted per-(slab, owner) counts into the
        per-home-shard pressure EWMA (owners implicated in capacity or
        degraded sheds pin to 1.0) and the occupancy peak."""
        kd = max(1, int(depth))
        x = np.minimum(counts2d.max(axis=0) / kd, 1.0)
        x[hot] = 1.0
        if self.degraded:
            x[sorted(self.degraded)] = 1.0
        a = self._pressure_alpha
        self.slab_pressure = (1.0 - a) * self.slab_pressure + a * x
        self.slab_occupancy_peak = max(self.slab_occupancy_peak,
                                       float(counts2d.max() / kd))

    def home_shards(self, chain) -> np.ndarray:
        """Distinct home shards of ``chain``'s chunk hashes (sorted)."""
        h = np.asarray(list(chain), np.int32).reshape(-1)
        if h.size == 0:
            return np.zeros(0, np.int64)
        o = np.asarray(set_index_for(self.cfg, jnp.asarray(h[:, None]))
                       ) // self._s_local
        return np.unique(o)

    def chain_pressure(self, chain) -> float:
        """Max ``slab_pressure`` over ``chain``'s home shards — the
        ``ServeEngine`` admission-throttle signal (0.0 for empty chains
        or a cold mesh)."""
        o = self.home_shards(chain)
        if o.size == 0:
            return 0.0
        return float(self.slab_pressure[o].max())

    # -- elasticity / fault tolerance -------------------------------------

    def note_chain(self, chain) -> None:
        """Register a chain (sequence of chunk hashes) as live.  The table
        stores bare chunk->page entries with no chain structure, so the
        serving tier notes every chain it touches; ``reshard`` drains the
        registry in last-touch (LRU-first) order.  Re-noting refreshes the
        touch counter; prefixes of a longer chain need no separate entry
        (the longer drain sweep covers them)."""
        key = tuple(int(h) for h in np.asarray(chain).reshape(-1))
        if not key:
            return
        self._touch += 1
        self._chain_registry[key] = self._touch

    def inject_route_failures(self, calls: int = 1, frac: float = 0.5,
                              seed: int = 0) -> None:
        """Fault injection: for the next ``calls`` access() calls, each
        group independently sheds with probability ``frac`` (on top of the
        capacity/degraded checks).  Models transient route loss — the
        serving tier's retry queue must absorb it without drops."""
        self._transient_fail = [int(calls), float(frac),
                                np.random.default_rng(seed)]

    def mark_degraded(self, shard: int) -> list[int]:
        """Treat ``shard`` as lost: wipe its sets from the table and shed
        every future group that homes a chunk there (permanently, until a
        ``reshard``).  Returns the ORPHANED pages — value-plane-0 ints of
        the entries that lived on the lost shard — so the serving tier can
        reconcile its page pool (release reservations the shard held).
        Orphaned chains are not errors: their next serve misses, sheds, and
        re-prefills through the normal shed/retry + plain-fallback path."""
        assert 0 <= shard < self.ndev, shard
        if shard in self.degraded:
            return []
        self.degraded.add(shard)
        assert len(self.degraded) < self.ndev, \
            "every shard degraded; reshard() to a live mesh"
        kp = self.cfg.key_planes
        tbl = np.array(jax.device_get(self.table))[: self.cfg.num_sets]
        lo = shard * self._s_local
        hi = min((shard + 1) * self._s_local, self.cfg.num_sets)
        live = tbl[lo:hi, :, 0] != EMPTY_KEY
        # dedupe (first-seen order): with split-placed chains the fragments
        # of one chain drain/re-home independently, and a caller releasing
        # each listed orphan must never see one page twice — a double
        # release would free a page some other entry still references
        orphans = (list(dict.fromkeys(
            int(p) for p in tbl[lo:hi, :, kp][live]))
            if self.cfg.value_planes else [])
        tbl[lo:hi] = 0
        tbl[lo:hi, :, 0] = EMPTY_KEY
        self.table = shard_table(tbl, self.mesh, self._axis)
        # the lost shard's buffers are gone: pin its pressure so the
        # serving tier's admission throttle defers chains homing there
        self.slab_pressure[shard] = 1.0
        return orphans

    def _full_engine(self):
        """Full-cap engine on the current mesh for control-plane sweeps
        (drain): a drain must observe every entry, never shed on capacity."""
        if self._full_run is None:
            self._full_run = make_sharded_engine(
                self.cfg, self.mesh, axis=self._axis, cap="full",
                **self._engine_kwargs)
        return self._full_run

    def _sweep_access(self, keys, vals, ops, chain_ids, costs=None):
        """access() with sheds disabled: full cap, degraded and injected
        faults bypassed.  Used by reshard()'s drain/re-insert sweeps.
        Split placement is inert here by construction: with cap forced to
        "full" and no degraded shards the owner mirror is never built, so
        every chain deals whole (round-robin) regardless of
        ``self.placement`` — a drain observes each chain as ONE segment
        even if serving placed it as fragments."""
        run, cap = self._run, self.cap
        degraded, tf = self.degraded, self._transient_fail
        self._run, self.cap = self._full_engine(), "full"
        self.degraded, self._transient_fail = set(), None
        try:
            return self.access(keys, vals, ops, chain_ids, costs=costs)
        finally:
            self._run, self.cap = run, cap
            self.degraded, self._transient_fail = degraded, tf

    def reshard(self, new_ndev: int, drain_batch: int = 256) -> list[int]:
        """Live D→D′ reshard: drain every registered chain from the current
        mesh via batched OP_CHAIN_GET sweeps, rebuild a cold table on a
        ``new_ndev``-device mesh, and re-insert the drained prefixes via
        OP_CHAIN_PUT in canonical caller order.

        Bit-reproducibility: ``num_sets`` is unchanged, so each set gets
        back exactly the entries it held (≤ assoc — they were co-resident),
        meaning the rebuild never evicts; with the canonical ``order``
        ranks the rebuilt table is bit-equal to a cold SEQUENTIAL engine
        fed the same stream — recorded in ``self.last_drain_stream`` as the
        oracle's replay input (list of {keys, vals, ops, chain_ids}
        batches, numpy, in call order).

        What survives: for each registry chain, its longest resident prefix
        (lookups stop at the first miss, so deeper chunks behind an evicted
        or lost one are unreachable).  Everything live-but-unreachable is
        returned as ORPHANED pages for pool reconciliation; those chains
        re-prefill on their next serve.  Degraded shards are cleared — the
        new mesh is assumed healthy."""
        assert new_ndev >= 1
        assert self.cfg.value_planes >= 1, \
            "reshard drains (key, page) pairs; needs a value plane"
        kp = self.cfg.key_planes
        # 1. snapshot live entries host-side: key -> value planes
        tbl = np.asarray(jax.device_get(self.table))[: self.cfg.num_sets]
        live = tbl[:, :, 0] != EMPTY_KEY
        live_map = {int(k): vv.astype(np.int32)
                    for k, vv in zip(tbl[live][:, 0], tbl[live][:, kp:])}
        # 2. drain: CHAIN_GET sweeps in last-touch (LRU-first) order — the
        # canonical re-insert order, so the rebuilt recency lanes rank
        # chains exactly as serving touched them
        chains = sorted(self._chain_registry,
                        key=self._chain_registry.__getitem__)
        drained: list[tuple] = []      # (chain_prefix,) surviving prefixes
        reached: set[int] = set()
        batch: list[tuple] = []
        rows = 0

        def flush():
            nonlocal rows
            if not batch:
                return
            keys = np.concatenate(
                [np.asarray(c, np.int32) for c in batch])
            ops = np.full(keys.size, OP_CHAIN_GET, np.int32)
            cids = np.concatenate(
                [np.full(len(c), j, np.int32)
                 for j, c in enumerate(batch)])
            hit = self._sweep_access(keys, None, ops, cids).hit
            off = 0
            for c in batch:
                h = hit[off: off + len(c)]
                off += len(c)
                hitlen = len(c) if h.all() else int(np.argmin(h))
                if hitlen:
                    drained.append(c[:hitlen])
                    reached.update(c[:hitlen])
            batch.clear()
            rows = 0

        for c in chains:
            if rows + len(c) > drain_batch and batch:
                flush()
            batch.append(c)
            rows += len(c)
        flush()
        # dedupe for the same reason as mark_degraded: a split-placed
        # chain's fragments drain independently and the caller releases
        # each orphan exactly once
        orphans = list(dict.fromkeys(
            int(live_map[k][0]) for k in live_map if k not in reached))
        # 3. rebuild on the new mesh, cold
        from repro.launch.mesh import make_cache_mesh
        self.degraded.clear()
        self._bind_mesh(make_cache_mesh(new_ndev))
        self.table = shard_table(init_table(self.cfg), self.mesh,
                                 self._axis)
        # 4. re-insert the surviving prefixes via CHAIN_PUT in the same
        # canonical order, batched; record the stream for the oracle
        self.last_drain_stream = []
        self._chain_registry = {
            c: t for c, t in self._chain_registry.items()
            if c and int(c[0]) in reached}
        batch2: list[tuple] = []
        rows = 0

        def flush2():
            nonlocal rows
            if not batch2:
                return
            keys = np.concatenate(
                [np.asarray(c, np.int32) for c in batch2])
            planes = np.concatenate(
                [np.stack([live_map[k] for k in c]) for c in batch2])
            # live_map rows pack [value planes | cost plane]; split so the
            # re-insert restores each entry's stored cost on the new mesh
            v = self.cfg.value_planes
            vals = planes[:, :v]
            costs = planes[:, v] if self.cfg.cost_planes else None
            ops = np.full(keys.size, OP_CHAIN_PUT, np.int32)
            cids = np.concatenate(
                [np.full(len(c), j, np.int32)
                 for j, c in enumerate(batch2)])
            self.last_drain_stream.append(dict(
                keys=keys, vals=vals, ops=ops, chain_ids=cids,
                costs=costs))
            self._sweep_access(keys, vals, ops, cids, costs=costs)
            batch2.clear()
            rows = 0

        for c in drained:
            if rows + len(c) > drain_batch and batch2:
                flush2()
            batch2.append(c)
            rows += len(c)
        flush2()
        return orphans

    @property
    def occupancy(self) -> float:
        # elastic meshes pad the sharded table with EMPTY sets — slice back
        tbl = np.asarray(jax.device_get(self.table))[: self.cfg.num_sets]
        return float((tbl[:, :, 0] != EMPTY_KEY).mean())


def make_sharded_stream_runner(cfg: MSLRUConfig, mesh, axis: str = "cache",
                               cap: int | None = None, batch: int = 4096,
                               engine: str = "rounds", **engine_kwargs):
    """Scan the sharded engine over a long stream (throughput/scaling bench).

    Parity with every other engine entry point: ``run(table, qkeys, qvals,
    ops=None, chain_ids=None, costs=None)`` — ``ops`` (N,) per-query
    opcodes and
    ``chain_ids`` (N,) per-query chain segment ids (device-local per batch,
    requires ``ops``) reshape alongside the query stream, one (batch,)
    slice per scan step.  ``ops=None`` stays the separately-compiled
    ACCESS-only specialization (no ops plane in the all_to_all).  Returns
    (table, hits, served) — ``served`` counts non-shed queries, so
    ``1 - served/n`` is the stream's shed rate under a bounded ``cap``.
    """
    eng = make_sharded_engine(cfg, mesh, axis, cap, engine=engine,
                              **engine_kwargs)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_stream(table, qkeys, qvals, ops, chain_ids, costs):
        # ops/chain_ids/costs=None are distinct (static) pytree structures:
        # the ACCESS-only / no-chain / no-cost paths compile without those
        # planes
        n = qkeys.shape[0] // batch * batch
        qk = qkeys[:n].reshape(-1, batch, qkeys.shape[-1])
        qv = qvals[:n].reshape(-1, batch, qvals.shape[-1])
        qo = None if ops is None else ops[:n].reshape(-1, batch)
        qc = None if chain_ids is None else chain_ids[:n].reshape(-1, batch)
        qcost = None if costs is None else costs[:n].reshape(-1, batch)

        def step(tbl, xs):
            k, q, o, c, cst = xs
            out = eng(tbl, k, q, o, c, costs=cst)
            tbl, hit, _val, served = out[:4]   # chain mode appends evicted
            return tbl, (jnp.sum(hit), jnp.sum(served))

        table, (hits, served) = jax.lax.scan(
            step, table, (qk, qv, qo, qc, qcost))
        return table, jnp.sum(hits), jnp.sum(served)

    def run(table, qkeys, qvals, ops=None, chain_ids=None, costs=None):
        if ops is not None:
            ops = jnp.asarray(ops, jnp.int32)
        if chain_ids is not None:
            assert ops is not None, "chain_ids requires an ops vector"
            chain_ids = jnp.asarray(chain_ids, jnp.int32)
        if costs is not None:
            costs = jnp.asarray(costs, jnp.int32)
        return run_stream(table, qkeys, qvals, ops, chain_ids, costs)

    return run
