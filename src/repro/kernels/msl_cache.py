"""Pallas TPU kernels for the batched multi-step LRU access op.

This is the compute hot-spot the paper optimizes with AVX intrinsics: the
compare + permute + insert over a set's A = M*P lanes.  On TPU the unit of
work is a *block of queries*: each grid cell loads a block of gathered set
rows into VMEM plus the query tiles, and performs the entire fused
get-or-put transition with lane-select arithmetic on the VPU — no gathers,
no scalar loops, no pattern table (see invector.py for the mapping from the
paper's ``vpermd`` idiom).

Kernel layout (plane-major, query-on-lanes).  The table keeps its
``(S, A, C)`` set-row shape; on TPU its HBM layout keeps the set axis
minor, so the one-pass engine gathers set *columns* of the ``(C*A, S)``
view straight into the kernel's ``(C, A, B)`` layout
(``multistep.set_columns``).  The XLA prologue lays the per-query operands
out as ``(n, 1, B)`` / ``(1, B)`` and the epilogue transposes the outputs
back; the stateless kernel transposes its ``(B, A, C)`` rows in and out
the same way.  Inside a kernel every
value is 2-D: one plane of a set row is an ``(A, BB)`` tile (set lanes on
sublanes, queries on the 128-wide lane axis), and a per-query value is a
``(1, BB)`` row.  ``_transition`` and ``_chain_body`` work on tuples of
such planes, so no op ever reshapes across the tiled axes; reductions over
the set run down the sublanes (``keepdims``), and the two shifts — the
rotate-insert over set lanes and the chain hand-off to the next query —
are ``pltpu.roll`` (``jnp.roll`` outside a kernel).

Two kernels share the transition math (``_transition``), which applies a
per-row opcode (LOOKUP/GET/ACCESS/DELETE — see the table in core/engine.py)
with pure lane selects, so a batch may mix operations freely:

* ``msl_access_kernel_call`` — stateless: one transition per row, conflicts
  (duplicate set ids in the batch) are the *caller's* problem (the rounds
  engine re-invokes it once per conflict round, re-gathering from HBM each
  time).

* ``msl_onepass_kernel_call`` — conflict-aware single pass: queries arrive
  *sorted by set id* with per-query chain metadata (local rank within the
  duplicate chain, served mask), so the whole batch needs exactly one HBM
  gather before and one scatter after the kernel.  Same-set duplicates are
  resolved on-chip: a ``fori_loop`` whose trip count is the block's maximum
  chain rank (scalar-prefetched, so the scalar core knows it before the
  vector body runs) hands each updated row to the next chain member by a
  one-lane roll over the query axis — the rounds loop of the XLA engine
  collapsed into lane arithmetic over VMEM-resident rows.  A ``(C, A, BB)``
  VMEM scratch carries the previous block's rows (rolled so its last query
  sits at lane 0) across grid cells (TPU grid cells execute sequentially on
  a core), and a scalar-prefetched per-block flag says whether the block's
  first query continues that chain, so duplicate chains may span block
  boundaries.

Grid/BlockSpec: 1-D grid over query blocks; every ref is blocked on the
query (lane) axis only.  Scoped VMEM of the compiled kernels for a
described v5e chip at A=8, C=3 (m=2, p=4, 2 value planes), as the kernel's
custom call reports it (``used_scoped_memory_configs`` in
``compiled.as_text()``; ``compiled.memory_analysis()`` counts only the XLA
program's buffers, and none of the kernel's): one-pass 2,703,360 B at
BB=2048 and 141,312 B at BB=256; stateless 1,519,616 B and 116,736 B —
the double-buffered ``(C, A, BB)`` row tiles in and out, the carry
scratch, and the loop's ``cur``/``after`` row state, all inside the
16 MiB default scoped limit.

All index movement uses select+reduce (never take_along_axis/gather), so the
kernels lower to pure vector ops on TPU.  Correctness is pinned to the
pure-jnp oracle (ref.msl_access_ref == core row_access) in interpret mode —
bit-exact, every geometry/dtype in the test sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.invector import EMPTY_KEY
from repro.core.multistep import (MSLRUConfig, OP_ACCESS, OP_CHAIN_GET,
                                  OP_CHAIN_PUT, OP_DELETE, OP_LOOKUP)

__all__ = ["msl_access_kernel_call", "msl_onepass_kernel_call"]

LANES = 128   # TPU lane width: kernel query blocks are multiples of it


# ---------------------------------------------------------------------------
# Layout: (B, A, C) set rows <-> (C, A, B) planes; (B, n) <-> (n, 1, B)
# ---------------------------------------------------------------------------

def rows_to_planes(rows):
    """(B, A, C) set rows -> (C, A, B) kernel layout."""
    return jnp.transpose(rows, (2, 1, 0))


def planes_to_rows(planes):
    """(C, A, B) kernel layout -> (B, A, C) set rows."""
    return jnp.transpose(planes, (2, 1, 0))


def cols_to_planes(x):
    """(B, n) per-query values -> (n, 1, B)."""
    return x.T[:, None, :]


def planes_to_cols(x):
    """(n, 1, B) -> (B, n)."""
    return x[:, 0, :].T


def split_planes(x):
    """Tuple of the leading-axis slices of an array or a ref."""
    return tuple(x[i] for i in range(x.shape[0]))


def _rowmask(mask, shape):
    """Broadcast a (1, BB) per-query bool to a (A, BB) plane mask."""
    return jnp.broadcast_to(mask.astype(jnp.int32), shape) != 0


def _vector_base(cfg: MSLRUConfig, pos):
    """First lane of the vector holding ``pos`` (pos >= 0): (pos // p) * p,
    by comparisons so that no integer division reaches the VPU."""
    base = jnp.zeros_like(pos)
    for j in range(1, cfg.m):
        base += jnp.where(pos >= j * cfg.p, cfg.p, 0)
    return base


def _transition(cfg: MSLRUConfig, rows, qk, qv, ops=None, chain_live=None,
                qc=None):
    """Mixed-op transition on set rows held as planes; pure select/reduce.

    ``rows`` is a tuple of C ``(A, BB)`` planes; ``qk`` / ``qv`` tuples of
    KP / V ``(1, BB)`` query planes.  ``ops`` (1, BB) int32 opcode per row
    (OP_ACCESS/OP_GET/OP_DELETE/OP_LOOKUP/OP_CHAIN_GET/OP_CHAIN_PUT);
    ``None`` keeps the legacy all-ACCESS specialization (no opcode selects
    compiled in).  ``chain_live`` (1, BB) int32 execute mask for the chain
    ops (precomputed by the engine's segmented longest-prefix scan; ``None``
    treats chain rows as live): a live CHAIN_GET runs the GET path, a live
    CHAIN_PUT the ACCESS path, and a dead chain row passes its row through
    and reports a plain miss.  ``qc`` (1, BB) int32 insert cost per row
    (only read when cfg.cost_planes; ``None`` inserts cost 0) — with a cost
    plane the full-set victim is the cheapest lane of the last vector
    instead of blind lane A-1 (ties to the deepest lane; see
    core.multistep.row_put).  Returns (new_rows planes, hit (1, BB) bool,
    pos (1, BB) int32, val C-tuple of (1, BB), ev C-tuple of (1, BB) with
    key plane 0 == EMPTY_KEY when nothing was evicted); pos/val/ev follow
    the normalized per-op contract of ``core.multistep.row_apply`` (DELETE:
    pos = -1, val = 0; only an evicting ACCESS / live-CHAIN_PUT insert
    reports a real ev).
    """
    a = cfg.assoc
    kp, v = cfg.key_planes, cfg.value_planes
    p = cfg.p
    shape = rows[0].shape                                     # (A, BB)
    row_shape = (1, shape[1])

    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 0)

    def lane_max(x):
        return jnp.max(x, axis=0, keepdims=True)

    def pick(sel):       # the lane chosen by the (A, BB) mask, per plane
        return tuple(jnp.sum(jnp.where(sel, r, 0), axis=0, keepdims=True)
                     for r in rows)

    # --- probe: position of the key match (unique by invariant) -----------
    key_eq = rows[0] == qk[0]
    for kplane in range(1, kp):
        key_eq &= rows[kplane] == qk[kplane]
    pos = lane_max(jnp.where(key_eq, lane, -1))              # (1, BB)
    hit = pos >= 0
    pos_c = jnp.maximum(pos, 0)

    # item at pos via select+reduce (VPU-friendly; no gather)
    at_pos = pick(lane == pos_c)

    # --- get path: promote within vector / upgrade across vectors ---------
    base_get = _vector_base(cfg, pos_c)
    lo_get = jnp.where(pos_c > base_get, base_get, jnp.maximum(pos_c - 1, 0))
    if cfg.policy == "set_lru":
        lo_get = jnp.zeros_like(pos_c)
    hi_get = pos_c

    # --- put path: deepest empty slot, else evict the set's LRU tail ------
    # (cheapest last-vector lane instead, when a cost plane is configured)
    e = lane_max(jnp.where(rows[0] == EMPTY_KEY, lane, -1))
    if cfg.cost_planes:
        ccol = rows[kp + v]
        seg_lo = 0 if cfg.policy == "set_lru" else (cfg.m - 1) * p
        cand = jnp.where(lane >= seg_lo, ccol, jnp.int32(2**31 - 1))
        cmin = jnp.min(cand, axis=0, keepdims=True)
        victim = lane_max(jnp.where(cand == cmin, lane, -1))
    else:
        victim = a - 1
    pos_ins = jnp.where(e >= 0, e, victim)
    lo_put = _vector_base(cfg, pos_ins)
    if cfg.policy == "set_lru":
        lo_put = jnp.zeros_like(pos_ins)
    hi_put = pos_ins

    # --- fuse: one rotate_insert with per-row (lo, hi, item) --------------
    # The put range applies only to an ACCESS (or live CHAIN_PUT) miss; a
    # GET miss degenerates to the identity rotation (lo = hi = 0,
    # item = rows[0]).
    if ops is None:
        use_put = ~hit
        dead = None
    else:
        is_cget = ops == OP_CHAIN_GET
        is_cput = ops == OP_CHAIN_PUT
        if chain_live is None:
            dead = jnp.zeros(row_shape, bool)
        else:
            dead = (is_cget | is_cput) & (chain_live == 0)
        is_putop = (ops == OP_ACCESS) | (is_cput & ~dead)
        use_put = is_putop & ~hit
    lo = jnp.where(use_put, lo_put, lo_get)
    hi = jnp.where(use_put, hi_put, hi_get)
    new_item = tuple(qk) + tuple(qv)
    if cfg.cost_planes:
        new_item += (jnp.zeros(row_shape, jnp.int32) if qc is None else qc,)
    item = tuple(jnp.where(use_put, n, o) for n, o in zip(new_item, at_pos))

    at_lo = lane == lo
    moves = (lane > lo) & (lane <= hi)
    out = tuple(
        jnp.where(at_lo, it, jnp.where(moves, pltpu.roll(r, 1, 0), r))
        for r, it in zip(rows, item))

    # a hit "displaces" the item itself — normalize to the EMPTY sentinel so
    # callers can test ev[0] != EMPTY_KEY (identical to the jnp oracle)
    displaced = pick(lane == hi)
    empty_ev = tuple(jnp.full(row_shape, EMPTY_KEY if c < kp else 0,
                              jnp.int32) for c in range(cfg.planes))

    if ops is None:
        ev = tuple(jnp.where(hit, x, d) for x, d in zip(empty_ev, displaced))
        return out, hit, pos, at_pos, ev

    is_del = ops == OP_DELETE
    is_look = ops == OP_LOOKUP
    # DELETE: kill key plane 0 at the hit lane; LOOKUP (and a dead chain
    # row): pass rows through.
    kill = lane == jnp.where(hit & is_del, pos_c, -1)
    keep = _rowmask(is_look | dead, shape)
    delm = _rowmask(is_del, shape)
    out = tuple(
        jnp.where(delm, jnp.where(kill, jnp.int32(EMPTY_KEY), r) if c == 0
                  else r, jnp.where(keep, r, o))
        for c, (r, o) in enumerate(zip(rows, out)))

    zero_out = is_del | dead
    no_ev = hit | ~is_putop
    ev = tuple(jnp.where(no_ev, x, d) for x, d in zip(empty_ev, displaced))
    pos_out = jnp.where(zero_out, -1, pos)
    val_out = tuple(jnp.where(zero_out, 0, x) for x in at_pos)
    return out, hit & ~dead, pos_out, val_out, ev


def _chain_body(cfg: MSLRUConfig, qk, qv, ops, lrank, served,
                chain_live=None, qc=None):
    """fori_loop body resolving one duplicate-chain step (shared verbatim by
    the Pallas one-pass kernel and its jnp mirror in ops.py).

    Operands are in the plane layout of ``_transition``; ``lrank`` (1, BB)
    int32 and ``served`` (1, BB) bool.  State: (cur chain rows, after
    committed rows, hit, pos, val, ev).  At step r the queries with chain
    rank r apply their transition — selected per row by ``ops`` plus the
    ``chain_live`` execute mask for CHAIN_GET/CHAIN_PUT rows (identity when
    not ``served``) — commit into ``after``, and hand the updated row to
    rank r+1 by a one-lane roll over the query axis (sorted order makes
    chain neighbours adjacent).
    """
    kp, v = cfg.key_planes, cfg.value_planes

    def body(r, state):
        cur, after, h, po, va, ev = state
        shape = cur[0].shape
        new_rows, hitv, posv, valv, evv = _transition(cfg, cur, qk, qv, ops,
                                                      chain_live, qc)
        active = lrank == r
        act = active & served                 # dropped queries: identity
        act_m = _rowmask(act, shape)
        active_m = _rowmask(active, shape)
        after = tuple(jnp.where(active_m, jnp.where(act_m, n, c), a)
                      for n, c, a in zip(new_rows, cur, after))
        h = jnp.where(act, hitv.astype(jnp.int32), h)
        po = jnp.where(act, posv, po)
        if v:
            va = tuple(jnp.where(act, x, o) for x, o in zip(valv[kp:], va))
        ev = tuple(jnp.where(act, x, o) for x, o in zip(evv, ev))
        nxt_m = _rowmask(lrank == r + 1, shape)
        cur = tuple(jnp.where(nxt_m, pltpu.roll(a, 1, 1), c)
                    for a, c in zip(after, cur))
        return cur, after, h, po, va, ev

    return body


def _chain_state0(cfg: MSLRUConfig, rows):
    """Initial chain-loop state for gathered rows (C-tuple of (A, BB))."""
    row_shape = (1, rows[0].shape[1])
    ve = max(cfg.value_planes, 1)
    zeros = jnp.zeros(row_shape, jnp.int32)
    return (rows, rows, zeros, jnp.full(row_shape, -1, jnp.int32),
            (zeros,) * ve, (zeros,) * cfg.planes)


def _kernel_block(b: int, block_b: int) -> int:
    """Query-block size: ``block_b``, or the batch rounded up to whole
    lane tiles when it is smaller."""
    return min(block_b, -(-b // LANES) * LANES)


def _read_optional(refs, has_ops, has_chain, has_cost):
    """Optional (1, BB) operands arrive positionally in a fixed order (ops,
    chain_live, costs) keyed on the static has_* flags."""
    it = iter(refs)
    ops = next(it)[...] if has_ops else None
    chain_live = next(it)[...] if has_chain else None
    qc = next(it)[...] if has_cost else None
    return ops, chain_live, qc, list(it)


def _write_outputs(out_rows_ref, hit_ref, pos_ref, val_ref, ev_ref,
                   rows, hit, pos, val, ev):
    for c, x in enumerate(rows):
        out_rows_ref[c] = x
    hit_ref[...] = hit
    pos_ref[...] = pos
    for i, x in enumerate(val):
        val_ref[i] = x
    for c, x in enumerate(ev):
        ev_ref[c] = x


def _kernel(cfg: MSLRUConfig, has_ops: bool, has_chain: bool, has_cost: bool,
            krows_ref, qkey_ref, qval_ref, *refs):
    ops, chain_live, qc, outs = _read_optional(refs, has_ops, has_chain,
                                               has_cost)
    kp, v = cfg.key_planes, cfg.value_planes
    out, hit, pos, val, ev = _transition(
        cfg, split_planes(krows_ref), split_planes(qkey_ref),
        split_planes(qval_ref)[:v], ops, chain_live, qc)
    val = val[kp:kp + v] if v else (jnp.zeros_like(pos),)
    _write_outputs(*outs, out, hit.astype(jnp.int32), pos, val, ev)


def _out_specs_shapes(a, c, ve, bb, bp, index_map):
    """Output BlockSpecs and shapes shared by both kernels: rows_after
    (C, A, B), hit (1, B), pos (1, B), value (Ve, 1, B), ev (C, 1, B)."""
    row_spec = pl.BlockSpec((c, a, bb), lambda *ix: (0, 0, index_map(*ix)))
    flat_spec = pl.BlockSpec((1, bb), lambda *ix: (0, index_map(*ix)))

    def vec_spec(n):
        return pl.BlockSpec((n, 1, bb), lambda *ix: (0, 0, index_map(*ix)))

    specs = [row_spec, flat_spec, flat_spec, vec_spec(ve), vec_spec(c)]
    shapes = (
        jax.ShapeDtypeStruct((c, a, bp), jnp.int32),
        jax.ShapeDtypeStruct((1, bp), jnp.int32),
        jax.ShapeDtypeStruct((1, bp), jnp.int32),
        jax.ShapeDtypeStruct((ve, 1, bp), jnp.int32),
        jax.ShapeDtypeStruct((c, 1, bp), jnp.int32),
    )
    return specs, shapes, row_spec, flat_spec, vec_spec


def _unpack_outputs(out, b, v):
    """Kernel outputs -> (planes (C, A, B), hit, pos, value (B, V), ev)."""
    planes_o, hit_o, pos_o, val_o, ev_o = out
    return (planes_o[..., :b], hit_o[0, :b], pos_o[0, :b],
            planes_to_cols(val_o)[:b, :v], planes_to_cols(ev_o)[:b])


@functools.partial(jax.jit, static_argnames=("cfg", "block_b", "interpret"))
def msl_access_kernel_call(rows, qkeys, qvals, ops=None, chain_live=None,
                           costs=None, *,
                           cfg: MSLRUConfig, block_b: int = 2048,
                           interpret: bool = True):
    """Fused multi-step LRU op over pre-gathered rows.

    rows (B, A, C) int32; qkeys (B, KP); qvals (B, V); ops (B,) optional
    opcode vector — ``None`` compiles the ACCESS-only kernel with no opcode
    operand (the legacy hot path, zero overhead); chain_live (B,) optional
    int32 execute mask for CHAIN_GET/CHAIN_PUT rows (requires ``ops``);
    costs (B,) optional int32 insert costs (only meaningful when
    cfg.cost_planes — ``None`` inserts cost 0).
    B is padded to a multiple of the query block with EMPTY queries (their
    outputs are sliced away).  Returns the same tuple as ref.msl_access_ref.
    """
    b, a, c = rows.shape
    kp, v = cfg.key_planes, cfg.value_planes
    ve = max(v, 1)  # BlockSpec needs >= 1 plane; dummy sliced off below
    has_ops = ops is not None
    has_chain = chain_live is not None
    has_cost = costs is not None
    assert not (has_chain and not has_ops), "chain_live requires ops"
    bb = _kernel_block(b, block_b)
    pad = (-b) % bb
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.broadcast_to(_empty_row(cfg), (pad, a, c))])
        qkeys = jnp.concatenate([qkeys, jnp.zeros((pad, kp), jnp.int32)])
        qvals = jnp.concatenate([qvals, jnp.zeros((pad, v), jnp.int32)])
        if has_ops:
            ops = jnp.concatenate(
                [ops, jnp.full((pad,), OP_ACCESS, jnp.int32)])
        if has_chain:
            chain_live = jnp.concatenate(
                [chain_live, jnp.zeros((pad,), jnp.int32)])
        if has_cost:
            costs = jnp.concatenate([costs, jnp.zeros((pad,), jnp.int32)])
    bp = b + pad
    qvals_e = qvals if v else jnp.zeros((bp, 1), jnp.int32)

    out_specs, out_shapes, row_spec, flat_spec, vec_spec = _out_specs_shapes(
        a, c, ve, bb, bp, lambda i: i)
    extra = [x[None, :] for x in (ops, chain_live, costs) if x is not None]
    out = pl.pallas_call(
        functools.partial(_kernel, cfg, has_ops, has_chain, has_cost),
        grid=(bp // bb,),
        in_specs=[row_spec, vec_spec(kp), vec_spec(ve)]
        + [flat_spec] * len(extra),
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        name="msl_access_kernel_call",
    )(rows_to_planes(rows), cols_to_planes(qkeys), cols_to_planes(qvals_e),
      *extra)
    planes_o, *rest = _unpack_outputs(out, b, v)
    return (planes_to_rows(planes_o), *rest)


def _onepass_kernel(cfg: MSLRUConfig, has_ops: bool, has_chain: bool,
                    has_cost: bool,
                    nrounds_ref, cont_ref, krows_ref, qkey_ref, qval_ref,
                    *refs):
    ops, chain_live, qc, refs = _read_optional(refs, has_ops, has_chain,
                                               has_cost)
    lrank_ref, served_ref = refs[:2]
    outs = refs[2:7]
    carry_ref = refs[7]
    pid = pl.program_id(0)
    v = cfg.value_planes

    rows = split_planes(krows_ref)            # C x (A, BB) gathered rows
    qk = split_planes(qkey_ref)               # KP x (1, BB), sorted by set id
    qv = split_planes(qval_ref)[:v]
    lrank = lrank_ref[...]                    # (1, BB) rank in duplicate chain
    served = served_ref[...] != 0             # (1, BB)

    # Splice the cross-block carry into query 0: when the first query
    # continues the previous block's duplicate chain, its gathered row is
    # stale (another chain member already updated the set on-chip).  The
    # carry holds the previous block's committed rows rolled by one lane,
    # so its lane 0 is that block's last query.
    shape = rows[0].shape
    qidx = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    splice = qidx == jnp.where(cont_ref[pid] != 0, 0, -1)
    rows = tuple(jnp.where(splice, carry_ref[c], r)
                 for c, r in enumerate(rows))

    n_rounds = nrounds_ref[pid]               # scalar-prefetched trip count
    _, after, h, po, va, ev = jax.lax.fori_loop(
        0, n_rounds,
        _chain_body(cfg, qk, qv, ops, lrank, served, chain_live, qc),
        _chain_state0(cfg, rows))

    _write_outputs(*outs, after, h, po, va, ev)
    for c, x in enumerate(after):
        carry_ref[c] = pltpu.roll(x, 1, 1)


@functools.partial(jax.jit, static_argnames=("cfg", "block_b", "interpret"))
def msl_onepass_kernel_call(planes, qkeys, qvals, ops, lrank, served, nrounds,
                            cont, chain_live=None, costs=None, *,
                            cfg: MSLRUConfig,
                            block_b: int = 2048, interpret: bool = True):
    """Conflict-aware single-pass mixed-op batch over *sorted-by-set-id* queries.

    planes (C, A, B) int32 — set rows gathered once, in the kernel's plane
    layout (only the entry at each duplicate chain's head needs to be live;
    the rest are resolved on-chip);
    qkeys (B, KP); qvals (B, V); ops (B,) sorted opcodes (each chain step
    applies its own query's op) or ``None`` for the ACCESS-only kernel with
    no opcode operand (the legacy hot path); lrank (B,) rank of each query
    within its block-local duplicate chain; served (B,) int32 mask (0 ⇒
    the transition is skipped, identity on the chain); nrounds
    (ceil(B/block_b),) int32 per-block chain depth and cont (same shape)
    int32 flag "this block's first query continues the previous block's
    chain" (both scalar-prefetched); chain_live (B,) optional int32
    execute mask for CHAIN_GET/CHAIN_PUT rows, sorted alongside the queries
    (the fused serving tick — computed by the prologue's segmented
    longest-prefix scan; requires ``ops``); costs (B,) optional int32
    insert costs sorted alongside the queries (only meaningful when
    cfg.cost_planes).

    B must already be a multiple of block_b (the one-pass prologue pads with
    unserved sentinel queries).  Returns (planes_after (C, A, B), hit, pos,
    value, ev) where planes_after[..., i] is the set's state *after* query
    i — the epilogue scatters it back at each chain's tail.
    """
    c, a, b = planes.shape
    kp, v = cfg.key_planes, cfg.value_planes
    ve = max(v, 1)
    has_ops = ops is not None
    has_chain = chain_live is not None
    assert not (has_chain and not has_ops), "chain_live requires ops"
    bb = min(block_b, b)
    assert b % bb == 0, "one-pass kernel expects pre-padded batch"
    qvals_e = qvals if v else jnp.zeros((b, 1), jnp.int32)

    out_specs, out_shapes, row_spec, flat_spec, vec_spec = _out_specs_shapes(
        a, c, ve, bb, b, lambda i, nr, ct: i)
    extra = [x[None, :] for x in (ops, chain_live, costs) if x is not None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // bb,),
        in_specs=[row_spec, vec_spec(kp), vec_spec(ve)]
        + [flat_spec] * (2 + len(extra)),
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((c, a, bb), jnp.int32)],  # block carry
    )
    out = pl.pallas_call(
        functools.partial(_onepass_kernel, cfg, has_ops, has_chain,
                          costs is not None),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        name="msl_onepass_kernel_call",
    )(nrounds, cont, planes, cols_to_planes(qkeys),
      cols_to_planes(qvals_e), *extra, lrank[None, :], served[None, :])
    return _unpack_outputs(out, b, v)


def _empty_row(cfg: MSLRUConfig):
    r = jnp.zeros((1, cfg.assoc, cfg.planes), jnp.int32)
    return r.at[:, :, 0].set(EMPTY_KEY)
