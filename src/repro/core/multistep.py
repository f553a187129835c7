"""Multi-step LRU set-associative cache (the paper's contribution) in JAX.

State layout
------------
One int32 array ``table`` of shape (S, A, C):

  * S = num_sets (power of two; a key is assigned to a set by fmix32 hash)
  * A = M*P lanes per set, ordered hot->cold: lane a = m*P + p where m is the
    vector index (0 = hottest vector) and p the in-vector position (0 = MRU).
    The set's global LRU victim is always lane A-1 — eviction needs no scan.
  * C = key_planes + value_planes + cost_planes "planes": plane 0..KP-1 hold
    the key (KP=1 for 32-bit keys — the TPU-native lane width — or KP=2 for
    the paper's 64-bit keys as (hi, lo) int32 planes), the next hold the
    value (e.g. 2 planes = a 64-bit pointer, or 1 plane = a KV-page index),
    and an optional final plane holds the item's re-prefill *cost* — see
    "Cost plane and victim choice" in core/engine.py.

Because recency/frequency are encoded purely in lane *order*, there is no
per-item LRU metadata — the paper's core property.  Every mutation is one
``rotate_insert`` over a lane range (see invector.py), applied to all C
planes identically, so the whole transition is a handful of full-rate VPU
selects regardless of which case (promote / upgrade / fill / evict) fires.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.invector import EMPTY_KEY, get_update_lo

__all__ = [
    "MSLRUConfig",
    "AccessResult",
    "OP_ACCESS",
    "OP_GET",
    "OP_DELETE",
    "OP_LOOKUP",
    "OP_CHAIN_GET",
    "OP_CHAIN_PUT",
    "init_table",
    "row_lookup",
    "row_get",
    "row_put",
    "row_access",
    "row_access_ev",
    "row_delete",
    "row_apply",
    "row_apply_ev",
    "set_index_for",
]

POLICY_MULTISTEP = "multistep"
POLICY_SET_LRU = "set_lru"  # exact LRU *within* each set (baseline)

# Per-query opcodes (the paper's §III.B operation set).  The numeric values
# are part of the on-device ABI: they travel through sort prologues, Pallas
# kernel operands, and all_to_all payload planes.  policies.py mirrors them
# for the pure-Python oracle (asserted equal in tests).  Queries a bounded
# sharded route sheds (``served`` False) execute NO op at all and report a
# plain miss — see "Sheds and canonical ordering" in core/engine.py for how
# that composes with the chain ops and the serving tier's retry queue.
OP_ACCESS = 0  # get; on miss, put (the paper's benchmark op)
OP_GET = 1     # get only (a miss leaves the cache untouched)
OP_DELETE = 2  # invalidate in place
OP_LOOKUP = 3  # read-only probe (no recency update, no mutation)
# Chain-segmented ops (the fused serving tick).  Queries carrying these ops
# come with a chain id; the engine derives a per-query execute mask from the
# chain's longest-hit prefix (the segmented cumulative AND — see
# engine.chain_exec_from_hits) and hands it to the row transition as
# ``chain_live``: a CHAIN_GET row behaves as GET while its chain is still
# all-hits and degrades to a reported-miss no-op past the chain's first
# miss; a CHAIN_PUT row is the mirror image — a no-op while its chunk index
# is inside the chain's hit prefix, an ACCESS (insert) past it.
OP_CHAIN_GET = 4
OP_CHAIN_PUT = 5


@dataclasses.dataclass(frozen=True)
class MSLRUConfig:
    """Static configuration of a multi-step LRU cache."""

    num_sets: int               # S, power of two
    m: int = 2                  # vectors per set (M); m=1 == in-vector LRU
    p: int = 4                  # lanes per vector (P); AVX2/64-bit analogue
    key_planes: int = 1         # 1 => 32-bit keys, 2 => 64-bit (hi,lo)
    value_planes: int = 2       # 2 => 64-bit values (pointers)
    cost_planes: int = 0        # 1 => cost-aware victim choice (one int32 plane)
    policy: str = POLICY_MULTISTEP

    def __post_init__(self):
        assert self.num_sets > 0 and (self.num_sets & (self.num_sets - 1)) == 0, (
            "num_sets must be a power of two")
        assert self.m >= 1 and self.p >= 1
        assert self.key_planes in (1, 2)
        assert self.value_planes >= 0
        assert self.cost_planes in (0, 1)
        assert self.policy in (POLICY_MULTISTEP, POLICY_SET_LRU)

    @property
    def assoc(self) -> int:  # A
        return self.m * self.p

    @property
    def planes(self) -> int:  # C
        return self.key_planes + self.value_planes + self.cost_planes

    @property
    def capacity(self) -> int:
        return self.num_sets * self.assoc


class AccessResult(NamedTuple):
    """Outcome of a batch of cache operations (all int32 arrays)."""

    hit: jnp.ndarray            # (B,) bool
    value: jnp.ndarray          # (B, value_planes) value of the hit item (garbage if miss)
    pos: jnp.ndarray            # (B,) flat lane of the hit, -1 on miss (pos//P = vector, for Fig.12)
    evicted_key: jnp.ndarray    # (B, key_planes) key displaced by a put (EMPTY if none)
    evicted_val: jnp.ndarray    # (B, value_planes)
    evicted_valid: jnp.ndarray  # (B,) bool — True when a real item was evicted


def init_table(cfg: MSLRUConfig) -> jnp.ndarray:
    """Empty cache: key plane 0 = EMPTY_KEY sentinel, everything else 0."""
    t = jnp.zeros((cfg.num_sets, cfg.assoc, cfg.planes), jnp.int32)
    return t.at[:, :, 0].set(EMPTY_KEY)


def set_columns(table: jnp.ndarray) -> jnp.ndarray:
    """(S, A, C) table -> its (C*A, S) plane view, one column per set.

    On TPU the table's HBM layout keeps the set axis minor (the compact
    layout of a shape whose trailing axes are small), so this view costs
    nothing, and gathering or updating set *columns* of it leaves that
    layout alone; a row gather on the (S, A, C) shape instead re-lays the
    whole table out with its trailing axis padded to 128 lanes (21x at
    A=8, C=3)."""
    s, a, c = table.shape
    return jnp.transpose(table, (2, 1, 0)).reshape(c * a, s)


def set_rows(view: jnp.ndarray, a: int, c: int) -> jnp.ndarray:
    """Inverse of ``set_columns``."""
    return jnp.transpose(view.reshape(c, a, -1), (2, 1, 0))


def set_index_for(cfg: MSLRUConfig, qkeys: jnp.ndarray) -> jnp.ndarray:
    """Set assignment by MurmurHash3 finalizer over key plane(s). qkeys: (B, KP)."""
    if cfg.key_planes == 1:
        return hashing.set_index(qkeys[..., 0], cfg.num_sets)
    hi, lo = hashing.fmix64_planes(qkeys[..., 0], qkeys[..., 1])
    return (lo & jnp.uint32(cfg.num_sets - 1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Lane helpers operating on plane-carrying rows (..., A, C)
# ---------------------------------------------------------------------------

def _lane(rows: jnp.ndarray) -> jnp.ndarray:
    """Lane iota along the A axis of (..., A, C) rows."""
    return jax.lax.broadcasted_iota(jnp.int32, rows.shape[:-1], rows.ndim - 2)


def _find_key_planes(cfg: MSLRUConfig, rows: jnp.ndarray, qkeys: jnp.ndarray) -> jnp.ndarray:
    """Flat lane of the key match (-1 if absent). rows (..., A, C), qkeys (..., KP)."""
    kp = cfg.key_planes
    hit = jnp.all(rows[..., :kp] == qkeys[..., None, :], axis=-1)
    lane = _lane(rows)
    return jnp.max(jnp.where(hit, lane, -1), axis=-1)


def _find_deepest_empty_planes(rows: jnp.ndarray) -> jnp.ndarray:
    lane = _lane(rows)
    return jnp.max(jnp.where(rows[..., 0] == EMPTY_KEY, lane, -1), axis=-1)


def _rotate_insert_planes(rows, lo, hi, item):
    """rotate_insert (invector.py) applied to all C planes of (..., A, C) rows.

    lo, hi: (...,); item: (..., C).  Returns (new_rows, displaced (..., C)).
    """
    lane = _lane(rows)[..., None]                      # (..., A, 1)
    lo_b = lo[..., None, None]
    hi_b = hi[..., None, None]
    shifted = jnp.roll(rows, 1, axis=-2)
    out = jnp.where(
        lane == lo_b,
        item[..., None, :],
        jnp.where((lane > lo_b) & (lane <= hi_b), shifted, rows),
    )
    idx = hi[..., None, None].astype(jnp.int32)
    displaced = jnp.take_along_axis(rows, jnp.broadcast_to(idx, rows.shape[:-2] + (1, rows.shape[-1])), axis=-2)[..., 0, :]
    return out, displaced


# ---------------------------------------------------------------------------
# Row-level operations (batched over a leading dim; rows (B, A, C))
# ---------------------------------------------------------------------------

def row_lookup(cfg: MSLRUConfig, rows: jnp.ndarray, qkeys: jnp.ndarray):
    """Read-only probe: (hit (B,), value (B, V), pos (B,))."""
    pos = _find_key_planes(cfg, rows, qkeys)
    hit = pos >= 0
    pos_c = jnp.maximum(pos, 0)
    item = jnp.take_along_axis(
        rows, jnp.broadcast_to(pos_c[..., None, None], rows.shape[:-2] + (1, rows.shape[-1])), axis=-2
    )[..., 0, :]
    return hit, item[..., cfg.key_planes:cfg.key_planes + cfg.value_planes], pos


def row_get(cfg: MSLRUConfig, rows: jnp.ndarray, qkeys: jnp.ndarray):
    """get: probe + recency update (promote within vector / upgrade across).

    Returns (new_rows, hit, value, pos).  A miss is a provable no-op: the
    rotation degenerates to re-writing lane 0 with itself.
    """
    pos = _find_key_planes(cfg, rows, qkeys)
    hit = pos >= 0
    pos_c = jnp.maximum(pos, 0)
    item = jnp.take_along_axis(
        rows, jnp.broadcast_to(pos_c[..., None, None], rows.shape[:-2] + (1, rows.shape[-1])), axis=-2
    )[..., 0, :]
    if cfg.policy == POLICY_SET_LRU:
        lo = jnp.zeros_like(pos_c)
    else:
        lo = get_update_lo(pos_c, cfg.p)
    new_rows, _ = _rotate_insert_planes(rows, lo, pos_c, item)
    return new_rows, hit, item[..., cfg.key_planes:cfg.key_planes + cfg.value_planes], pos


def _empty_ev_planes(cfg: MSLRUConfig, like: jnp.ndarray) -> jnp.ndarray:
    """Sentinel eviction record: key planes EMPTY_KEY, all other planes 0."""
    col = jax.lax.broadcasted_iota(jnp.int32, like.shape, like.ndim - 1)
    return jnp.where(col < cfg.key_planes, EMPTY_KEY, 0)


def row_put(cfg: MSLRUConfig, rows: jnp.ndarray, new_key: jnp.ndarray,
            new_val: jnp.ndarray, new_cost: jnp.ndarray | None = None):
    """put: insert a (known-absent) item; fill deepest hole or evict.

    new_key (B, KP), new_val (B, V), new_cost (B,) int32 (ignored unless
    cfg.cost_planes; None inserts cost 0).  The victim for a full set is lane
    A-1 (the paper's zero-scan global LRU) unless the config carries a cost
    plane, in which case it is the cheapest lane of the eviction-candidate
    segment — the last vector (the whole set under set_lru) — with ties
    broken toward the deepest lane, so a uniform cost plane degenerates to
    exactly lane A-1.  Returns (new_rows, displaced (B, C), evicted_valid).
    """
    e = _find_deepest_empty_planes(rows)
    a = cfg.assoc
    if cfg.cost_planes:
        lane = _lane(rows)
        ccol = rows[..., cfg.key_planes + cfg.value_planes]
        seg_lo = 0 if cfg.policy == POLICY_SET_LRU else (cfg.m - 1) * cfg.p
        cand = jnp.where(lane >= seg_lo, ccol, jnp.int32(2**31 - 1))
        cmin = jnp.min(cand, axis=-1)
        victim = jnp.max(jnp.where(cand == cmin[..., None], lane, -1), axis=-1)
    else:
        victim = jnp.full_like(e, a - 1)
    pos_ins = jnp.where(e >= 0, e, victim)
    if cfg.policy == POLICY_SET_LRU:
        lo = jnp.zeros_like(pos_ins)
    else:
        # MRU slot of the vector holding the insertion lane; for a full set
        # the victim lies in the last vector so lo = (M-1)*P, per the paper.
        lo = (pos_ins // cfg.p) * cfg.p
    parts = [new_key]
    if cfg.value_planes:
        parts.append(new_val)
    if cfg.cost_planes:
        qc = jnp.zeros(new_key.shape[:-1], jnp.int32) if new_cost is None else new_cost
        parts.append(qc[..., None].astype(jnp.int32))
    item = jnp.concatenate(parts, axis=-1) if len(parts) > 1 else new_key
    new_rows, displaced = _rotate_insert_planes(rows, lo, pos_ins, item)
    ev_valid = displaced[..., 0] != EMPTY_KEY
    return new_rows, displaced, ev_valid


def row_access_ev(cfg: MSLRUConfig, rows: jnp.ndarray, qkeys: jnp.ndarray,
                  qvals: jnp.ndarray, costs: jnp.ndarray | None = None):
    """row_access that also returns the full (B, C) eviction record.

    ``ev`` carries the displaced planes of an evicting put and the EMPTY
    sentinel row everywhere else — the same contract as the Pallas kernels'
    C-wide ev output, so ref.msl_access_ref can stay bit-comparable to the
    kernels when a cost plane widens C past key+value.
    """
    got_rows, hit, value, pos = row_get(cfg, rows, qkeys)
    put_rows, displaced, ev_ok = row_put(cfg, rows, qkeys, qvals, costs)
    new_rows = jnp.where(hit[..., None, None], got_rows, put_rows)
    ev_ok = ev_ok & ~hit
    ev = jnp.where(hit[..., None], _empty_ev_planes(cfg, displaced), displaced)
    kp, v = cfg.key_planes, cfg.value_planes
    res = AccessResult(
        hit=hit,
        value=value,
        pos=pos,
        evicted_key=ev[..., :kp],
        evicted_val=ev[..., kp:kp + v],
        evicted_valid=ev_ok,
    )
    return new_rows, res, ev


def row_access(cfg: MSLRUConfig, rows: jnp.ndarray, qkeys: jnp.ndarray,
               qvals: jnp.ndarray, costs: jnp.ndarray | None = None):
    """The paper's benchmark op: get, and on miss put (key, val).

    Fuses row_get and row_put with per-row selection so a (B, A, C) batch with
    mixed hits/misses stays branch-free.  Returns (new_rows, AccessResult).
    """
    new_rows, res, _ = row_access_ev(cfg, rows, qkeys, qvals, costs)
    return new_rows, res


def row_delete(cfg: MSLRUConfig, rows: jnp.ndarray, qkeys: jnp.ndarray):
    """delete: invalidate in place (paper §III.B); no compaction."""
    pos = _find_key_planes(cfg, rows, qkeys)
    hit = pos >= 0
    lane = _lane(rows)
    kill = (lane == pos[..., None]) & hit[..., None]
    key0 = jnp.where(kill, EMPTY_KEY, rows[..., 0])
    new_rows = rows.at[..., 0].set(key0)
    return new_rows, hit


def row_apply_ev(cfg: MSLRUConfig, rows: jnp.ndarray, qkeys: jnp.ndarray,
                 qvals: jnp.ndarray, ops: jnp.ndarray,
                 chain_live: jnp.ndarray | None = None,
                 costs: jnp.ndarray | None = None):
    """Branch-free mixed-op transition: per-row opcode selects the op.

    rows (B, A, C); qkeys (B, KP); qvals (B, V); ops (B,) int32 OP_* codes;
    chain_live (B,) bool execute mask for CHAIN_GET/CHAIN_PUT rows (derived
    by engine.chain_exec_from_hits; ignored for the four plain ops; ``None``
    treats every chain row as live — CHAIN_GET ≡ GET, CHAIN_PUT ≡ ACCESS);
    costs (B,) int32 insert costs (only read when cfg.cost_planes).
    All transitions are computed once over the whole batch and the opcode
    picks per row — the batch stays SPMD regardless of the op mix.  Returns
    (new_rows, AccessResult) with one normalized result contract for every
    engine (see the opcode table in engine.py):

      * hit/pos/value come from the probe for LOOKUP/GET/ACCESS and live
        chain rows; DELETE reports hit (found) but pos = -1 and value = 0;
        a dead (downgraded) chain row reports a plain miss,
      * evicted_* fire only for an evicting ACCESS / live-CHAIN_PUT insert;
        everywhere else evicted_key carries the EMPTY_KEY sentinel (never
        query garbage).

    Returns (new_rows, AccessResult, ev) where ev is the full (B, C)
    eviction record (see row_access_ev).
    """
    is_acc = ops == OP_ACCESS
    is_del = ops == OP_DELETE
    is_look = ops == OP_LOOKUP
    is_chain = (ops == OP_CHAIN_GET) | (ops == OP_CHAIN_PUT)
    if chain_live is None:
        dead = jnp.zeros(ops.shape, bool)
    else:
        dead = is_chain & ~chain_live
    is_putop = is_acc | ((ops == OP_CHAIN_PUT) & ~dead)

    got_rows, hit, value, pos = row_get(cfg, rows, qkeys)
    put_rows, displaced, ev_ok = row_put(cfg, rows, qkeys, qvals, costs)
    del_rows, _ = row_delete(cfg, rows, qkeys)

    # GET (and a live CHAIN_GET) falls back to got_rows, which is a provable
    # identity on a miss; dead chain rows pass the row through like LOOKUP.
    acc_or_get = jnp.where((is_putop & ~hit)[..., None, None], put_rows, got_rows)
    new_rows = jnp.where(
        is_del[..., None, None], del_rows,
        jnp.where((is_look | dead)[..., None, None], rows, acc_or_get))

    evicting = is_putop & ~hit
    zero_out = is_del | dead
    ev = jnp.where(evicting[..., None], displaced, _empty_ev_planes(cfg, displaced))
    kp, v = cfg.key_planes, cfg.value_planes
    res = AccessResult(
        hit=hit & ~dead,
        value=jnp.where(zero_out[..., None], 0, value),
        pos=jnp.where(zero_out, -1, pos),
        evicted_key=ev[..., :kp],
        evicted_val=ev[..., kp:kp + v],
        evicted_valid=evicting & ev_ok,
    )
    return new_rows, res, ev


def row_apply(cfg: MSLRUConfig, rows: jnp.ndarray, qkeys: jnp.ndarray,
              qvals: jnp.ndarray, ops: jnp.ndarray,
              chain_live: jnp.ndarray | None = None,
              costs: jnp.ndarray | None = None):
    """row_apply_ev without the kernel-parity ev record (the engine API)."""
    new_rows, res, _ = row_apply_ev(cfg, rows, qkeys, qvals, ops, chain_live, costs)
    return new_rows, res
