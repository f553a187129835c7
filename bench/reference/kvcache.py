"""Plain reference of the multi-step LRU cache, one set at a time, in Python.

Semantics (Wang et al., "Multi-step LRU", and the YCSB look-aside use):

* A key lives in set ``fmix32(key) & (num_sets - 1)`` (MurmurHash3's 32-bit
  finalizer).  A set is ``m`` vectors of ``p`` lanes, hottest lane first.
* get, hit at lane ``pos``: inside a vector the item moves to the vector's
  first lane; at a vector's first lane it swaps with the last lane of the
  vector before (one step up); at lane 0 nothing moves.  The answer is the
  item's value and ``pos``.
* get, miss, fill: the item goes into the deepest empty lane, or, in a full
  set, over the last lane (the evicted item); either way it then moves to the
  first lane of that lane's vector, pushing the lanes between down by one.
* delete: the key's lane is marked empty where it stands.

Each set evolves on its own, so replaying the operations of a few sets in
stream order checks every answer and final row of those sets exactly.
"""

from __future__ import annotations

import numpy as np

EMPTY = -(2**31)
GET_FILL, DELETE = 0, 1


def fmix32_sets(keys: np.ndarray, num_sets: int) -> np.ndarray:
    h = np.asarray(keys).astype(np.uint32)
    with np.errstate(over="ignore"):
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return (h & np.uint32(num_sets - 1)).astype(np.int64)


class SetReplay:
    """One set: lane keys and lane values (tuples), hottest lane first."""

    def __init__(self, row, p: int, promote: bool = True):
        self.keys = [int(k) for k, _ in row]
        self.vals = [tuple(int(x) for x in v) for _, v in row]
        self.p = p
        self.promote = promote          # False: the control, no recency

    def _find(self, key):
        try:
            return len(self.keys) - 1 - self.keys[::-1].index(key)
        except ValueError:
            return -1

    def _rotate(self, lo, hi, key, val):
        ks, vs = self.keys, self.vals
        out = ks[hi], vs[hi]
        ks[lo + 1:hi + 1] = ks[lo:hi]
        vs[lo + 1:hi + 1] = vs[lo:hi]
        ks[lo], vs[lo] = key, val
        return out

    def get_fill(self, key, value):
        """-> (hit, value, pos, evicted_key, evicted_value, evicted_valid)"""
        pos = self._find(key)
        if pos >= 0:
            val = self.vals[pos]
            if self.promote and pos:
                lo = (pos // self.p) * self.p if pos % self.p else pos - 1
                self._rotate(lo, pos, key, val)
            return True, val, pos, EMPTY, None, False
        ks = self.keys
        try:
            lane = len(ks) - 1 - ks[::-1].index(EMPTY)
        except ValueError:
            lane = len(ks) - 1
        ev_key, ev_val = self._rotate((lane // self.p) * self.p, lane, key,
                                      value)
        valid = ev_key != EMPTY
        return False, None, -1, ev_key, ev_val if valid else None, valid

    def delete(self, key):
        pos = self._find(key)
        if pos >= 0:
            self.keys[pos] = EMPTY
        return pos >= 0

    @property
    def row(self):
        return list(zip(self.keys, self.vals))


FIELDS = ("hit", "value", "pos", "evicted_key", "evicted_value",
          "evicted_valid")


def replay(rows_before: dict, p: int, keys, values, ops, sets,
           answers: dict, promote: bool = True):
    """Replay the operations of the sets in ``rows_before`` in stream order.

    rows_before: set -> list of (key, value) lanes.  keys (N,), values (N, V),
    ops (N,) GET_FILL/DELETE, sets (N,) of every operation; ``answers``
    holds the program's answers for the fields it returns (a subset of
    ``FIELDS``; "value" and "evicted_value" are (N, V)).

    Returns (mismatched operations, compared operations, final rows)."""
    states = {s: SetReplay(r, p, promote) for s, r in rows_before.items()}
    idx = np.flatnonzero(np.isin(sets, np.fromiter(states, np.int64)))
    idx = idx[np.argsort(sets[idx], kind="stable")]
    # plain Python values of the sampled operations only
    k_l = keys[idx].tolist()
    s_l = sets[idx].tolist()
    o_l = ops[idx].tolist()
    v_l = [tuple(v) for v in values[idx].tolist()]
    have = {}
    for f in FIELDS:
        if f in answers:
            a = np.asarray(answers[f])[idx]
            if f in ("value", "evicted_value"):
                have[f] = [tuple(v) for v in a.reshape(len(idx), -1).tolist()]
            else:
                have[f] = a.reshape(len(idx)).tolist()
    bad = 0
    for j in range(len(idx)):
        st = states[s_l[j]]
        if o_l[j] == DELETE:
            want = (st.delete(k_l[j]), None, -1, EMPTY, None, False)
        else:
            want = st.get_fill(k_l[j], v_l[j])
        bad += not _same(have, j, want)
    final = {s: st.row for s, st in states.items()}
    return bad, len(idx), final


def answers_of(rows_before: dict, p: int, keys, values, ops, sets,
               promote: bool = True):
    """The answers this reference gives to every operation of the sets in
    ``rows_before`` (other operations read as misses), as the program's
    fields, and its final rows; ``promote=False`` is the control."""
    n, v = len(keys), np.asarray(values).shape[-1]
    out = {"hit": np.zeros(n, bool), "value": np.zeros((n, v), np.int64),
           "pos": np.full(n, -1, np.int64),
           "evicted_key": np.full((n, 1), EMPTY, np.int64),
           "evicted_value": np.zeros((n, v), np.int64),
           "evicted_valid": np.zeros(n, bool)}
    states = {s: SetReplay(r, p, promote) for s, r in rows_before.items()}
    idx = np.flatnonzero(np.isin(sets, np.fromiter(states, np.int64)))
    for i in idx:
        st = states[int(sets[i])]
        if ops[i] == DELETE:
            out["hit"][i] = st.delete(int(keys[i]))
            continue
        hit, val, pos, ev_key, ev_val, ev_valid = st.get_fill(
            int(keys[i]), tuple(int(x) for x in values[i]))
        out["hit"][i], out["pos"][i] = hit, pos
        out["evicted_key"][i, 0], out["evicted_valid"][i] = ev_key, ev_valid
        if val is not None:
            out["value"][i] = val
        if ev_val is not None:
            out["evicted_value"][i] = ev_val
    return out, {s: st.row for s, st in states.items()}


def _same(have, j, want) -> bool:
    hit, value, pos, ev_key, ev_val, ev_valid = want
    if "hit" in have and bool(have["hit"][j]) != hit:
        return False
    if hit and value is not None and "value" in have:
        if have["value"][j] != value:
            return False
    if "pos" in have and have["pos"][j] != pos:
        return False
    if "evicted_valid" in have and bool(have["evicted_valid"][j]) != ev_valid:
        return False
    if "evicted_key" in have and have["evicted_key"][j] != ev_key:
        return False
    if ev_valid and "evicted_value" in have:
        if have["evicted_value"][j] != ev_val:
            return False
    return True


def rows_differ(final: dict, rows_after: dict) -> int:
    """Sets whose final row differs: every lane's key, and the value of
    every lane that holds a key (an empty lane's stale value is no answer)."""
    bad = 0
    for s, row in final.items():
        got = [(int(k), tuple(int(x) for x in v)) for k, v in rows_after[s]]
        if any(a[0] != b[0] or (a[0] != EMPTY and a[1] != b[1])
               for a, b in zip(row, got)):
            bad += 1
    return bad
