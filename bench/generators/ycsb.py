"""YCSB core-workload requests for a look-aside key-value cache.

Keys follow YCSB's own generators (``site.ycsb.generator``):

* ``zipfian``: ``ScrambledZipfianGenerator`` — a rank from the closed-form
  zipfian of Gray et al. over 10**10 items with YCSB's precomputed zeta
  (``ZETAN``), then ``fnvhash64(rank) % key_space``.  No CDF is built.
* ``uniform``: ``UniformLongGenerator`` over ``[0, key_space)``.

Operations: a read is a get that fills on a miss (look-aside: the caller
fetches the record and puts it); an update writes the record and
invalidates the cached copy (delete on update).

Batch ``i`` of seed ``s`` is drawn from ``numpy.random.default_rng((s, i))``
alone, so batches can be made in any order, ahead of time or on another
thread, and a batch is the same whoever makes it.
"""

from __future__ import annotations

import numpy as np

# site.ycsb.generator.ScrambledZipfianGenerator
ZIPF_ITEM_COUNT = 10_000_000_000
ZIPF_ZETAN = 26.46902820178302          # zeta(10**10 + 1, 0.99), YCSB's constant
ZIPF_THETA = 0.99
# site.ycsb.Utils.fnvhash64
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211

READ, UPDATE = 0, 1                      # op names of this generator


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 low-first octets of a
    long, then ``Math.abs``.  int64 in, int64 out."""
    x = np.asarray(values).astype(np.uint64)
    h = np.full(x.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= x & np.uint64(0xFF)
            h *= prime
            x >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zipf_ranks(u: np.ndarray, theta: float = ZIPF_THETA,
               zetan: float = ZIPF_ZETAN,
               items: int = ZIPF_ITEM_COUNT + 1) -> np.ndarray:
    """``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1)."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5 ** theta, 1, tail))


def scrambled_zipf_keys(u: np.ndarray, key_space: int) -> np.ndarray:
    return fnvhash64(zipf_ranks(u)) % key_space


def record_values(keys: np.ndarray) -> np.ndarray:
    """The record pointer a look-aside fill stores for each key: two int32
    planes that only that key gives (a 64-bit pointer)."""
    k = np.asarray(keys).astype(np.uint32)
    hi = (k * np.uint32(2654435761)).view(np.int32)
    lo = (k ^ np.uint32(0x5BD1E995)).view(np.int32)
    return np.stack([hi, lo], axis=-1)


class Stream:
    """The request stream of one run: ``batch(i)`` gives batch ``i`` as
    (keys int32 (B,), ops int8 (B,) of READ/UPDATE)."""

    def __init__(self, traffic: dict, key_space: int, batch_size: int,
                 seed: int):
        self.distribution = traffic["distribution"]
        if self.distribution not in ("zipfian", "uniform"):
            raise ValueError(f"unknown distribution {self.distribution!r}")
        if (self.distribution == "zipfian"
                and traffic.get("zipf_theta", ZIPF_THETA) != ZIPF_THETA):
            raise ValueError("only YCSB's zipfian constant 0.99 has a "
                             "precomputed zeta over 10**10 items")
        self.update_share = float(traffic["update_proportion"])
        read = float(traffic["read_proportion"])
        if abs(read + self.update_share - 1.0) > 1e-9:
            raise ValueError("read and update proportions must sum to 1")
        if not 0 < key_space < 2**31:
            raise ValueError("keys are int32 and must stay below 2**31 - 1 "
                             "(INT32_MIN marks an empty lane)")
        self.key_space = int(key_space)
        self.batch_size = int(batch_size)
        self.seed = int(seed)

    def batch(self, i: int):
        rng = np.random.default_rng((self.seed, int(i)))
        if self.distribution == "zipfian":
            keys = scrambled_zipf_keys(rng.random(self.batch_size),
                                       self.key_space)
        else:
            keys = rng.integers(0, self.key_space, self.batch_size)
        ops = (rng.random(self.batch_size) < self.update_share).astype(np.int8)
        return keys.astype(np.int32), ops


def popular_keys_host(ranks: np.ndarray, key_space: int) -> np.ndarray:
    """The keys of zipfian ranks (the host twin of ``popular_keys``)."""
    return (fnvhash64(ranks) % key_space).astype(np.int32)


# ---------------------------------------------------------------------------
# the same scrambling on the device, for filling a table of 10**8 - 10**9 keys
# in set-up: 64-bit FNV on (hi, lo) uint32 planes
# ---------------------------------------------------------------------------

def _mul32(a, b_const: int):
    """Full 64-bit product of uint32 ``a`` and a constant below 2**16, as
    (hi, lo) uint32."""
    import jax.numpy as jnp
    b = jnp.uint32(b_const)
    a0 = a & jnp.uint32(0xFFFF)
    a1 = a >> 16
    p0 = a0 * b                       # < 2**32
    p1 = a1 * b                       # < 2**32
    lo = p0 + (p1 << 16)
    carry = (lo < p0).astype(jnp.uint32)
    hi = (p1 >> 16) + carry
    return hi, lo


def popular_keys(ranks, key_space: int):
    """``fnvhash64(rank) % key_space`` for uint32 ranks, on the device.

    key_space must be a power of two or 2**31 - 1 (both used here); the
    64-bit value is (hi, lo) uint32 planes throughout."""
    import jax.numpy as jnp
    assert FNV_PRIME_64 == (0x100 << 32) + 0x1B3
    hi = jnp.full(ranks.shape, FNV_OFFSET_BASIS_64 >> 32, jnp.uint32)
    lo = jnp.full(ranks.shape, FNV_OFFSET_BASIS_64 & 0xFFFFFFFF, jnp.uint32)
    x = ranks.astype(jnp.uint32)      # the octets above bit 32 are zero
    for i in range(8):
        octet = (x >> (8 * i)) & jnp.uint32(0xFF) if i < 4 else jnp.uint32(0)
        lo = lo ^ octet
        # (hi, lo) * (0x100 << 32 | 0x1B3) mod 2**64
        p_hi, p_lo = _mul32(lo, 0x1B3)
        hi = p_hi + hi * jnp.uint32(0x1B3) + (lo << 8)
        lo = p_lo
    # Math.abs on the signed value
    neg = (hi >> 31) == 1
    nlo = ~lo + jnp.uint32(1)
    nhi = ~hi + (nlo == 0).astype(jnp.uint32)
    hi = jnp.where(neg, nhi, hi)
    lo = jnp.where(neg, nlo, lo)
    if key_space & (key_space - 1) == 0 and key_space <= 2**32:
        return (lo & jnp.uint32(key_space - 1)).astype(jnp.int32)
    if key_space != 2**31 - 1:
        raise ValueError("device key scrambling supports a power-of-two key "
                         "space or 2**31 - 1")
    m = jnp.uint32(2**31 - 1)

    def red(v):                       # v < 2 * m  ->  v mod m
        return jnp.where(v >= m, v - m, v)

    # x = hi * 2**32 + lo, and 2**31 = 1 (mod m), so 2**32 = 2 (mod m)
    a = red((lo & m) + (lo >> 31))
    h2 = hi << 1                      # hi < 2**31 after abs
    b = red((h2 & m) + (h2 >> 31))
    return red(a + b).astype(jnp.int32)


def record_values_device(keys):
    import jax
    import jax.numpy as jnp
    k = keys.astype(jnp.uint32)
    hi = jax.lax.bitcast_convert_type(k * jnp.uint32(2654435761), jnp.int32)
    lo = jax.lax.bitcast_convert_type(k ^ jnp.uint32(0x5BD1E995), jnp.int32)
    return jnp.stack([hi, lo], axis=-1)
