"""Traffic generation: the load keys and YCSB's request streams, from a
seed.

Keys and values are as wide as the configuration says (``key_bits``,
``value_bits``: 32 or 64), in the unsigned type of that width; the
all-ones word of the value width is the miss marker.  Record keys are
distinct and never a reserved word (0, and the all-ones word, the
index's empty-slot marker):

* at 32 bits, a fixed bijection of the record number (MurmurHash3's
  32-bit finalizer), the reserved words skipped;
* at 64 bits, YCSB's hashed keys (``insertorder=hashed``:
  ``CoreWorkload.buildKeyName`` names record ``k`` ``"user" +
  fnvhash64(k)``), a reserved word or a repeat skipped.

They do not depend on the seed, as YCSB's load keys do not.  The seed
draws the loaded values and the request stream; the streams draw record
numbers, so they are the same at either width.

The request streams are YCSB's (core workloads, ``CoreWorkload``), over
record numbers:

* ``zipfian`` is ``ScrambledZipfianGenerator`` as published: a rank
  drawn from ``ZipfianGenerator``'s closed form (Gray et al., "Quickly
  generating billion-record synthetic databases", SIGMOD 1994) with
  constant 0.99 over ``ITEM_COUNT`` = 10^10 items, whose zeta YCSB fixes
  at ``ZETAN``; the rank goes to record ``fnvhash64(rank) % (n + 1)``
  (the key chooser's range is ``[0, recordcount]``), and a draw past the
  last record is drawn again, as ``CoreWorkload.nextKeynum`` does.  The
  hottest record so takes about 1/ZETAN = 3.8% of requests.
* ``uniform`` is ``UniformLongGenerator`` over ``[0, n - 1]``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

_KEY_START = 0x632BE5AB        # fixed: the load set never depends on a seed
WORDS = {32: np.uint32, 64: np.uint64}


def word(bits: int) -> type:
    """The unsigned type of a key or value of ``bits`` bits."""
    try:
        return WORDS[int(bits)]
    except KeyError:
        raise ValueError(f"keys and values are 32 or 64 bits wide; "
                         f"got {bits}") from None


def miss(bits: int):
    """The all-ones word of ``bits`` bits: the miss marker, and the
    empty-slot marker of a key."""
    return word(bits)(2 ** int(bits) - 1)


def fmix32(x: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer: a bijection on uint32."""
    x = np.asarray(x, np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
ITEM_COUNT = 10_000_000_000            # ScrambledZipfianGenerator.ITEM_COUNT
ZETAN = 26.46902820178302              # its zeta of ITEM_COUNT items at 0.99
USED_ZIPFIAN_CONSTANT = 0.99


def fnvhash64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 bytes of a long, low
    byte first, then ``Math.abs`` of the signed result."""
    x = np.asarray(x, np.uint64).copy()
    h = np.full(x.shape, FNV_OFFSET_BASIS_64, np.uint64)
    for _ in range(8):
        h ^= x & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        x >>= np.uint64(8)
    return np.where(h >> np.uint64(63), -h, h)     # two's-complement abs


def record_keys(n: int, key_bits: int = 32) -> np.ndarray:
    """The ``n`` record keys of ``key_bits`` bits, in record order:
    distinct, never 0 or all ones, the same in every run."""
    reserved = miss(key_bits)
    if key_bits == 32:
        keys = fmix32(np.arange(n + 2, dtype=np.uint32)
                      + np.uint32(_KEY_START))
        return keys[(keys != 0) & (keys != reserved)][:n]
    m = n
    while True:
        keys = fnvhash64(np.arange(m, dtype=np.uint64))
        first = np.zeros(m, bool)
        first[np.unique(keys, return_index=True)[1]] = True
        keys = keys[first & (keys != 0) & (keys != reserved)]
        if keys.size >= n:
            return keys[:n]
        m += n - keys.size


class ScrambledZipf:
    """YCSB's ``ScrambledZipfianGenerator`` over ``n`` records."""

    def __init__(self, n: int, theta: float):
        if theta != USED_ZIPFIAN_CONSTANT:
            raise ValueError(f"YCSB scrambles Zipf({USED_ZIPFIAN_CONSTANT})"
                             f" only with its fixed ZETAN; got {theta}")
        self.n, self.theta = n, theta
        self.items = ITEM_COUNT + 1            # ZipfianGenerator(0, ITEM_COUNT)
        self.zetan = ZETAN
        zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = ((1.0 - (2.0 / self.items) ** (1.0 - theta))
                    / (1.0 - zeta2 / self.zetan))

    def ranks(self, rng: np.random.Generator, size) -> np.ndarray:
        """``ZipfianGenerator.nextLong``: ranks in ``[0, ITEM_COUNT]``."""
        u = rng.random(size)
        uz = u * self.zetan
        r = (self.items * (self.eta * u - self.eta + 1.0) ** self.alpha
             ).astype(np.int64)
        r = np.where(uz < 1.0 + 0.5 ** self.theta, 1, r)
        return np.where(uz < 1.0, 0, r)

    def records(self, ranks: np.ndarray) -> np.ndarray:
        """The scramble: rank to record number, in ``[0, n]``."""
        return (fnvhash64(ranks) % np.uint64(self.n + 1)).astype(np.int64)

    def draw(self, rng: np.random.Generator, size) -> np.ndarray:
        out = self.records(self.ranks(rng, size))
        past = out >= self.n
        while np.any(past):                    # nextKeynum draws again
            out[past] = self.records(self.ranks(rng, int(past.sum())))
            past = out >= self.n
        return out


class Uniform:
    """YCSB's ``requestdistribution=uniform`` over ``n`` records."""

    def __init__(self, n: int):
        self.n = n

    def draw(self, rng: np.random.Generator, size) -> np.ndarray:
        return rng.integers(0, self.n, size=size, dtype=np.int64)


def distribution(traffic: dict, n: int):
    kind = traffic["distribution"]
    if kind == "zipfian":
        return ScrambledZipf(n, float(traffic["zipf_theta"]))
    if kind == "uniform":
        return Uniform(n)
    raise ValueError(f"unknown request distribution {kind!r}")


def load_values(seed: int, n: int, value_bits: int = 32) -> np.ndarray:
    """The values the load stores, one per record, drawn from the seed:
    below the miss marker of ``value_bits`` bits."""
    rng = np.random.default_rng([seed, 1])
    return rng.integers(0, miss(value_bits), size=n, dtype=word(value_bits))


def miss_probe(seed: int, keys: np.ndarray, size: int) -> np.ndarray:
    """``size`` 64-bit keys that are not among ``keys``, drawn from the
    seed: the first half each share their low 32-bit word with a stored
    key, the rest their high word, and none is a reserved word.  An index
    that drops either word of a key answers some of them with a stored
    key's value instead of the miss marker."""
    rng = np.random.default_rng([seed, 3])
    low = np.uint64(0xFFFFFFFF)
    half = size // 2
    stored = keys[rng.integers(0, keys.size, size)]
    kept = np.concatenate([stored[:half] & low, stored[half:] & ~low])
    probe = np.empty(size, np.uint64)
    todo = np.arange(size)
    while todo.size:
        fresh = rng.integers(0, 1 << 32, todo.size, dtype=np.uint64)
        probe[todo] = kept[todo] | np.where(todo < half,
                                            fresh << np.uint64(32), fresh)
        p = probe[todo]
        todo = todo[np.isin(p, keys) | (p == 0) | (p == miss(64))]
    return probe


@dataclass
class RequestPool:
    """``requests`` distinct requests, cycled by the window.

    Request ``seq`` is pool entry ``seq % requests``: first its updates
    (records ``updates[i]``), then its reads (records ``reads[i]``).  The
    value an update stores is :meth:`update_values` of ``seq``, fresh
    for every issue, so a cycled request never rewrites what it wrote
    before: consecutive values, wrapping below the miss marker."""
    reads: np.ndarray               # (P, R) record numbers
    updates: Optional[np.ndarray]   # (P, U) record numbers, or None
    value_base: int
    value_bits: int = 32

    @property
    def requests(self) -> int:
        return self.reads.shape[0]

    def entry(self, seq: int) -> int:
        return seq % self.requests

    def update_values(self, seq: int) -> np.ndarray:
        u = self.updates.shape[1]
        mod = int(miss(self.value_bits))
        start = (self.value_base + seq * u) % mod
        i = np.arange(u, dtype=np.uint64)
        room = np.uint64(mod - start)           # values before the wrap
        vals = np.where(i < room, np.uint64(start) + i, i - room)
        return vals.astype(word(self.value_bits))


def request_pool(seed: int, traffic: dict, n: int,
                 value_bits: int = 32) -> RequestPool:
    """Draw the pool of requests of a traffic mix from the seed."""
    rng = np.random.default_rng([seed, 2])
    dist = distribution(traffic, n)
    p = int(traffic["pool_requests"])
    reads = dist.draw(rng, (p, int(traffic["reads_per_request"])))
    u = int(traffic.get("updates_per_request", 0))
    updates = dist.draw(rng, (p, u)) if u else None
    base = int(rng.integers(0, miss(value_bits), dtype=np.uint64))
    return RequestPool(reads.astype(np.int32),
                       None if updates is None else updates.astype(np.int32),
                       base, value_bits)
