"""Shared hashing and probing primitives (single source of truth).

The paper uses one "lightweight multiplicative hash" for the directory
slot and a second one for the slot within a bucket (§4); the same pair —
Knuth's golden-ratio constants on uint32 — is used by every structure in
this repo for comparability (§4.2).  Before this module existed the
constants and the masked linear-probe logic were duplicated across the
XLA core (``core/extendible_hashing.py``), the Pallas kernels
(``kernels/eh_lookup.py``) and the baselines (``core/baselines.py``);
they now live here and *only* here.

Two flavours of each constant are exported:

  * plain Python ints (``HASH_C1`` …) — safe to close over inside Pallas
    kernels (a module-level traced constant would be captured by the
    kernel, which Pallas forbids); cast at use sites.
  * ``jnp.uint32`` values (``EMPTY_KEY``, ``MISS``) for the XLA paths.

Probing follows the paper's evaluation setup: open addressing / linear
probing with the *first-empty-slot-terminates* rule — a hit after an
empty slot is a ghost from a different probe chain and must be ignored.

Keys and values are 32 or 64 bits wide.  JAX runs 32-bit and the TPU's
vector unit has no 64-bit integers, so on the device a 64-bit word is
two uint32 words, high then low.  Every function here takes a key (or a
row of keys) either as one uint32 array, or as a tuple of its word
arrays ``(hi, lo)``: both words are compared wherever a key is
compared, and both are selected wherever a value is.  A bucket row of
``S`` slots of a 64-bit key is ``2 * S`` lanes: the high words in lanes
``[0, S)``, the low words in ``[S, 2 * S)`` (:func:`lane_words`).  An
empty slot and a miss are all ones in every word.  The directory hash
folds a 64-bit key into one word (:func:`fold`), the high word mixed
into the low, so keys that share either word still spread.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np

# -- the constants (defined here and nowhere else) ---------------------------

HASH_C1: int = 2654435761          # Knuth multiplicative (directory hash)
HASH_C2: int = 0x9E3779B1          # golden-ratio variant (bucket-slot hash)
HASH_C3: int = 0x85EBCA6B          # MurmurHash3 fmix32 (64-bit key fold)
EMPTY_SENTINEL: int = 0xFFFFFFFF   # slot unused (python int, kernel-safe)
MISS_SENTINEL: int = 0xFFFFFFFF    # lookup miss marker (python int)

EMPTY_KEY = jnp.uint32(EMPTY_SENTINEL)
MISS = jnp.uint32(MISS_SENTINEL)

WIDTHS = (32, 64)                  # key and value widths, in bits
_WORD = 0xFFFFFFFF


# -- words of a key -----------------------------------------------------------

def words(x, n: int):
    """The ``n`` uint32 words of keys or values stacked along axis 0
    (high first): ``x`` itself when ``n == 1`` or already a tuple of
    words, else the tuple of its word arrays."""
    if n == 1 or isinstance(x, tuple):
        return x
    return tuple(x[w] for w in range(n))


def lane_words(row: jnp.ndarray, n: int):
    """A row (or tile of rows) of ``n``-word slots split into its word
    blocks along the last axis: ``row`` itself when ``n == 1``, else the
    tuple of its ``n`` equal lane blocks (high words first)."""
    if n == 1:
        return row
    s = row.shape[-1] // n
    return tuple(row[..., w * s:(w + 1) * s] for w in range(n))


def num_words(key) -> int:
    return len(key) if isinstance(key, tuple) else 1


def per_word(f, x):
    """``f`` of a one-word array, or of each word of a tuple."""
    return tuple(f(w) for w in x) if isinstance(x, tuple) else f(x)


def take(row, idx):
    """``row[idx]`` of every word."""
    return per_word(lambda w: w[idx], row)


def equal(a, b):
    """Elementwise key equality, every word compared."""
    if not isinstance(a, tuple):
        return a == b.astype(jnp.uint32)
    return functools.reduce(operator.and_, map(equal, a, b))


def is_empty(a):
    """Elementwise: the slot holds no key (all ones in every word)."""
    if not isinstance(a, tuple):
        return a == jnp.uint32(EMPTY_SENTINEL)
    return functools.reduce(operator.and_, map(is_empty, a))


# -- hashes ------------------------------------------------------------------

def fold(key) -> jnp.ndarray:
    """One uint32 word per key: the key at 32 bits; at 64 bits its low
    word xor a mix of its high word (``x * C3``, then ``x ^ x >> 15``:
    a bijection, so keys that share a low word differ here, as do keys
    that share a high word).  Python-int constants only: it traces
    inside the kernels."""
    if not isinstance(key, tuple):
        return key.astype(jnp.uint32)
    hi, lo = key
    h = hi.astype(jnp.uint32) * jnp.uint32(HASH_C3)
    return lo.astype(jnp.uint32) ^ h ^ (h >> jnp.uint32(15))


def hash_dir(key) -> jnp.ndarray:
    """Primary multiplicative hash; directories use its most significant
    bits (the precondition for contiguous fan-in ranges, §4.1)."""
    return (fold(key) * jnp.uint32(HASH_C1)).astype(jnp.uint32)


def hash_bucket(key) -> jnp.ndarray:
    """Secondary hash for the slot within a bucket page."""
    k = fold(key) * jnp.uint32(HASH_C2)
    return (k ^ (k >> jnp.uint32(16))).astype(jnp.uint32)


def hash_dir_host(key: int, key_bits: int = 32) -> int:
    """Host-side twin of :func:`hash_dir` of one key, for invariant
    checks and host-built views."""
    return int(hash_dir_np(np.asarray([key], np.uint64), key_bits)[0])


def hash_dir_np(keys: np.ndarray, key_bits: int = 32) -> np.ndarray:
    """Vectorised host twin of :func:`hash_dir`: uint64 hashes of a
    numpy key array of ``key_bits`` bits."""
    k = np.asarray(keys, np.uint64)
    if key_bits == 64:
        h = ((k >> np.uint64(32)) * np.uint64(HASH_C3)) & np.uint64(_WORD)
        k = (k & np.uint64(_WORD)) ^ h ^ (h >> np.uint64(15))
    return (k * np.uint64(HASH_C1)) & np.uint64(_WORD)


# -- host side: arrays of the index's width, and their words ------------------

def word_type(bits: int) -> type:
    """The numpy type of a ``bits``-bit key or value."""
    if bits not in WIDTHS:
        raise ValueError(f"keys and values are 32 or 64 bits wide; "
                         f"got {bits}")
    return np.uint32 if bits == 32 else np.uint64


def all_ones(bits: int):
    """The empty-slot key and the miss value of ``bits`` bits, on the
    host."""
    return word_type(bits)(2 ** bits - 1)


def check_width(a, bits: int, what: str = "key"):
    """``a`` unchanged, if it holds ``bits``-bit keys or values without
    wrapping; else ``ValueError``, naming the width.  An array whose
    dtype is wider than ``bits`` is refused, as is a signed one holding
    a negative number; a list or scalar (numpy's default int64) is
    checked by value."""
    dtype = getattr(a, "dtype", None)
    if dtype is None:
        a = np.asarray(a)
        if a.size and a.dtype.kind in "iu" and (
                int(a.min()) < 0 or int(a.max()) >> bits):
            raise ValueError(f"the index holds {bits}-bit {what}s; a {what} "
                             f"out of that range would be wrapped")
        return a.astype(word_type(bits))
    dtype = np.dtype(dtype)
    if dtype.kind not in "iu" or dtype.itemsize * 8 > bits:
        raise ValueError(f"the index holds {bits}-bit {what}s; {dtype} "
                         f"{what}s would be wrapped")
    if dtype.kind == "i" and np.size(a) and int(np.min(a)) < 0:
        raise ValueError(f"the index holds {bits}-bit {what}s; a negative "
                         f"{what} would be wrapped")
    return a


def split_words(a: np.ndarray) -> np.ndarray:
    """uint64 array ``(..., n)`` -> its uint32 words ``(..., 2, n)``,
    high words at ``[..., 0, :]`` and low words at ``[..., 1, :]``: one
    copy, through a view of the array's bytes."""
    a = np.ascontiguousarray(a, np.uint64)
    w = a.view(np.uint32).reshape(a.shape + (2,))
    if np.little_endian:                 # the low word comes first in memory
        w = w[..., ::-1]
    return np.ascontiguousarray(np.moveaxis(w, -1, -2))


def join_words(w: np.ndarray) -> np.ndarray:
    """:func:`split_words`' inverse: ``(..., 2, n)`` uint32 -> uint64."""
    w = np.asarray(w)
    return (w[..., 0, :].astype(np.uint64) << np.uint64(32)) \
        | w[..., 1, :].astype(np.uint64)


def dir_slot(h: jnp.ndarray, depth: jnp.ndarray) -> jnp.ndarray:
    """Most-significant-bit slot of hash ``h`` in a table of ``2**depth``
    entries; depth 0 => single slot 0.  (uint32 >> 32 is undefined, so
    depth 0 is guarded.)"""
    d = depth.astype(jnp.uint32) if hasattr(depth, "astype") \
        else jnp.uint32(depth)
    return jnp.where(d == jnp.uint32(0), jnp.uint32(0),
                     h >> (jnp.uint32(32) - d)).astype(jnp.int32)


# -- probe-sequence generators ----------------------------------------------

def probe_positions(key: jnp.ndarray, slots: int) -> jnp.ndarray:
    """Full cyclic probe sequence over a bucket row of ``slots`` entries,
    starting at the secondary hash."""
    start = hash_bucket(key) % jnp.uint32(slots)
    return ((start + jnp.arange(slots, dtype=jnp.uint32))
            % jnp.uint32(slots)).astype(jnp.int32)


def window_positions(h: jnp.ndarray, size_log2: jnp.ndarray,
                     window: int) -> jnp.ndarray:
    """Linear probe window of ``window`` slots from the home slot of
    hash ``h`` in an active table prefix of ``2**size_log2`` entries."""
    size = jnp.int32(1) << size_log2
    home = dir_slot(h, size_log2)
    return (home + jnp.arange(window, dtype=jnp.int32)) % size


# -- masked probes (the duplicated core, now shared) -------------------------

def probe_hit(probed: jnp.ndarray, key: jnp.ndarray):
    """Find ``key`` in the probed key sequence.

    Returns ``(found, idx)`` where ``idx`` indexes *into the probe
    sequence*; a hit after the first EMPTY slot is ignored (linear
    probing terminates at the first empty slot)."""
    hit = equal(probed, key)
    # sentinel built at use site: these helpers trace inside Pallas
    # kernels, where closing over a module-level concrete array is an
    # illegal captured constant
    empties = is_empty(probed)
    before = jnp.cumsum(empties.astype(jnp.int32)) - empties.astype(jnp.int32)
    live = hit & (before == 0)
    return jnp.any(live), jnp.argmax(live)


def _row_ranks(row_keys: jnp.ndarray, keys):
    """The rank mask shared by :func:`probe_rows` and
    :func:`probe_rows_slot`: returns ``(start, rank, hit)``, where
    ``start`` (T, 1) is each key's first probe lane, ``rank`` (T, S) each
    lane's rank in that key's cyclic probe sequence, and ``hit`` (T, 1)
    the rank of the live match, ``S`` where there is none."""
    row_keys = lane_words(row_keys, num_words(keys))
    first = row_keys[0] if isinstance(row_keys, tuple) else row_keys
    slots = first.shape[-1]
    start = (hash_bucket(keys) % jnp.uint32(slots)).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, first.shape, first.ndim - 1)
    rank = (lane - start + slots) % slots
    empty = is_empty(row_keys)
    first_empty = jnp.min(jnp.where(empty, rank, slots), axis=-1,
                          keepdims=True)
    live = equal(row_keys, keys) & (rank < first_empty)
    hit = jnp.min(jnp.where(live, rank, slots), axis=-1, keepdims=True)
    return start, rank, hit


def probe_rows(row_keys: jnp.ndarray, row_vals: jnp.ndarray, keys):
    """:func:`probe_hit` for a whole tile of keys at once, as a rank mask
    over each key's bucket row instead of a gather along its probe
    sequence (the form the TPU's vector unit lowers).

    ``row_keys``/``row_vals`` are (T, S) at one word a slot, (T, 2 * S)
    at two (:func:`lane_words`): row t is key t's bucket row; ``keys``
    is (T, 1), or a tuple of its (T, 1) words.  Lane j of row t sits at
    rank ``(j - start_t) mod S`` of key t's cyclic probe sequence, so "a
    hit after the first EMPTY is ignored" becomes "a hit counts only
    when its rank is below the rank of the row's first EMPTY".  Returns
    ``(found, value)``, both (T, 1), ``value`` a tuple of its words at
    two words a value; ``value`` is 0 where not found."""
    _, rank, hit = _row_ranks(row_keys, keys)
    slots = rank.shape[-1]
    # ranks are a permutation of the lanes, so exactly one lane has the
    # hit's rank; int32 because the vector unit reduces signed lanes
    value = per_word(
        lambda vals: jnp.sum(jnp.where(rank == hit, vals, 0)
                             .astype(jnp.int32), axis=-1, keepdims=True),
        lane_words(row_vals, row_vals.shape[-1] // slots))
    found = hit < slots
    return found, per_word(lambda v: v.astype(jnp.uint32), value)


def probe_rows_slot(row_keys: jnp.ndarray, keys):
    """:func:`probe_rows`, answering with the lane of the hit instead of
    its value: the slot :func:`probe_slot` would overwrite.  Returns
    ``(found, lane)``, both (T, 1); ``lane`` is meaningless where not
    found."""
    start, rank, hit = _row_ranks(row_keys, keys)
    slots = rank.shape[-1]
    return hit < slots, (start + hit) % slots


def probe_slot(probed: jnp.ndarray, key: jnp.ndarray):
    """Find the insert slot for ``key``: the first position that either
    already holds ``key`` (overwrite) or is EMPTY.

    Returns ``(ok, idx)`` with ``idx`` into the probe sequence; ``ok`` is
    False when the probed window is full and the key absent."""
    usable = equal(probed, key) | is_empty(probed)
    return jnp.any(usable), jnp.argmax(usable)
