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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# -- the constants (defined here and nowhere else) ---------------------------

HASH_C1: int = 2654435761          # Knuth multiplicative (directory hash)
HASH_C2: int = 0x9E3779B1          # golden-ratio variant (bucket-slot hash)
EMPTY_SENTINEL: int = 0xFFFFFFFF   # slot unused (python int, kernel-safe)
MISS_SENTINEL: int = 0xFFFFFFFF    # lookup miss marker (python int)

EMPTY_KEY = jnp.uint32(EMPTY_SENTINEL)
MISS = jnp.uint32(MISS_SENTINEL)


# -- hashes ------------------------------------------------------------------

def hash_dir(key: jnp.ndarray) -> jnp.ndarray:
    """Primary multiplicative hash; directories use its most significant
    bits (the precondition for contiguous fan-in ranges, §4.1)."""
    return (key.astype(jnp.uint32) * jnp.uint32(HASH_C1)).astype(jnp.uint32)


def hash_bucket(key: jnp.ndarray) -> jnp.ndarray:
    """Secondary hash for the slot within a bucket page."""
    k = key.astype(jnp.uint32) * jnp.uint32(HASH_C2)
    return (k ^ (k >> jnp.uint32(16))).astype(jnp.uint32)


def hash_dir_host(key: int) -> int:
    """Host-side (numpy-free) twin of :func:`hash_dir` for invariant
    checks and host-built views."""
    return (int(key) * HASH_C1) & 0xFFFFFFFF


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
    hit = probed == key.astype(jnp.uint32)
    # sentinel built at use site: these helpers trace inside Pallas
    # kernels, where closing over a module-level concrete array is an
    # illegal captured constant
    empties = probed == jnp.uint32(EMPTY_SENTINEL)
    before = jnp.cumsum(empties.astype(jnp.int32)) - empties.astype(jnp.int32)
    live = hit & (before == 0)
    return jnp.any(live), jnp.argmax(live)


def _row_ranks(row_keys: jnp.ndarray, keys: jnp.ndarray):
    """The rank mask shared by :func:`probe_rows` and
    :func:`probe_rows_slot`: returns ``(start, rank, hit)``, where
    ``start`` (T, 1) is each key's first probe lane, ``rank`` (T, S) each
    lane's rank in that key's cyclic probe sequence, and ``hit`` (T, 1)
    the rank of the live match, ``S`` where there is none."""
    slots = row_keys.shape[-1]
    start = (hash_bucket(keys) % jnp.uint32(slots)).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, row_keys.shape,
                                    row_keys.ndim - 1)
    rank = (lane - start + slots) % slots
    empty = row_keys == jnp.uint32(EMPTY_SENTINEL)
    first_empty = jnp.min(jnp.where(empty, rank, slots), axis=-1,
                          keepdims=True)
    live = (row_keys == keys.astype(jnp.uint32)) & (rank < first_empty)
    hit = jnp.min(jnp.where(live, rank, slots), axis=-1, keepdims=True)
    return start, rank, hit


def probe_rows(row_keys: jnp.ndarray, row_vals: jnp.ndarray,
               keys: jnp.ndarray):
    """:func:`probe_hit` for a whole tile of keys at once, as a rank mask
    over each key's bucket row instead of a gather along its probe
    sequence (the form the TPU's vector unit lowers).

    ``row_keys``/``row_vals`` are (T, S): row t is key t's bucket row;
    ``keys`` is (T, 1).  Lane j of row t sits at rank
    ``(j - start_t) mod S`` of key t's cyclic probe sequence, so "a hit
    after the first EMPTY is ignored" becomes "a hit counts only when
    its rank is below the rank of the row's first EMPTY".  Returns
    ``(found, value)``, both (T, 1); ``value`` is 0 where not found."""
    slots = row_keys.shape[-1]
    _, rank, hit = _row_ranks(row_keys, keys)
    # ranks are a permutation of the lanes, so exactly one lane has the
    # hit's rank; int32 because the vector unit reduces signed lanes
    value = jnp.sum(jnp.where(rank == hit, row_vals, 0).astype(jnp.int32),
                    axis=-1, keepdims=True)
    return hit < slots, value.astype(jnp.uint32)


def probe_rows_slot(row_keys: jnp.ndarray, keys: jnp.ndarray):
    """:func:`probe_rows`, answering with the lane of the hit instead of
    its value: the slot :func:`probe_slot` would overwrite.  Returns
    ``(found, lane)``, both (T, 1); ``lane`` is meaningless where not
    found."""
    slots = row_keys.shape[-1]
    start, _, hit = _row_ranks(row_keys, keys)
    return hit < slots, (start + hit) % slots


def probe_slot(probed: jnp.ndarray, key: jnp.ndarray):
    """Find the insert slot for ``key``: the first position that either
    already holds ``key`` (overwrite) or is EMPTY.

    Returns ``(ok, idx)`` with ``idx`` into the probe sequence; ``ok`` is
    False when the probed window is full and the key absent."""
    usable = (probed == key.astype(jnp.uint32)) \
        | (probed == jnp.uint32(EMPTY_SENTINEL))
    return jnp.any(usable), jnp.argmax(usable)
