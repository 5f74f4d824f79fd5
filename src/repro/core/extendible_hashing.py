"""Extendible hashing (Fagin et al. [3]) as a pure-functional, jittable JAX
data structure — the paper's showcase index (§4).

Layout (all arrays statically sized, validity tracked by scalars):

  * ``directory``    -- (max_dir,) int32; the first ``2**global_depth`` slots
                        are valid and hold bucket ids.  Indexed by the
                        *most significant* ``global_depth`` bits of the hash
                        (as in the paper), so all slots referencing one bucket
                        form a contiguous range — the precondition for
                        coalesced remapping (``rewiring.remap_range``).
  * ``bucket_keys``/``bucket_vals`` -- (capacity, words * bucket_slots)
                        uint32; a bucket is a 4 KB page analogue.  Open
                        addressing / linear probing *within* a bucket, as in
                        the paper's evaluation.  A 64-bit key or value is two
                        words, high words in lanes ``[0, S)`` and low words
                        in ``[S, 2S)`` (``hashing.lane_words``); the widths
                        (``key_words``, ``value_words``) are static.
  * ``local_depth``  -- (capacity,) int32 per-bucket depth.
  * ``counts``       -- (capacity,) int32 live entries per bucket.
  * ``num_buckets``  -- () int32 bump-allocator high-water mark (EH never
                        frees buckets; the KV-cache layer exercises the pool's
                        free ring instead).

Hashing: the paper uses one "lightweight multiplicative hash" for the
directory slot and a second one for the bucket slot; the constants and
probe primitives are shared with the kernels and baselines via
``core/hashing.py`` (``hash_dir``/``hash_bucket``/``dir_slot`` are
re-exported here for backwards compatibility).

All mutating ops return a new state (functional); batched insertion
overwrites present keys in one vectorised pass and loops over the absent
ones, batched lookup is a ``vmap``.  Batches of 64-bit keys or values
come as ``(2, n)`` uint32 word planes (high words first), single ones as
``(2,)``; batched lookups answer ``(2, n)`` at 64-bit values.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.hashing import (EMPTY_KEY, MISS,  # noqa: F401  (re-export)
                                dir_slot, hash_bucket, hash_dir)


class EHState(NamedTuple):
    directory: jax.Array     # (max_dir,) int32 bucket ids
    bucket_keys: jax.Array   # (capacity, key_words * bucket_slots) uint32
    bucket_vals: jax.Array   # (capacity, value_words * bucket_slots) uint32
    counts: jax.Array        # (capacity,) int32
    local_depth: jax.Array   # (capacity,) int32
    global_depth: jax.Array  # () int32
    num_buckets: jax.Array   # () int32
    dropped: jax.Array       # () int32  inserts refused (capacity exhausted)
    key_words: int = 1       # static: uint32 words of a key (1 or 2)
    value_words: int = 1     # static: uint32 words of a value (1 or 2)

    @property
    def max_global_depth(self) -> int:
        return int(self.directory.shape[0]).bit_length() - 1

    @property
    def capacity(self) -> int:
        return self.bucket_keys.shape[0]

    @property
    def bucket_slots(self) -> int:
        return self.bucket_keys.shape[1] // self.key_words


_ARRAYS = EHState._fields[:-2]
# the word counts are the pytree's static data, not leaves: a jit
# specializes on them, and at one word each the leaves, their names
# (and so every program) are what they were before wide keys
jax.tree_util.register_pytree_with_keys(
    EHState,
    lambda st: (tuple((jax.tree_util.GetAttrKey(f), getattr(st, f))
                      for f in _ARRAYS), (st.key_words, st.value_words)),
    lambda widths, leaves: EHState(*leaves, *widths),
    flatten_func=lambda st: (tuple(st[:len(_ARRAYS)]),
                             (st.key_words, st.value_words)))


def eh_create(max_global_depth: int, bucket_slots: int, capacity: int,
              key_bits: int = 32, value_bits: int = 32) -> EHState:
    """One empty bucket, one directory slot (the paper's 4 KB start state).
    ``key_bits``/``value_bits`` (32 or 64) fix the words of a slot."""
    assert capacity >= 1
    for bits in (key_bits, value_bits):
        hashing.word_type(bits)            # 32 or 64, else ValueError
    kw, vw = key_bits // 32, value_bits // 32
    return EHState(
        directory=jnp.zeros((1 << max_global_depth,), jnp.int32),
        bucket_keys=jnp.full((capacity, kw * bucket_slots), EMPTY_KEY,
                             jnp.uint32),
        bucket_vals=jnp.zeros((capacity, vw * bucket_slots), jnp.uint32),
        counts=jnp.zeros((capacity,), jnp.int32),
        local_depth=jnp.zeros((capacity,), jnp.int32),
        global_depth=jnp.zeros((), jnp.int32),
        num_buckets=jnp.ones((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
        key_words=kw, value_words=vw,
    )


# ---------------------------------------------------------------------------
# Intra-bucket open addressing (vectorized probe, no loops).
# ---------------------------------------------------------------------------

# A key or value in these helpers is one uint32 scalar, or the tuple of
# its words (``hashing.words``); a row holds each word in its own lane
# block (``hashing.lane_words``).

def _is_empty(key):
    """``hashing.is_empty``; a one-word key is compared with the module's
    ``EMPTY_KEY``, as this XLA-only path always has (the kernels build
    the sentinel in place instead)."""
    return key == EMPTY_KEY if not isinstance(key, tuple) \
        else hashing.is_empty(key)


def bucket_find(keys_row: jax.Array, key) -> jax.Array:
    """Probe a bucket row; return slot index of ``key`` or -1."""
    kw = hashing.num_words(key)
    pos = hashing.probe_positions(key, keys_row.shape[0] // kw)
    found, j = hashing.probe_hit(
        hashing.take(hashing.lane_words(keys_row, kw), pos), key)
    return jnp.where(found, pos[j], -1)


def _put(row: jax.Array, idx, word, ok, slots: int) -> jax.Array:
    """Write ``word`` (each of its words) at slot ``idx`` where ``ok``."""
    if not isinstance(word, tuple):
        return row.at[idx].set(jnp.where(ok, word.astype(jnp.uint32),
                                         row[idx]))
    for w, x in enumerate(word):
        i = idx + w * slots
        row = row.at[i].set(jnp.where(ok, x.astype(jnp.uint32), row[i]))
    return row


def bucket_put(keys_row: jax.Array, vals_row: jax.Array, key, value):
    """Insert/overwrite (key,value) in a bucket row.

    Returns (keys_row, vals_row, inserted_new, ok):
      inserted_new -- 1 if a fresh slot was consumed (count must grow)
      ok           -- 0 if the bucket was full and key absent
    """
    kw = hashing.num_words(key)
    slots = keys_row.shape[0] // kw
    row = hashing.lane_words(keys_row, kw)
    pos = hashing.probe_positions(key, slots)
    ok, j = hashing.probe_slot(hashing.take(row, pos), key)
    idx = pos[j]
    was_empty = _is_empty(hashing.take(row, idx))
    keys_row = _put(keys_row, idx, key, ok, slots)
    vals_row = _put(vals_row, idx, value, ok, slots)
    inserted_new = (ok & was_empty).astype(jnp.int32)
    return keys_row, vals_row, inserted_new, ok


# ---------------------------------------------------------------------------
# Directory maintenance: doubling and bucket split.
# ---------------------------------------------------------------------------

def _double_directory(st: EHState) -> EHState:
    """MSB indexing: each valid slot i fans out to slots 2i, 2i+1."""
    max_dir = st.directory.shape[0]
    idx = jnp.arange(max_dir, dtype=jnp.int32)
    grown = st.directory[idx >> 1]
    valid = idx < (1 << (st.global_depth + 1))
    return st._replace(
        directory=jnp.where(valid, grown, st.directory),
        global_depth=st.global_depth + 1,
    )


def _split_bucket(st: EHState, h: jax.Array) -> EHState:
    """Split the bucket addressed by hash ``h`` (paper Fig. 6 step)."""
    st = jax.lax.cond(
        st.local_depth[st.directory[dir_slot(h, st.global_depth)]]
        == st.global_depth,
        _double_directory, lambda s: s, st)

    g = st.global_depth
    slot = dir_slot(h, g)
    b = st.directory[slot]
    l = st.local_depth[b]
    b2 = st.num_buckets  # bump allocation

    # Redistribute entries of b between b and b2 on hash bit (l+1) from the top.
    old_keys = hashing.lane_words(st.bucket_keys[b], st.key_words)
    old_vals = hashing.lane_words(st.bucket_vals[b], st.value_words)
    slots = st.bucket_slots
    empty_row = jnp.full((st.bucket_keys.shape[1],), EMPTY_KEY, jnp.uint32)
    zero_row = jnp.zeros((st.bucket_vals.shape[1],), jnp.uint32)

    def redistribute(i, carry):
        k0, v0, c0, k1, v1, c1 = carry
        key = hashing.take(old_keys, i)
        val = hashing.take(old_vals, i)
        live = key != EMPTY_KEY if st.key_words == 1 \
            else ~hashing.is_empty(key)
        bit = (hash_dir(key) >> (jnp.uint32(31) - l.astype(jnp.uint32))) \
            & jnp.uint32(1)
        to_new = live & (bit == 1)
        to_old = live & (bit == 0)
        nk0, nv0, inew0, _ = bucket_put(k0, v0, key, val)
        nk1, nv1, inew1, _ = bucket_put(k1, v1, key, val)
        k0 = jnp.where(to_old, nk0, k0)
        v0 = jnp.where(to_old, nv0, v0)
        c0 = c0 + jnp.where(to_old, inew0, 0)
        k1 = jnp.where(to_new, nk1, k1)
        v1 = jnp.where(to_new, nv1, v1)
        c1 = c1 + jnp.where(to_new, inew1, 0)
        return k0, v0, c0, k1, v1, c1

    k0, v0, c0, k1, v1, c1 = jax.lax.fori_loop(
        0, slots, redistribute,
        (empty_row, zero_row, jnp.int32(0), empty_row, zero_row, jnp.int32(0)))

    # Directory range [start, start+2^(g-l)) pointed at b; upper half -> b2.
    shift = (g - l).astype(jnp.uint32)
    start = (slot >> shift) << shift
    length = jnp.int32(1) << (g - l)
    half = length >> 1
    idx = jnp.arange(st.directory.shape[0], dtype=jnp.int32)
    in_upper = (idx >= start + half) & (idx < start + length)
    return st._replace(
        directory=jnp.where(in_upper, b2, st.directory),
        bucket_keys=st.bucket_keys.at[b].set(k0).at[b2].set(k1),
        bucket_vals=st.bucket_vals.at[b].set(v0).at[b2].set(v1),
        counts=st.counts.at[b].set(c0).at[b2].set(c1),
        local_depth=st.local_depth.at[b].set(l + 1).at[b2].set(l + 1),
        num_buckets=st.num_buckets + 1,
    )


# ---------------------------------------------------------------------------
# Public ops.
# ---------------------------------------------------------------------------

def eh_insert(st: EHState, key: jax.Array, value: jax.Array) -> EHState:
    """Insert (key, value); splits (possibly cascading) handled in-line.
    A 64-bit key or value is its ``(2,)`` words, or their tuple."""
    key = hashing.words(key, st.key_words)
    value = hashing.words(value, st.value_words)
    h = hash_dir(key)

    def needs_split(s: EHState):
        b = s.directory[dir_slot(h, s.global_depth)]
        full = s.counts[b] >= s.bucket_slots
        present = bucket_find(s.bucket_keys[b], key) >= 0
        can_grow = (s.num_buckets < s.capacity) & \
            ((s.local_depth[b] < s.global_depth) |
             (s.global_depth < s.max_global_depth))
        return full & ~present & can_grow

    st = jax.lax.while_loop(needs_split, lambda s: _split_bucket(s, h), st)

    b = st.directory[dir_slot(h, st.global_depth)]
    nk, nv, inserted_new, ok = bucket_put(
        st.bucket_keys[b], st.bucket_vals[b], key, value)
    return st._replace(
        bucket_keys=st.bucket_keys.at[b].set(
            jnp.where(ok, nk, st.bucket_keys[b])),
        bucket_vals=st.bucket_vals.at[b].set(
            jnp.where(ok, nv, st.bucket_vals[b])),
        counts=st.counts.at[b].add(inserted_new),
        dropped=st.dropped + (1 - ok.astype(jnp.int32)),
    )


# Keys classified per program: the (tile, bucket_slots) rows gathered for
# one tile stay near 8 MiB at 512 slots, whatever the batch length.
CLASSIFY_TILE = 4096


def _classify(st: EHState, keys: jax.Array):
    """Bucket, slot and presence of each key in ``st``, in tiles of at
    most ``CLASSIFY_TILE`` keys.  A key counts as present only where
    ``bucket_find`` would find it (before the first EMPTY of its probe
    sequence); its slot is then the one ``bucket_put`` would overwrite.
    Also returns each key's ``ahead``: the number of absent keys before
    it in the batch (a tile at a time, since the TPU compiler takes
    seconds over a prefix sum at a bulk load's length)."""
    n = keys.shape[-1]
    tile = min(n, CLASSIFY_TILE)
    tiles = -(-n // tile)
    padded = hashing.words(keys, st.key_words)
    padded = hashing.per_word(
        lambda w: jnp.pad(w, (0, tiles * tile - n)).reshape(tiles, tile),
        padded)

    def one(seen, tk):
        b = st.directory[dir_slot(hash_dir(tk), st.global_depth)]
        found, slot = hashing.probe_rows_slot(
            st.bucket_keys[b], hashing.per_word(lambda w: w[:, None], tk))
        absent = 1 - found[:, 0].astype(jnp.int32)
        ahead = seen + jnp.cumsum(absent) - absent
        return seen + jnp.sum(absent), (b, slot[:, 0], found[:, 0], ahead)

    _, parts = jax.lax.scan(one, jnp.int32(0), padded)
    return tuple(x.reshape(-1)[:n] for x in parts)


@jax.jit
def eh_insert_many(st: EHState, keys: jax.Array, values: jax.Array):
    """Batch upsert, equal to ``eh_insert`` of each pair in batch order.

    Keys present in ``st`` can never split their bucket, so they are
    overwritten in place in one vectorised pass, the last occurrence of
    each winning.  Keys absent from ``st`` then run through ``eh_insert``
    one at a time, in batch order (splits serialize them by nature); a
    split moves the overwritten values along with their rows.  Returns
    ``(state, fresh, touched)``: ``fresh`` is the number of absent keys,
    the sequential loop's trip count; ``touched`` is the ``(capacity,)``
    bool mask of the buckets the batch's keys map to in the returned
    state, not in ``st``: a key a split moved counts in its new
    bucket."""
    keys = keys.astype(jnp.uint32)
    values = values.astype(jnp.uint32)
    n = keys.shape[-1]
    if n == 0:
        return st, jnp.zeros((), jnp.int32), jnp.zeros((st.capacity,), bool)
    b, slot, present, ahead = _classify(st, keys)
    idx = jnp.arange(n, dtype=jnp.int32)

    # the last occurrence of each present key is the largest batch index
    # aimed at its slot: a max does not depend on the order in which
    # duplicate scatter indices apply, which XLA leaves open (a sort by
    # key would do too, but takes the TPU compiler half a minute at a
    # bulk load's length)
    last = jnp.full((st.capacity, st.bucket_slots), -1, jnp.int32).at[
        jnp.where(present, b, st.capacity), slot].max(idx, mode="drop")
    won = present & (last[b, slot] == idx)
    rows = jnp.where(won, b, st.capacity)
    vals = st.bucket_vals
    planes = [values] if st.value_words == 1 else list(values)
    for w, plane in enumerate(planes):      # word w lives at lane w*S + slot
        lane = slot if w == 0 else slot + w * st.bucket_slots
        vals = vals.at[rows, lane].set(plane, mode="drop")
    st = st._replace(bucket_vals=vals)

    # absent keys, compacted to the front in batch order, a word plane
    # at a time (a (n, 2) array would pad its 2 lanes to 128 on a TPU)
    fresh = n - jnp.sum(present, dtype=jnp.int32)
    dest = jnp.where(present, n, ahead)

    def compact(x):
        return jnp.zeros_like(x).at[dest].set(x, mode="drop")

    fresh_keys = hashing.per_word(compact,
                                  hashing.words(keys, st.key_words))
    fresh_vals = hashing.per_word(compact,
                                  hashing.words(values, st.value_words))

    def body(carry):
        i, s = carry
        return i + 1, eh_insert(s, hashing.take(fresh_keys, i),
                                hashing.take(fresh_vals, i))

    _, st = jax.lax.while_loop(lambda c: c[0] < fresh, body,
                               (jnp.int32(0), st))
    slots = dir_slot(hash_dir(hashing.words(keys, st.key_words)),
                     st.global_depth)
    touched = jnp.zeros((st.capacity,), bool).at[st.directory[slots]].set(
        True)
    return st, fresh, touched


def _answer(found, vals_row: jax.Array, idx, value_words: int):
    """The value at slot ``idx`` of a row (each of its words, stacked),
    or MISS in every word where not ``found``."""
    if value_words == 1:
        return jnp.where(found, vals_row[idx], MISS)
    return jnp.stack([jnp.where(found, w[idx], MISS)
                      for w in hashing.lane_words(vals_row, value_words)])


def _lookup_many(one, keys: jax.Array, value_words: int) -> jax.Array:
    """``one`` over a batch: keys ``(n,)`` or ``(words, n)``; answers
    ``(n,)``, or ``(2, n)`` at 64-bit values."""
    keys = keys.astype(jnp.uint32)
    return jax.vmap(one, in_axes=keys.ndim - 1,
                    out_axes=0 if value_words == 1 else 1)(keys)


def eh_lookup(st: EHState, key: jax.Array) -> jax.Array:
    """Traditional path: directory gather -> bucket gather -> probe."""
    key = hashing.words(key, st.key_words)
    b = st.directory[dir_slot(hash_dir(key), st.global_depth)]
    idx = bucket_find(st.bucket_keys[b], key)
    found = idx >= 0
    return _answer(found, st.bucket_vals[b], idx, st.value_words)


@jax.jit
def eh_lookup_many(st: EHState, keys: jax.Array) -> jax.Array:
    return _lookup_many(lambda k: eh_lookup(st, k), keys, st.value_words)


# ---------------------------------------------------------------------------
# Shortcut path: lookups against a pre-composed view (rewiring.compose of the
# bucket pages by the directory).  One indirection instead of two.
# ---------------------------------------------------------------------------

def shortcut_lookup(view_keys: jax.Array, view_vals: jax.Array,
                    global_depth: jax.Array, key: jax.Array) -> jax.Array:
    """Lookup through the composed view: slot arithmetic + one gather.
    A 64-bit key is its ``(2,)`` words; the value's words follow from
    the rows' widths."""
    kw = 1 if key.ndim == 0 else key.shape[0]
    vw = view_vals.shape[-1] * kw // view_keys.shape[-1]
    key = hashing.words(key, kw)
    slot = dir_slot(hash_dir(key), global_depth)
    idx = bucket_find(view_keys[slot], key)
    found = idx >= 0
    return _answer(found, view_vals[slot], idx, vw)


@jax.jit
def shortcut_lookup_many(view_keys: jax.Array, view_vals: jax.Array,
                         global_depth: jax.Array,
                         keys: jax.Array) -> jax.Array:
    kw = 1 if keys.ndim == 1 else keys.shape[0]
    return _lookup_many(
        lambda k: shortcut_lookup(view_keys, view_vals, global_depth, k),
        keys, view_vals.shape[-1] * kw // view_keys.shape[-1])


@functools.partial(jax.jit, static_argnames=("view_slots",))
def compose_shortcut(st: EHState, view_slots: int):
    """Create-request replay: materialize (view_keys, view_vals) for the first
    ``view_slots`` directory slots (a static power of two >= 2**global_depth).

    This is the expensive one-shot 'mmap loop' of the paper's step (2); the
    ShortcutEH wrapper runs it asynchronously.
    """
    idx = jnp.arange(view_slots, dtype=jnp.int32)
    valid = idx < (1 << st.global_depth)
    src = jnp.where(valid, st.directory[:view_slots], 0)
    return st.bucket_keys[src], st.bucket_vals[src]


# ---------------------------------------------------------------------------
# Introspection used by routing and tests.
# ---------------------------------------------------------------------------

def avg_fan_in(st: EHState) -> jax.Array:
    """Average number of directory slots per bucket = 2^g / #buckets."""
    return (jnp.int32(1) << st.global_depth).astype(jnp.float32) \
        / st.num_buckets.astype(jnp.float32)


def eh_num_entries(st: EHState) -> jax.Array:
    return jnp.sum(st.counts)


def host_keys(st: EHState):
    """The allocated buckets' keys on the host, one per slot:
    ``(num_buckets, S)`` uint32, or uint64 joined from a 64-bit key's
    words; an empty slot holds all ones."""
    import numpy as np
    keys = np.asarray(st.bucket_keys[:int(st.num_buckets)])
    if st.key_words == 1:
        return keys
    s = st.bucket_slots
    return hashing.join_words(np.stack([keys[:, :s], keys[:, s:]], 1))


def check_invariants(st: EHState) -> dict:
    """Host-side invariant checks (used by property tests).

    I1: every valid directory slot points to an allocated bucket.
    I2: bucket b with local depth l is referenced by exactly 2^(g-l)
        *contiguous* slots whose top-l hash bits are constant.
    I3: local_depth <= global_depth for all allocated buckets.
    I4: every live key is stored in the bucket its hash addresses.
    I5: counts match the number of non-empty slots.
    """
    import numpy as np
    g = int(st.global_depth)
    nd = 1 << g
    directory = np.asarray(st.directory[:nd])
    nb = int(st.num_buckets)
    out = {"ok": True, "errors": []}

    def fail(msg):
        out["ok"] = False
        out["errors"].append(msg)

    if not ((directory >= 0) & (directory < nb)).all():
        fail("I1: dangling directory slot")
    ld = np.asarray(st.local_depth[:nb])
    if (ld > g).any():
        fail("I3: local depth exceeds global depth")
    ref_counts = {}
    for slot, b in enumerate(directory):
        ref_counts.setdefault(int(b), []).append(slot)
    for b, slots in ref_counts.items():
        expect = 1 << (g - int(ld[b]))
        if len(slots) != expect:
            fail(f"I2: bucket {b} referenced {len(slots)}x, expect {expect}")
        if slots != list(range(slots[0], slots[0] + len(slots))):
            fail(f"I2: bucket {b} slots not contiguous")
    keys = host_keys(st)
    counts = np.asarray(st.counts[:nb])
    live = keys != hashing.all_ones(32 * st.key_words)
    if not (live.sum(axis=1) == counts).all():
        fail("I5: counts mismatch")
    for b in range(nb):
        for k in keys[b][live[b]]:
            h = hashing.hash_dir_host(int(k), 32 * st.key_words)
            slot = h >> (32 - g) if g > 0 else 0
            if int(directory[slot]) != b:
                fail(f"I4: key {k} misplaced (bucket {b}, slot {slot})")
    return out
