"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Deliberately naive implementations: full score matrices, explicit gathers —
independent of both the kernels and the model's blockwise code paths.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None) -> jax.Array:
    """q: (B, KV, G, Sq, hd); k/v: (B, KV, Skv, hd) -> (B, KV, G, Sq, hd)."""
    B, KV, G, Sq, hd = q.shape
    Skv = k.shape[2]
    s = jnp.einsum("bkgqd,bksd->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    if softcap is not None:
        s = softcap_ref(s, softcap)
    qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)  # right-aligned
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def softcap_ref(x, cap):
    return cap * jnp.tanh(x / cap)


def decode_attention_ref(q, k_ctx, v_ctx, ctx_len, *,
                         window: Optional[int] = None,
                         softcap: Optional[float] = None) -> jax.Array:
    """Decode over a contiguous view.

    q: (B, KV, G, hd); k_ctx/v_ctx: (B, KV, S, hd); ctx_len: (B,) live
    tokens (positions [0, ctx_len) are valid) -> (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    S = k_ctx.shape[2]
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   k_ctx.astype(jnp.float32)) * hd ** -0.5
    if softcap is not None:
        s = softcap_ref(s, softcap)
    pos = jnp.arange(S)[None]
    live = pos < ctx_len[:, None]
    if window is not None:
        live &= pos > (ctx_len[:, None] - 1 - window)
    s = jnp.where(live[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v_ctx.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, block_tables, seq_lens, *,
                        softcap: Optional[float] = None) -> jax.Array:
    """Decode through the block-table indirection.

    q: (B, KV, G, hd); pools: (nblocks, KV, bs, hd);
    block_tables: (B, MB) int32 (-1 unset); seq_lens: (B,).
    Gathers the context (the two-indirection 'traditional' path), then
    plain decode attention."""
    B = q.shape[0]
    MB = block_tables.shape[1]
    bs = k_pool.shape[2]
    safe = jnp.maximum(block_tables, 0)
    k_ctx = k_pool[safe]                   # (B, MB, KV, bs, hd)
    v_ctx = v_pool[safe]
    k_ctx = k_ctx.transpose(0, 2, 1, 3, 4).reshape(
        B, k_pool.shape[1], MB * bs, k_pool.shape[3])
    v_ctx = v_ctx.transpose(0, 2, 1, 3, 4).reshape(
        B, v_pool.shape[1], MB * bs, v_pool.shape[3])
    return decode_attention_ref(q, k_ctx, v_ctx, seq_lens, softcap=softcap)


def _probe_ref(keys, rows_of, key_lanes: int, val_lanes: int):
    """Naive bucket probe of every key: hash, row, cyclic probe by
    gathers, every word compared, first EMPTY ends the probe.

    ``keys`` (N,) uint32, or (W, N) words of wider keys (high first);
    ``rows_of(key)`` gives a key's (key row, value row), each word in
    its own lane block (a wide key as the tuple of its words).  Returns
    (N,), or (V, N) at V-word values."""
    from repro.core.hashing import (EMPTY_SENTINEL, MISS_SENTINEL,
                                    hash_bucket)
    keys = keys.astype(jnp.uint32)
    kw = 1 if keys.ndim == 1 else keys.shape[0]
    S = key_lanes // kw
    vw = val_lanes // S

    def one(key):
        if kw > 1:
            key = tuple(key[w] for w in range(kw))
        row_k, row_v = rows_of(key)
        start = hash_bucket(key) % jnp.uint32(S)
        pos = ((start + jnp.arange(S, dtype=jnp.uint32))
               % jnp.uint32(S)).astype(jnp.int32)
        hit = jnp.ones((S,), bool)
        empties = jnp.ones((S,), bool)
        for w, kword in enumerate(key if kw > 1 else (key,)):
            probed = row_k[w * S + pos]
            hit &= probed == kword
            empties &= probed == jnp.uint32(EMPTY_SENTINEL)
        before = jnp.cumsum(empties.astype(jnp.int32)) \
            - empties.astype(jnp.int32)
        live = hit & (before == 0)
        found = jnp.any(live)
        j = pos[jnp.argmax(live)]
        return jnp.stack([jnp.where(found, row_v[w * S + j],
                                    jnp.uint32(MISS_SENTINEL))
                          for w in range(vw)])

    out = jax.vmap(one, in_axes=keys.ndim - 1, out_axes=1)(keys)
    return out[0] if vw == 1 else out


def eh_lookup_ref(keys, directory, bucket_keys, bucket_vals,
                  global_depth) -> jax.Array:
    """Batched EH lookup: hash -> directory slot -> bucket probe.

    keys: (N,) uint32 or (2, N) words; directory: (D,) int32;
    bucket_keys/vals: (C, words * S).  Returns (N,) uint32 values
    (0xFFFFFFFF on miss), (2, N) at 64-bit values."""
    from repro.core.hashing import dir_slot, hash_dir

    def rows_of(key):
        b = directory[dir_slot(hash_dir(key), global_depth)]
        return bucket_keys[b], bucket_vals[b]

    return _probe_ref(keys, rows_of, bucket_keys.shape[1],
                      bucket_vals.shape[1])


def shortcut_lookup_ref(keys, view_keys, view_vals,
                        global_depth) -> jax.Array:
    """One-indirection variant: slot arithmetic + direct view probe."""
    from repro.core.hashing import dir_slot, hash_dir

    def rows_of(key):
        slot = dir_slot(hash_dir(key), global_depth)
        return view_keys[slot], view_vals[slot]

    return _probe_ref(keys, rows_of, view_keys.shape[1], view_vals.shape[1])


def ragged_copy_ref(view, pool, slots, offsets) -> jax.Array:
    """view[slots[i]] = pool[offsets[i]] (last write wins)."""
    return view.at[slots].set(pool[offsets])
