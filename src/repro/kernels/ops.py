"""Public jit'd entry points for the Pallas kernels.

Each op accepts *model-layout* arrays, adapts them to the kernel layouts,
and dispatches to the kernel.  Execution mode is auto-detected (compiled
on TPU, interpreted elsewhere — ``kernels/backend.py``).  ``ref.py``
holds the pure-jnp oracles the tests sweep against.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref  # noqa: F401  (re-exported for tests)
from repro.kernels.eh_lookup import eh_lookup, shortcut_lookup
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ragged_copy import ragged_copy
from repro.kernels.shortcut_attention import shortcut_attention


def mha_forward(q, k, v, *, causal: bool = True,
                window: Optional[int] = None,
                softcap: Optional[float] = None,
                bq: int = 256, bkv: int = 512) -> jax.Array:
    """Model-layout flash attention.

    q: (B, S, H, hd); k/v: (B, S, KV, hd) -> (B, S, H, hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qk = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)
    kk = k.transpose(0, 2, 1, 3)
    vk = v.transpose(0, 2, 1, 3)
    o = flash_attention(qk, kk, vk, causal=causal, window=window,
                        softcap=softcap, bq=bq, bkv=bkv)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)


def decode_shortcut(q, view_k, view_v, ctx_len, *,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    bs: int = 512) -> jax.Array:
    """Serve-layout shortcut decode.

    q: (B, H, hd); view_k/v: (B, S_cap, KV, hd); ctx_len: (B,).
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    KV = view_k.shape[2]
    G = H // KV
    qk = q.reshape(B, KV, G, hd)
    kk = view_k.transpose(0, 2, 1, 3)
    vk = view_v.transpose(0, 2, 1, 3)
    o = shortcut_attention(qk, kk, vk, ctx_len, window=window,
                           softcap=softcap, bs=bs)
    return o.reshape(B, H, hd)


def decode_paged(q, k_pool, v_pool, block_tables, seq_lens, *,
                 softcap: Optional[float] = None) -> jax.Array:
    """Serve-layout paged decode.

    q: (B, H, hd); pools: (nblocks, bs, KV, hd) (cache layout);
    block_tables: (B, MB); seq_lens: (B,).  Returns (B, H, hd)."""
    B, H, hd = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    qk = q.reshape(B, KV, G, hd)
    kp = k_pool.transpose(0, 2, 1, 3)   # (nblocks, KV, bs, hd)
    vp = v_pool.transpose(0, 2, 1, 3)
    o = paged_attention(qk, kp, vp, block_tables, seq_lens,
                        softcap=softcap)
    return o.reshape(B, H, hd)


def eh_lookup_op(keys, st, *, tile: int = 256) -> jax.Array:
    """Traditional fused lookup against an ``EHState``."""
    D = 1 << int(st.max_global_depth)
    return eh_lookup(keys, st.directory[:D], st.bucket_keys,
                     st.bucket_vals, st.global_depth, tile=tile)


def shortcut_lookup_op(keys, view_keys, view_vals, global_depth, *,
                       tile: int = 256) -> jax.Array:
    """Shortcut fused lookup against a composed view."""
    return shortcut_lookup(keys, view_keys, view_vals, global_depth,
                           tile=tile)


def remap_rows(view, pool, slots, offsets) -> jax.Array:
    """Maintenance replay: ``view[slots] = pool[offsets]`` (last wins)."""
    return ragged_copy(view, pool, slots, offsets)
