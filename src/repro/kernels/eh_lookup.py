"""Fused extendible-hashing lookup kernels — the paper's hot loop on TPU.

Two access paths, mirroring §2 of the paper:

  * the *traditional* path: hash -> directory read -> bucket row ->
    probe.  Two data-dependent indirections.
  * the *shortcut* path: hash -> view row -> probe.  One indirection:
    the composed view (``rewiring.compose``) plays the role of the page
    table having pre-resolved the mapping.

Every entry point is one ``pallas_call`` over a grid of (shard, key
tile) with one shared body, :func:`_resolve_tile`:

  * :func:`sharded_eh_lookup` / :func:`sharded_shortcut_lookup` — the
    partitioned index (``core/sharded_eh.py``): per-shard structures
    stacked on a leading shard axis, the shard loop a grid dimension, so
    N shards share one kernel specialization.  The flat index is their
    N=1 case.
  * :func:`sharded_routed_lookup` — per-shard routed: a scalar-prefetched
    ``two_level`` flag per shard picks the directory or the composed
    view *inside the same dispatch*; the flag is uniform per grid cell,
    so each cell runs exactly one ``pl.when`` arm of the shared body.

How a key tile resolves on the chip (DESIGN.md §2.4).  One shard's bucket
pools (or composed view) are VMEM-resident for the whole shard: the block
index is constant along the key-tile axis, so those blocks are single
buffered.  VMEM lays a row out on 128 lanes, so a 64-slot u32 row takes a
full 128-lane row: one shard's 2^14-row view pair occupies 16 MiB of VMEM,
and ``vmem_limit_bytes`` is sized from the blocks.  The directory block
lives in SMEM, where the scalar unit reads it.  Per tile:

  1. the VPU hashes the lane-major key tile into directory slots, and one
     local DMA moves the slots to SMEM;
  2. a scalar loop reads each key's row index (the directory read is the
     traditional path's extra indirection) and copies that key's bucket
     row into a (tile, S) gather buffer with dynamic sublane loads;
  3. the probe runs on the whole tile at once as a rank mask over the
     gathered rows (``hashing.probe_rows``), and a transpose returns the
     per-key results to the lane-major output tile.

Keys and values of 64 bits are two uint32 words each (``core/hashing``).
Such keys come as ``(..., 2, K)`` word planes, high words first, where
32-bit keys come as ``(..., K)``; a 64-bit page of S slots is one
``2 * S``-lane key row (high words in lanes ``[0, S)``, low words in
``[S, 2S)``) and one value row laid out the same way, so the row gather
moves the same bytes as a 32-bit page of ``2 * S`` slots.  The answers
have the value's words: ``(..., K)``, or ``(..., 2, K)`` at 64-bit
values.  The widths follow from the shapes, and so are static.

``tile`` must be a multiple of 128 (the lane width).  ``interpret=None``
compiles on a TPU and interprets elsewhere (``kernels/backend.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing
from repro.kernels.backend import resolve_interpret

# hashing's sentinels are python ints (NOT jnp scalars: a traced
# module-level constant would be captured by the kernel, which pallas
# forbids); cast at use sites.
MISS = hashing.MISS_SENTINEL

_LANES = 128
_SUBLANES = 8
_SCOPED_VMEM_DEFAULT = 16 << 20     # v5e's default scoped VMEM limit
_VMEM_MARGIN = 4 << 20              # Mosaic's own internal scratch


def _column(row):
    """(1, T) lane-major -> (T, 1) sublane-major, via a 2-D transpose
    (Mosaic has no vector reshape across the lane axis)."""
    return jnp.transpose(jnp.broadcast_to(row, (_SUBLANES, row.shape[1])))[
        :, :1]


def _row(col):
    """(T, 1) -> (1, T): the inverse of :func:`_column`."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], _LANES)))[
        :1, :]


def _resolve_tile(keys_ref, g, dir_ref, bk_ref, bv_ref, out_ref,
                  rows_k, rows_v, slot_v, slot_s, sem):
    """THE lookup body: resolve one (words, tile) key tile against one
    shard's pages.  ``dir_ref`` None = shortcut (the slot IS the row)."""
    kw, tile = keys_ref.shape
    keys = keys_ref[...] if kw == 1 else tuple(
        keys_ref[pl.ds(w, 1), :] for w in range(kw))
    slot_v[...] = hashing.dir_slot(hashing.hash_dir(keys), g)
    copy = pltpu.make_async_copy(slot_v, slot_s, sem)
    copy.start()
    copy.wait()

    def gather(i, carry):
        slot = slot_s[0, i]
        if dir_ref is None:
            row = slot
        else:                                  # indirection 1: directory
            row = dir_ref[slot // _LANES, slot % _LANES]
        rows_k[pl.ds(i, 1), :] = bk_ref[pl.ds(row, 1), :]
        rows_v[pl.ds(i, 1), :] = bv_ref[pl.ds(row, 1), :]
        return carry

    jax.lax.fori_loop(0, tile, gather, 0)
    found, value = hashing.probe_rows(rows_k[...], rows_v[...],
                                      hashing.per_word(_column, keys))
    if not isinstance(value, tuple):
        out_ref[...] = _row(jnp.where(found, value, jnp.uint32(MISS)))
        return
    for w, v in enumerate(value):
        out_ref[pl.ds(w, 1), :] = _row(jnp.where(found, v, jnp.uint32(MISS)))


def _lookup_kernel(gd_ref, keys_ref, *refs, two_level: bool):
    """One (shard, key-tile) grid cell, single-mode (``two_level`` is a
    static python bool baked into the specialization).  The shard's
    global depth comes from the scalar-prefetch vector — the only
    per-shard scalar, which is what lets every shard share this one
    specialization."""
    if not two_level:
        refs = (None,) + refs
    _resolve_tile(keys_ref, gd_ref[pl.program_id(0)], *refs)


def _routed_kernel(sc_ref, keys_ref, dir_ref, bk_ref, bv_ref, vk_ref,
                   vv_ref, out_ref, *scratch):
    """One (shard, key-tile) grid cell, per-shard routed.

    ``sc_ref`` is the packed (3, N) scalar-prefetch block: row 0 the
    per-shard ``two_level`` flags (1 → resolve traditionally through the
    directory, 0 → through the composed view), row 1 the traditional
    global depths, row 2 the view log2 sizes."""
    s = pl.program_id(0)

    @pl.when(sc_ref[0, s] != 0)
    def _traditional():
        _resolve_tile(keys_ref, sc_ref[1, s], dir_ref, bk_ref, bv_ref,
                      out_ref, *scratch)

    @pl.when(sc_ref[0, s] == 0)
    def _shortcut():
        _resolve_tile(keys_ref, sc_ref[2, s], None, vk_ref, vv_ref,
                      out_ref, *scratch)


def _lanes(pool) -> int:
    """A pool row's lanes in VMEM: padded to whole 128-lane rows."""
    return -(-pool.shape[-1] // _LANES) * _LANES


def _vmem_limit(pools, tile: int) -> int:
    """Scoped VMEM for one grid cell: the single-buffered pool blocks, the
    gather buffers (a key row and a value row per key) and the
    double-buffered key/output tiles (whose word rows pad to 8
    sublanes), every row padded to whole 128-lane rows."""
    resident = sum(p.shape[1] * _lanes(p) for p in pools) * 4
    gather = tile * (_lanes(pools[0]) + _lanes(pools[1])) * 4
    tiles = 5 * _SUBLANES * tile * 4
    return max(_SCOPED_VMEM_DEFAULT,
               resident + gather + tiles + _VMEM_MARGIN)


def _call(kernel, scalars, keys, directory, pools, *, tile: int,
          interpret: Optional[bool]):
    """What every entry point calls: ONE ``pallas_call`` over grid
    (shards, key tiles).

    keys (N, n), or (N, 2, n) words; directory (N, D) or None; pools:
    (N, R, words * S) arrays, key and value pools alternating.  Returns
    (N, n) uint32, or (N, 2, n) at 64-bit values."""
    if tile % _LANES:
        raise ValueError(f"tile must be a multiple of {_LANES}, got {tile}")
    n = keys.shape[-1]
    pad = (-n) % tile
    if keys.ndim == 2:
        keys = jnp.pad(keys.astype(jnp.uint32),
                       ((0, 0), (0, pad)))[:, None, :]
    else:
        keys = jnp.pad(keys.astype(jnp.uint32), ((0, 0), (0, 0), (0, pad)))
    B, kw, _ = keys.shape
    slots = pools[0].shape[-1] // kw
    vw = pools[1].shape[-1] // slots
    key_spec = pl.BlockSpec((None, kw, tile), lambda b, i, sc: (b, 0, i))
    out_spec = pl.BlockSpec((None, vw, tile), lambda b, i, sc: (b, 0, i))

    def per_shard(block, **kw):
        return pl.BlockSpec((None,) + block,
                            lambda b, i, sc: (b, 0, 0),
                            pipeline_mode=pl.Buffered(1), **kw)

    in_specs, args = [key_spec], [keys]
    if directory is not None:
        # (N, D) -> (N, D/128, 128): a 2-D block the scalar unit indexes
        # in SMEM; rows past 2**depth are never read, so zero padding is
        # inert
        N, D = directory.shape
        dpad = (-D) % _LANES
        directory = jnp.pad(directory.astype(jnp.int32),
                            ((0, 0), (0, dpad))).reshape(N, -1, _LANES)
        in_specs.append(per_shard(directory.shape[1:],
                                  memory_space=pltpu.SMEM))
        args.append(directory)
    for p in pools:
        in_specs.append(per_shard(p.shape[1:]))
        args.append(p)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, (n + pad) // tile),
            in_specs=in_specs, out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((tile, pools[0].shape[-1]),
                                       jnp.uint32),
                            pltpu.VMEM((tile, pools[1].shape[-1]),
                                       jnp.uint32),
                            pltpu.VMEM((1, tile), jnp.int32),
                            pltpu.SMEM((1, tile), jnp.int32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((B, vw, n + pad), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(pools, tile)),
        interpret=resolve_interpret(interpret),
    )(scalars, *args)
    return out[:, 0, :n] if vw == 1 else out[..., :n]


# ---------------------------------------------------------------------------
# Entry points (``core/sharded_eh.py``): one dispatch, one specialization,
# shard = grid dimension.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sharded_eh_lookup(keys, directories, bucket_keys, bucket_vals,
                      global_depths, *, tile: int = 256,
                      interpret: Optional[bool] = None):
    """Traditional lookup across N stacked shards.

    keys: (N, K), or (N, 2, K) words — shard-bucketized, padded to a
    static per-shard capacity (pad lanes return MISS or a stray hit and
    are dropped by the caller's scatter-back); directories: (N, D);
    bucket_keys/vals: (N, C, words * S); global_depths: (N,).  Returns
    (N, K) uint32, or (N, 2, K) at 64-bit values."""
    return _call(functools.partial(_lookup_kernel, two_level=True),
                 global_depths.astype(jnp.int32), keys, directories,
                 (bucket_keys, bucket_vals), tile=tile,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sharded_shortcut_lookup(keys, view_keys, view_vals, global_depths, *,
                            tile: int = 256,
                            interpret: Optional[bool] = None):
    """Shortcut lookup across N stacked shards (views (N, V, S))."""
    return _call(functools.partial(_lookup_kernel, two_level=False),
                 global_depths.astype(jnp.int32), keys, None,
                 (view_keys, view_vals), tile=tile,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sharded_routed_lookup(keys, directories, bucket_keys, bucket_vals,
                          global_depths, view_keys, view_vals, view_log2s,
                          two_level, *, tile: int = 256,
                          interpret: Optional[bool] = None):
    """Per-shard routed lookup across N stacked shards: ONE dispatch
    even when the shards disagree about their access path.

    ``two_level`` is the per-shard flag vector (N,): nonzero shards
    resolve traditionally (directories (N, D) + bucket pools (N, C, S)
    at ``global_depths``), zero shards resolve through their composed
    views ((N, V, S), slot-indexed at ``view_log2s``; rows past
    ``2**view_log2s[s]`` are pad and never indexed).  Both operand sets
    are VMEM-resident per shard — the price of not demoting a mixed
    batch is one extra resident block pair.  Returns (N, K) uint32 in
    the same padded layout as :func:`sharded_eh_lookup`.
    """
    if bucket_keys.shape[-1] != view_keys.shape[-1]:
        raise ValueError(
            f"bucket/view slot widths differ: {bucket_keys.shape[-1]} "
            f"vs {view_keys.shape[-1]}")
    scalars = jnp.stack([two_level.astype(jnp.int32),
                         global_depths.astype(jnp.int32),
                         view_log2s.astype(jnp.int32)])        # (3, N)
    return _call(_routed_kernel, scalars, keys, directories,
                 (bucket_keys, bucket_vals, view_keys, view_vals),
                 tile=tile, interpret=interpret)
