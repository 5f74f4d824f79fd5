"""Shortcut-EH: extendible hashing accompanied by an asynchronously
maintained shortcut directory (paper §4.1).

Architecture (faithful to the paper):

  * The *traditional* directory (``EHState``) is authoritative; every
    modification is applied to it synchronously and bumps the traditional
    version.
  * Maintenance — the FIFO request queue, the polling mapper thread (paper:
    25 ms) / synchronous ``pump()``, create-collapses-older-updates
    batching, eager ``block_until_ready`` population, version gating and
    fan-in routing — is the *generic* shortcut-maintenance runtime
    (``runtime/mapper.ShortcutMapper``, DESIGN.md §4).  This class, one
    shard of ``core/sharded_eh.ShardedShortcutEH``, supplies only the two
    replay callables:
      - ``update`` replay remaps the view slots of touched buckets
        (``rewiring.remap_slots``);
      - ``create`` replay rebuilds the whole view after a directory
        doubling (``extendible_hashing.compose_shortcut``).
  * Lookups (``ShardedShortcutEH.lookup_batched``, the only lookup) route
    a shard through the shortcut only when it is in sync *and* the
    average fan-in is at most ``fan_in_threshold`` (paper: 8) — above it
    the TLB-thrashing analogue (a virtual footprint of 2^g pages vs 2^g
    pointers + m pages) makes the traditional path cheaper
    (:class:`~repro.runtime.mapper.FanInRouting`).

Delta vs the paper (see DESIGN.md §2): the paper's shortcut *shares*
physical pages, so ordinary in-bucket inserts are instantly visible through
it.  XLA arrays are immutable, so our view is a replica; consequently *every*
insert batch enqueues maintenance for the touched buckets, not only splits.
The asynchronous, version-gated architecture is unchanged.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import extendible_hashing as eh
from repro.core import hashing, rewiring
from repro.runtime.mapper import (GLOBAL_VIEW, FanInRouting,
                                  MaintenanceStats, ShortcutMapper)

__all__ = ["ShortcutEH", "MaintenanceStats"]


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


# Padded replay-chunk sizes (bounded set => bounded number of jit variants).
_CHUNK_SIZES = (64, 256, 1024, 4096, 16384, 65536)


def _pad_chunk(n: int) -> int:
    for c in _CHUNK_SIZES:
        if n <= c:
            return c
    return _CHUNK_SIZES[-1]


@contextmanager
def _held(lock, span: str):
    """Hold ``lock``, with the wait for it as the profiler span ``span``."""
    with TraceAnnotation(span):
        lock.acquire()
    try:
        yield
    finally:
        lock.release()


class ShortcutEH:
    """One shard of the index: its extendible-hashing state, its mapper
    and its replays.  :class:`~repro.core.sharded_eh.ShardedShortcutEH`
    builds the shards and answers every lookup; this class has no lookup.

    The shard's composed view lives only in ``operands`` (a
    :class:`~repro.runtime.operand_cache.StackedOperandCache`), as row
    ``shard`` of its ``"eh_view"`` family: replays publish into it,
    and :meth:`view_snapshot` reads a memoised slice of it.  ``insert``
    keeps the ``"eh_trad"`` family's row warm once a lookup built it.

    ``async_mapper=True`` runs the paper's mapper thread; tests use
    ``async_mapper=False`` + :meth:`pump`.

    ``key_bits``/``value_bits`` (32 or 64) fix the width of keys and
    values.  At 64 bits the host splits each key and value into its two
    uint32 words on the way in (``split_seconds`` times it).  An array
    whose dtype is wider than the width is refused, never wrapped.
    """

    def __init__(self, max_global_depth: int, bucket_slots: int,
                 capacity: int, operands, shard: int, *,
                 fan_in_threshold: float = 8.0,
                 poll_interval: float = 0.025, async_mapper: bool = False,
                 key_bits: int = 32, value_bits: int = 32):
        self.state = eh.eh_create(max_global_depth, bucket_slots, capacity,
                                  key_bits, value_bits)
        self.key_bits, self.value_bits = key_bits, value_bits
        # host seconds spent splitting 64-bit keys and values into words
        # (0 at 32 bits)
        self.split_seconds = 0.0
        self._cache = operands
        self._shard = int(shard)
        self._vfam = "eh_view"
        self._tfam = "eh_trad"
        self._memo = None         # (stack slice, view) of view_snapshot
        # insert's keys by how eh_insert_many took them: overwritten in
        # place (present when their batch began) or run through the
        # sequential loop (absent)
        self.keys_in_place = 0
        self.keys_scanned = 0
        # buckets in the touched sets handed to update requests
        self.buckets_touched = 0
        # the directory's depth as the last insert left it, kept on the
        # host so an insert reads the device once
        self._global_depth = int(self.state.global_depth)
        self.mapper = ShortcutMapper(
            replay_create=self._replay_create,
            replay_update=self._replay_update,
            snapshot=lambda: self.state,
            view_arrays=self._view_arrays,
            routing=FanInRouting(float(fan_in_threshold)),
            poll_interval=poll_interval, async_mapper=async_mapper,
            name="eh-mapper")

    # -- delegated bookkeeping ----------------------------------------------

    @property
    def stats(self) -> MaintenanceStats:
        return self.mapper.stats

    @property
    def routed_shortcut(self) -> int:
        return self.mapper.routed_shortcut

    @property
    def routed_traditional(self) -> int:
        return self.mapper.routed_fallback

    @property
    def fan_in_threshold(self):
        return self.mapper.threshold

    @fan_in_threshold.setter
    def fan_in_threshold(self, value: float) -> None:
        self.mapper.threshold = value

    # -- publish epochs (operand-cache keys; runtime/operand_cache.py) -------
    #
    # state_epoch moves with every ``self.state`` reassignment (insert
    # stores the new state, then ``record()`` bumps under the same
    # lock); view_epoch with every replay-batch publication of the
    # shard's view (bumped by the runtime before sc_version, so a
    # version gate can never certify a view the cache still sees as
    # clean-but-old).  Read the epoch BEFORE snapshotting the arrays.

    @property
    def state_epoch(self) -> int:
        return self.mapper.trad_epoch

    @property
    def view_epoch(self) -> int:
        return self.mapper.view_epoch

    # -- the shard's view ---------------------------------------------------

    def view_snapshot(self) -> Optional[tuple]:
        """One consistent (view_keys, view_vals, view_log2), or None
        before this shard's first publication: slices of the stack.
        view_keys/vals are padded to the stacked extent; rows past
        ``2**view_log2`` are never indexed (the lookup slots by the
        shard's own log2).  Memoised on the cache's slice identity, so
        the device->host ``view_log2`` read happens once a publish, not
        once a read."""
        pub = self._cache.published(self._vfam)
        if pub is None or not pub[self._shard]:
            return None
        sl = self._cache.slice_of(self._vfam, self._shard)
        memo = self._memo
        if memo is not None and memo[0] is sl:
            return memo[1]
        view = (sl[0], sl[1], int(sl[2]))
        self._memo = (sl, view)
        return view

    # -- the host's words ----------------------------------------------------

    def _to_device(self, a, bits: int, what: str, span: str) -> jax.Array:
        """A host batch as the device's uint32 words: ``(n,)`` at 32
        bits, ``(2, n)`` at 64 (split in profiler span ``span``)."""
        a = hashing.check_width(a, bits, what)
        if bits == 32:
            return jnp.asarray(a, jnp.uint32)
        with TraceAnnotation(span):
            t = time.perf_counter()
            words = hashing.split_words(np.asarray(a))
            self.split_seconds += time.perf_counter() - t
        return jnp.asarray(words)

    # -- main-thread API ----------------------------------------------------

    def insert(self, keys, values) -> None:
        """Synchronous insert into the traditional index + enqueue
        maintenance (the paper's main-thread behaviour).

        Profiler spans: ``insert.scan`` (``eh_insert_many``, waited for
        by its one device read), ``insert.lock`` (the wait for the
        mapper's lock), ``insert.publish``, ``insert.touched``,
        ``insert.submit``; at 64 bits first ``insert.split``."""
        keys = self._to_device(keys, self.key_bits, "key", "insert.split")
        values = self._to_device(values, self.value_bits, "value",
                                 "insert.split")
        with TraceAnnotation("insert.scan"):
            old_g = self._global_depth
            with _held(self.mapper.lock, "insert.lock"):
                self.state, fresh, touched = eh.eh_insert_many(
                    self.state, keys, values)
                new_g, fresh, touched = jax.device_get(
                    (self.state.global_depth, fresh, touched))
                new_g, fresh = int(new_g), int(fresh)
                self._global_depth = new_g
                self.keys_scanned += fresh
                self.keys_in_place += keys.shape[-1] - fresh
                versions = self.mapper.record([GLOBAL_VIEW])
                # keep the stacked traditional family warm at
                # publish (write) time — but only once a lookup
                # actually built it; a shortcut-routed steady state
                # never pays for (or holds) the traditional stack
                st = self.state
                with TraceAnnotation("insert.publish"):
                    self._cache.publish_if_present(
                        self._tfam, self._shard,
                        lambda: (st.directory, st.bucket_keys,
                                 st.bucket_vals, st.global_depth),
                        epoch=self.mapper.trad_epoch)
        if new_g != old_g:
            # doubling: the runtime pops outdated updates before the create
            with TraceAnnotation("insert.submit", version=versions[0]):
                self.mapper.submit_create([GLOBAL_VIEW], versions)
        else:
            with TraceAnnotation("insert.touched"):
                touched = np.flatnonzero(touched).astype(np.int32)
                self.buckets_touched += touched.size
            with TraceAnnotation("insert.submit", version=versions[0]):
                self.mapper.submit_update([GLOBAL_VIEW], versions,
                                          payload=touched)

    def in_sync(self) -> bool:
        return self.mapper.in_sync([GLOBAL_VIEW])

    def avg_fan_in(self) -> float:
        return float((1 << int(self.state.global_depth))
                     / max(1, int(self.state.num_buckets)))

    def versions(self) -> tuple[int, int]:
        return self.mapper.versions(GLOBAL_VIEW)

    def pump(self, max_requests: int = 1 << 30) -> int:
        """Synchronously process pending maintenance (mapper surrogate)."""
        return self.mapper.pump(max_requests)

    def wait_in_sync(self, timeout: float = 30.0) -> bool:
        """Block until the shortcut caught up (async mode)."""
        return self.mapper.wait_in_sync([GLOBAL_VIEW], timeout)

    def close(self) -> None:
        self.mapper.close()

    # -- replay callables (the only EH-specific maintenance code) ------------

    def _view_arrays(self):
        # the stacked family IS the published object readers get
        return self._cache.handle(self._vfam) or ()

    def _publish_view(self, vk, vv, vlog2: int) -> None:
        """Publish one replayed view into the owning shard's row of the
        stack at the mapper's ``next_view_epoch`` (this runs on the
        mapper thread, before ``sc_version`` moves; a view grown past
        the stacked extent triggers the cache's re-stack)."""
        self._cache.publish(
            self._vfam, self._shard,
            (vk, vv, jnp.asarray(vlog2, jnp.int32)),
            epoch=self.mapper.next_view_epoch)

    def _replay_create(self, st: eh.EHState, requests) -> None:
        g = int(st.global_depth)
        view_slots = _next_pow2(1 << g)
        vk, vv = eh.compose_shortcut(st, view_slots)
        self._publish_view(vk, vv, view_slots.bit_length() - 1)
        self.mapper.stats.slots_remapped += view_slots

    def _replay_update(self, st: eh.EHState, requests) -> None:
        """Remap every view slot whose bucket is in the merged touched set.

        Host-side slot discovery (the mapper owns this cost, per §3.3), then
        a padded device scatter — ``rewiring.remap_slots`` is the per-slot
        ``mmap(MAP_SHARED|MAP_FIXED)`` replay; padding remaps slot 0 onto its
        own current bucket (a no-op), mirroring the paper's coalescing of
        neighbouring remaps into fewer calls.
        """
        view = self.view_snapshot()
        if view is None:
            # the composed view already reflects the snapshot (and thus
            # these updates); remapping on top would be duplicate work
            self._replay_create(st, requests)
            return
        vk, vv, vlog2 = view
        with TraceAnnotation("mapper.discover"):
            touched = np.unique(np.concatenate([r.payload
                                                for r in requests]))
            g = int(st.global_depth)
            dir_np = np.asarray(st.directory[: 1 << g])
            stale = np.isin(dir_np, touched)
            slots = np.nonzero(stale)[0].astype(np.int32)
        if slots.size == 0:
            # no stale slots, but the reader is still owed an epoch:
            # this _process will bump view_epoch and publish its
            # sc versions, and the entry must never lag a
            # gate-certified version
            self._cache.touch(self._vfam, self._shard,
                              epoch=self.mapper.next_view_epoch)
            return
        with TraceAnnotation("mapper.remap"):
            n = _pad_chunk(slots.size)
            pad = n - slots.size
            slots_p = np.concatenate([slots, np.zeros(pad, np.int32)])
            offsets_p = dir_np[slots_p].astype(np.int32)
            vk = rewiring.remap_slots(vk, st.bucket_keys, slots_p,
                                      offsets_p)
            vv = rewiring.remap_slots(vv, st.bucket_vals, slots_p,
                                      offsets_p)
            self._publish_view(vk, vv, vlog2)
        self.mapper.stats.slots_remapped += int(slots.size)
