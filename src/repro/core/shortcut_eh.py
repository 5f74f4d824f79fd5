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
    (``runtime/mapper.ShortcutMapper``, DESIGN.md §4).  This class supplies
    only the two replay callables:
      - ``update`` replay remaps the view slots of touched buckets
        (``rewiring.remap_slots``);
      - ``create`` replay rebuilds the whole view after a directory
        doubling (``extendible_hashing.compose_shortcut``).
  * Lookups route through the shortcut only when it is in sync *and* the
    average fan-in is at most ``fan_in_threshold`` (paper: 8) — below that
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
    """Thin client of the shortcut-maintenance runtime for the EH index.

    ``async_mapper=True`` runs the paper's mapper thread; tests and
    deterministic benchmarks use ``async_mapper=False`` + :meth:`pump`.
    A custom ``routing`` policy (e.g.
    :class:`~repro.runtime.mapper.HysteresisRouting`) may replace the
    default fan-in threshold rule.

    ``key_bits``/``value_bits`` (32 or 64) fix the width of keys and
    values.  At 64 bits the host splits each key and value into its two
    uint32 words on the way in and joins the words of each answer on the
    way out (``split_seconds`` times both); ``lookup`` then answers with
    a numpy uint64 array.  An array whose dtype is wider than the width
    is refused, never wrapped.
    """

    def __init__(self, max_global_depth: int, bucket_slots: int,
                 capacity: int, *, fan_in_threshold: float = 8.0,
                 poll_interval: float = 0.025, async_mapper: bool = False,
                 routing=None, key_bits: int = 32, value_bits: int = 32):
        self.state = eh.eh_create(max_global_depth, bucket_slots, capacity,
                                  key_bits, value_bits)
        self.key_bits, self.value_bits = key_bits, value_bits
        # host seconds spent splitting 64-bit keys and values into words
        # and joining answers from them (0 at 32 bits)
        self.split_seconds = 0.0
        # The composed view is ONE atomically-swapped tuple
        # (view_keys, view_vals, view_log2): replays publish a fully
        # built tuple and readers snapshot it once, so a reader racing
        # an async replay can never pair new keys with old vals.
        # When bound to a StackedOperandCache (bind_operand_cache), the
        # stack owns the view instead and _view stays None — per-shard
        # reads become memoized slices of the stack (DESIGN.md §4.4).
        self._view: Optional[tuple] = None
        self._cache = None                  # StackedOperandCache or None
        self._shard = 0
        self._vfam = "eh_view"
        self._tfam = "eh_trad"
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
            routing=routing or FanInRouting(float(fan_in_threshold)),
            poll_interval=poll_interval, async_mapper=async_mapper,
            name="eh-mapper")

    # -- delegated bookkeeping (kept for API compatibility) ------------------

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
    def trad_version(self) -> int:
        return self.mapper.trad_version(GLOBAL_VIEW)

    @property
    def sc_version(self) -> int:
        return self.mapper.sc_version(GLOBAL_VIEW)

    @property
    def fan_in_threshold(self):
        return self.mapper.threshold

    @fan_in_threshold.setter
    def fan_in_threshold(self, value: float) -> None:
        self.mapper.threshold = value

    @property
    def poll_interval(self) -> float:
        return self.mapper.poll_interval

    # -- publish epochs (operand-cache keys; runtime/operand_cache.py) -------
    #
    # state_epoch moves with every ``self.state`` reassignment (insert
    # stores the new state, then ``record()`` bumps under the same
    # lock); view_epoch with every replay-batch publication of
    # ``self._view`` (bumped by the runtime before sc_version, so a
    # version gate can never certify a view the cache still sees as
    # clean-but-old).  Read the epoch BEFORE snapshotting the arrays.

    @property
    def state_epoch(self) -> int:
        return self.mapper.trad_epoch

    @property
    def view_epoch(self) -> int:
        return self.mapper.view_epoch

    # -- operand-cache binding (inverted ownership, DESIGN.md §4.4) ----------

    def bind_operand_cache(self, cache, shard: int, *,
                           view_family: str = "eh_view",
                           trad_family: str = "eh_trad") -> None:
        """Hand view ownership to a stacked operand cache.

        After binding, replays publish straight into the owning shard's
        slice of the stacked ``view_family`` (at the mapper's
        ``next_view_epoch``, before ``sc_version`` moves), inserts keep
        ``trad_family`` warm once a lookup built it, and every per-shard
        view read is a memoized slice of the stack — the local ``_view``
        duplicate is deleted.  Bind before any maintenance is enqueued
        (``ShardedShortcutEH`` binds at construction)."""
        self._cache = cache
        self._shard = int(shard)
        self._vfam = view_family
        self._tfam = trad_family
        self._view = None        # the stack is the primary storage now
        self._bound_memo = None

    def _bound_view(self) -> Optional[tuple]:
        """(view_keys, view_vals, view_log2) slices of the stack, or
        None before this shard's first publication.  view_keys/vals are
        padded to the stacked extent; rows past ``2**view_log2`` are
        never indexed (the lookup slots by the shard's own log2).
        Memoized on the cache's slice identity, so the device->host
        ``view_log2`` read happens once per publish, not per lookup."""
        pub = self._cache.published(self._vfam)
        if pub is None or not pub[self._shard]:
            return None
        sl = self._cache.slice_of(self._vfam, self._shard)
        memo = self._bound_memo
        if memo is not None and memo[0] is sl:
            return memo[1]
        view = (sl[0], sl[1], int(sl[2]))
        self._bound_memo = (sl, view)
        return view

    # -- view snapshot (atomic read; see _view comment in __init__) ----------

    def view_snapshot(self) -> Optional[tuple]:
        """One consistent (view_keys, view_vals, view_log2) or None."""
        if self._cache is not None:
            return self._bound_view()
        return self._view

    @property
    def view_keys(self) -> Optional[jax.Array]:
        v = self.view_snapshot()
        return None if v is None else v[0]

    @property
    def view_vals(self) -> Optional[jax.Array]:
        v = self.view_snapshot()
        return None if v is None else v[1]

    @property
    def view_log2(self) -> int:
        v = self.view_snapshot()
        return -1 if v is None else v[2]

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

    def _to_host(self, res: jax.Array, span: str):
        """Answers as the caller gets them: as they are at 32-bit values,
        joined into a numpy uint64 array at 64."""
        if self.value_bits == 32:
            return res
        res = np.asarray(res)
        with TraceAnnotation(span):
            t = time.perf_counter()
            out = hashing.join_words(res)
            self.split_seconds += time.perf_counter() - t
        return out

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
                if self._cache is not None:
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

    def lookup(self, keys) -> jax.Array:
        """Route through the shortcut when in sync and fan-in permits."""
        keys = self._to_device(keys, self.key_bits, "key", "lookup.split")
        return self._to_host(self._lookup(keys), "lookup.split")

    def _lookup(self, keys: jax.Array) -> jax.Array:
        # gate FIRST, snapshot after: a replay landing in between
        # publishes a strictly newer view, which the gate's verdict
        # still covers; snapshotting first would let the gate certify
        # a stale tuple (async mode could then serve pre-insert data)
        use = self.mapper.gate(self.avg_fan_in(), [GLOBAL_VIEW])
        view = self.view_snapshot()   # single read: the swap is atomic
        use = use and view is not None
        self.mapper.count_route(use)
        if use:
            if self._cache is not None and jax.default_backend() == "tpu":
                # resolve straight off the stacked primary: the kernel
                # block-selects the shard via scalar prefetch, so no
                # per-shard slice is ever materialized on device
                from repro.kernels.eh_lookup import stacked_shortcut_lookup
                ops = self._cache.handle(self._vfam)
                return stacked_shortcut_lookup(keys, *ops, self._shard)
            # the tuple's own view_log2, never the live global_depth: a
            # doubling after the snapshot would index past the view.
            # Bound mode pays nothing extra here: view_snapshot is the
            # cache's memoized slice of the stack (zero device work in
            # steady state; the slice cost was paid at publish time).
            return eh.shortcut_lookup_many(view[0], view[1], view[2], keys)
        return eh.eh_lookup_many(self.state, keys)

    def use_shortcut(self) -> bool:
        return (self.view_snapshot() is not None
                and self.mapper.gate(self.avg_fan_in(), [GLOBAL_VIEW]))

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
        if self._cache is not None:
            # the stacked family IS the published object readers get
            return self._cache.handle(self._vfam) or ()
        view = self._view
        return () if view is None else view[:2]

    def _publish_view(self, vk, vv, vlog2: int) -> None:
        """Publish one replayed view: bound mode writes the owning
        shard's slice of the stack at the mapper's ``next_view_epoch``
        (zero-copy publish — this runs on the mapper thread, before
        ``sc_version`` moves; a view grown past the stacked extent
        triggers the cache's background re-stack); standalone mode is
        the classic atomic tuple swap."""
        if self._cache is not None:
            self._cache.publish(
                self._vfam, self._shard,
                (vk, vv, jnp.asarray(vlog2, jnp.int32)),
                epoch=self.mapper.next_view_epoch)
            return
        self._view = (vk, vv, vlog2)

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
            if self._cache is not None:
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

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
