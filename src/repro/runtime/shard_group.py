"""Sharded shortcut runtime: a group of independent mappers (DESIGN.md §4).

The generic runtime (``runtime/mapper.ShortcutMapper``) maintains ONE
shortcut view family.  Production-scale structures partition their key
space into shards — each shard a full structure of its own — exactly to
localize translation state (cf. Utopia's restrictive mappings and
NDPage's per-unit page tables in PAPERS.md): per-shard view size stays
bounded (the VMEM-resident regime of the Pallas kernels, DESIGN.md
§2.4), and maintenance, versioning, and the create-collapses-updates
batching are confined to one shard instead of the whole structure (the
paper's §5 shootdown concern).

:class:`MapperGroup` owns N :class:`~repro.runtime.mapper.ShortcutMapper`
instances with **independent** queues, versions, routing policies, locks
and (in async mode) threads, plus:

  * a **key → shard router** (client-supplied; Sharded-EH routes on the
    top bits of the directory hash, the KV manager on ``seq_id % N``);
  * an optional :class:`ShardViewRegistry` — per-shard atomically-swapped
    view tuples, so replay callables and ``view_arrays`` read the
    registry instead of closing over whole-structure client attributes;
  * **aggregated** :class:`~repro.runtime.mapper.MaintenanceStats` and
    route counters across the group (per-shard stats remain available
    through each member); batch-level route decisions that span shards
    land on a **group-level** counter instead of being misattributed to
    one shard;
  * group-wide ``pump()`` / ``wait_in_sync()`` / ``close()`` and the
    sharded version gate :meth:`in_sync` / :meth:`gate`, keyed by
    ``{shard: view keys}`` so a read only waits on the shards it
    actually touches.

The group deliberately does NOT share any state between members: one
shard's create request can never collapse, gate, or serialize behind
another shard's updates — that independence is the point, and
``tests/test_sharded_eh.py`` pins it.

This module also owns the generic **cross-shard batching** helpers every
sharded client shares (:func:`shard_order`, :func:`partition_by_shard`,
:func:`pad_batch`): one stable argsort pass bucketizes a batch per
shard, pads each shard's sub-batch to a static capacity drawn from a
bounded size set (bounded set ⇒ bounded jit variants), and the returned
permutation scatters per-shard results back to input order.  Sharded-EH
uses them for its fused lookup; the KV manager for its cross-shard
``get_context``.
"""
from __future__ import annotations

import time
from dataclasses import fields
from typing import (Callable, Dict, Hashable, Iterable, List, Optional,
                    Sequence)

import numpy as np

from repro.runtime.mapper import MaintenanceStats, ShortcutMapper

#: ``{shard index: view keys}`` — the sharded analogue of the key lists
#: the flat runtime takes; ``None`` values mean "all keys of that shard".
KeysByShard = Dict[int, Optional[Iterable[Hashable]]]

#: Static per-shard batch capacities (bounded set => bounded number of
#: jit/pallas variants), mirroring ``shortcut_eh._CHUNK_SIZES``.
_BATCH_SIZES = (64, 256, 1024, 4096, 16384, 65536, 262144)


def pad_batch(n: int) -> int:
    """Smallest static capacity from :data:`_BATCH_SIZES` holding ``n``
    (multiples of the largest beyond it)."""
    for c in _BATCH_SIZES:
        if n <= c:
            return c
    return -(-n // _BATCH_SIZES[-1]) * _BATCH_SIZES[-1]


def shard_order(sid: np.ndarray, num_shards: int):
    """The one stable argsort pass every batched operation shares:
    returns ``(order, counts, starts)`` — shard-sort permutation,
    per-shard key counts, and each shard's offset in the sorted order."""
    order = np.argsort(sid, kind="stable")
    counts = np.bincount(sid, minlength=num_shards)
    starts = np.zeros(num_shards, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    return order, counts, starts


def partition_by_shard(keys: np.ndarray, sid: np.ndarray, num_shards: int,
                       cap: int, fill: int = 0, *, order=None, counts=None,
                       starts=None):
    """Bucketize ``keys`` per shard (via :func:`shard_order`, reused when
    the caller already ran it to size ``cap``).

    Returns ``(padded, counts, order, rank)``: ``padded`` is
    (num_shards, cap) with shard s's keys in ``padded[s, :counts[s]]``
    and ``fill`` elsewhere; ``order``/``rank`` invert the permutation —
    input element ``order[i]`` sits at ``padded[sid[order][i],
    rank[i]]``, so per-shard results scatter back to input order with
    ``out[order] = results[sid[order], rank]``.
    """
    keys = np.asarray(keys)
    if order is None or counts is None or starts is None:
        order, counts, starts = shard_order(sid, num_shards)
    sid_sorted = sid[order]
    rank = np.arange(keys.size, dtype=np.int64) - starts[sid_sorted]
    padded = np.full((num_shards, cap), fill, keys.dtype)
    padded[sid_sorted, rank] = keys[order]
    return padded, counts, order, rank


class ShardViewRegistry:
    """Per-shard, atomically-published shortcut view tuples.

    Two storage modes behind one API:

    **Standalone** (``cache=None``): each slot holds ONE tuple of device
    arrays (or ``None`` before the first publication).  :meth:`publish`
    is a single list-item store and :meth:`snapshot` a single list-item
    load — both atomic under the GIL — so a reader can never pair
    arrays from two different publications of the same shard (the tear
    the KV manager's old two-attribute ``view_k, view_v = ...``
    publication allowed).

    **Cache-backed** (``cache=`` a
    :class:`~repro.runtime.operand_cache.StackedOperandCache`): the
    registry stops owning any arrays and becomes a per-shard facade of
    one stacked operand family — :meth:`publish` writes the shard's
    slice straight into the stack at the caller-supplied client epoch
    (zero-copy publish, DESIGN.md §4.4) and :meth:`snapshot` returns
    the cache's memoized slice of it.  Tear-freedom carries over: a
    slice tuple is drawn from ONE atomically-swapped stacked tuple.

    Writer discipline (both modes): one writer per slot — the shard's
    mapper thread (or the ``pump()`` caller in sync mode), enforced by
    the mapper's per-shard replay mutex
    (``ShortcutMapper._replay_mutex``).  That single-writer rule + the
    atomic swap is exactly the ``ShortcutEH._view`` protocol, lifted to
    N shards; no cross-shard lock exists and none is needed.
    """

    def __init__(self, num_shards: int, *, cache=None,
                 family: str = "kv_view"):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self._n = num_shards
        self._cache = cache
        self._family = family
        if cache is None:
            self._views: List[Optional[tuple]] = [None] * num_shards
            # publish epochs for the device-resident operand cache
            # (runtime/operand_cache.py): bumped AFTER the tuple store,
            # so a reader that reads the epoch first and snapshots
            # second can at worst record a newer tuple under an older
            # epoch — a redundant refresh next get(), never stale
            self._epochs: List[int] = [0] * num_shards
        elif cache.num_shards != num_shards:
            raise ValueError(f"cache has {cache.num_shards} shards, "
                             f"registry asked for {num_shards}")

    def __len__(self) -> int:
        return self._n

    def publish(self, shard: int, arrays: Iterable, *,
                epoch: Optional[int] = None) -> None:
        """Publish shard ``shard``'s view tuple.

        Standalone: atomic tuple swap, then bump the internal epoch
        (writer order matters, see ``_epochs``); ``epoch`` is ignored.
        Cache-backed: one donated ``dynamic_update_slice`` into the
        stacked family at the client ``epoch`` (required — replays pass
        their mapper's ``next_view_epoch``)."""
        if self._cache is not None:
            if epoch is None:
                raise ValueError("cache-backed registry publications "
                                 "must carry the client epoch")
            self._cache.publish(self._family, shard, tuple(arrays),
                                epoch=epoch)
            return
        self._views[shard] = tuple(arrays)
        self._epochs[shard] += 1

    def epoch(self, shard: int) -> int:
        """Shard's publish epoch; read BEFORE :meth:`snapshot`."""
        return self.epochs()[shard]

    def epochs(self) -> List[int]:
        """All shards' publish epochs (copied; read before snapshots)."""
        if self._cache is not None:
            eps = self._cache.epochs(self._family)
            return [0] * self._n if eps is None else eps
        return list(self._epochs)

    def snapshot(self, shard: int) -> Optional[tuple]:
        """One consistent view tuple (or None) — read the slot ONCE and
        index the result; never re-read per array.  Cache-backed: the
        memoized slice of the stack (zero device work in steady state)."""
        if self._cache is not None:
            return self._cache.slice_of(self._family, shard)
        return self._views[shard]

    def snapshot_all(self) -> list:
        """Per-shard snapshots, each internally consistent (the list is
        copied so concurrent publications don't mutate it underfoot)."""
        return [self.snapshot(s) for s in range(self._n)]

    def arrays(self, shard: int) -> tuple:
        """Population target for the runtime's ``view_arrays`` hook:
        the shard's current arrays, or () before first publication.
        Cache-backed: the stacked family itself — it IS the published
        object the reader will be handed."""
        if self._cache is not None:
            return self._cache.handle(self._family) or ()
        v = self._views[shard]
        return () if v is None else v


class MapperGroup:
    """N independent shortcut mappers + a router, presented as one unit.

    Parameters
    ----------
    mappers:
        the member :class:`ShortcutMapper` instances, one per shard, in
        shard order.  The group takes ownership (``close()`` closes all).
    router:
        ``f(key) -> shard index`` for single keys.  Optional — clients
        that bucketize batches themselves (Sharded-EH hashes whole numpy
        arrays at once) may never call it; :meth:`route` raises if it is
        needed but absent.
    views:
        optional :class:`ShardViewRegistry` the members' replay
        callables publish into; exposing it here lets group consumers
        (serving loops, benchmarks) snapshot per-shard views without
        reaching into the client object.
    """

    def __init__(self, mappers: Sequence[ShortcutMapper], *,
                 router: Optional[Callable[[Hashable], int]] = None,
                 views: Optional[ShardViewRegistry] = None):
        if not mappers:
            raise ValueError("MapperGroup needs at least one mapper")
        if views is not None and len(views) != len(mappers):
            raise ValueError(
                f"view registry has {len(views)} slots for "
                f"{len(mappers)} mappers")
        self.mappers = list(mappers)
        self._router = router
        self.views = views
        # batch-level decisions spanning shards (shard=None in
        # count_route) land here, not on an arbitrary member
        self._routed_shortcut_group = 0
        self._routed_fallback_group = 0

    # -- container protocol --------------------------------------------------

    def __len__(self) -> int:
        return len(self.mappers)

    def __getitem__(self, shard: int) -> ShortcutMapper:
        return self.mappers[shard]

    def __iter__(self):
        return iter(self.mappers)

    # -- routing -------------------------------------------------------------

    def route(self, key: Hashable) -> int:
        """Shard index owning ``key`` (via the client's router)."""
        if self._router is None:
            raise ValueError("MapperGroup was built without a router")
        shard = int(self._router(key))
        if not 0 <= shard < len(self.mappers):
            raise IndexError(f"router sent key {key!r} to shard {shard} "
                             f"of {len(self.mappers)}")
        return shard

    def mapper_for(self, key: Hashable) -> ShortcutMapper:
        return self.mappers[self.route(key)]

    # -- aggregated bookkeeping ----------------------------------------------

    @property
    def stats(self) -> MaintenanceStats:
        """Sum of all members' stats (a fresh snapshot object; mutate the
        per-shard ``group[i].stats`` instances, never this one)."""
        agg = MaintenanceStats()
        for m in self.mappers:
            for f in fields(MaintenanceStats):
                setattr(agg, f.name,
                        getattr(agg, f.name) + getattr(m.stats, f.name))
        return agg

    def per_shard_stats(self) -> list:
        return [m.stats for m in self.mappers]

    @property
    def routed_shortcut(self) -> int:
        return self._routed_shortcut_group + \
            sum(m.routed_shortcut for m in self.mappers)

    @property
    def routed_fallback(self) -> int:
        return self._routed_fallback_group + \
            sum(m.routed_fallback for m in self.mappers)

    def count_route(self, used_shortcut: bool,
                    shard: Optional[int] = None) -> None:
        """Count one routed batch: attributed to ``shard`` when the
        decision belongs to a single shard, otherwise (``shard=None``)
        to the group-level counter.  Batch-level decisions are one event
        — never one per touched shard, and never silently credited to
        shard 0 (that skewed per-shard stats for multi-shard batches)."""
        if shard is None:
            if used_shortcut:
                self._routed_shortcut_group += 1
            else:
                self._routed_fallback_group += 1
        else:
            self.mappers[shard].count_route(used_shortcut)

    # -- sharded version gate ------------------------------------------------

    def in_sync(self, keys_by_shard: Optional[KeysByShard] = None) -> bool:
        """True when every involved shard's views are caught up.

        ``keys_by_shard=None`` checks all keys of all shards; a dict
        restricts the gate to the listed shards (and, per shard, to the
        listed keys) — the sharded read set."""
        if keys_by_shard is None:
            return all(m.in_sync() for m in self.mappers)
        return all(self.mappers[s].in_sync(keys)
                   for s, keys in keys_by_shard.items())

    def gate(self, metric: float,
             keys_by_shard: Optional[KeysByShard] = None) -> bool:
        """Version gate across the involved shards AND every involved
        shard's routing policy accepting ``metric``.  Policies are
        per-shard (independent thresholds / hysteresis state); a batch
        routes the shortcut only when all of them agree.  Distinct
        policy *objects* each decide exactly once, without
        short-circuiting — a policy shared across shards (one object,
        many members) must see one state transition per gate, not one
        per shard it happens to back."""
        shards = (range(len(self.mappers)) if keys_by_shard is None
                  else sorted(keys_by_shard))
        if not self.in_sync(keys_by_shard):
            return False
        policies, seen = [], set()
        for s in shards:
            p = self.mappers[s].routing
            if id(p) not in seen:
                seen.add(id(p))
                policies.append(p)
        decisions = [bool(p.decide(metric)) for p in policies]
        return all(decisions)

    # -- group-wide maintenance ----------------------------------------------

    def pump(self, max_requests: int = 1 << 30) -> int:
        """Synchronously drain every shard's queue (mapper surrogate)."""
        return sum(m.pump(max_requests) for m in self.mappers)

    def wait_in_sync(self, keys_by_shard: Optional[KeysByShard] = None,
                     timeout: float = 30.0) -> bool:
        """Block until the involved shards caught up; one shared deadline
        across the group (not ``timeout`` per shard)."""
        deadline = time.monotonic() + timeout
        shards = (range(len(self.mappers)) if keys_by_shard is None
                  else sorted(keys_by_shard))
        ok = True
        for s in shards:
            keys = None if keys_by_shard is None else keys_by_shard[s]
            left = deadline - time.monotonic()
            ok &= self.mappers[s].wait_in_sync(keys, max(0.0, left))
        return ok

    def close(self) -> None:
        """Close every member, then re-raise the first member's
        mapper-thread failure (a failing member must not keep the others
        from closing)."""
        failures = []
        for m in self.mappers:
            try:
                m.close()
            except RuntimeError as e:
                failures.append(e)
        if failures:
            raise failures[0]

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
