"""Publish-owned stacked lookup operands: pay the patch at publish time.

The batched cross-shard kernels (``kernels/eh_lookup.sharded_*``, the KV
manager's cross-shard ``get_context``) consume the per-shard structures
stacked on a leading shard axis: ``(N, ...)`` directories, bucket pools,
composed views.  The first cache generation (PR 4) kept those stacks as
a *secondary* copy: replays published per-shard arrays, and the first
lookup after a publish patched the dirty slice with a
``dynamic_update_slice`` — lazy refresh on the read path, every cached
family resident twice (per-shard originals plus the stack).

This module inverts the ownership, which is the paper's §4 move applied
one level up: pay the mapping cost when the mapping *changes* (page
table rewiring at create/split time) so the common-case read does no
fix-up work at all (cf. Utopia's restrictive mappings, PAPERS.md).

  * The stacked ``(N, ...)`` device buffers are the **primary** storage.
    Writers — mapper replay threads, the KV view registry — call
    :meth:`StackedOperandCache.publish` from the *mapper thread* at
    publish time, **before** ``sc_version`` is published: one
    ``dynamic_update_slice`` per part, donated in place on accelerator
    backends.
  * The lookup path (:meth:`get` with no ``parts``) is an epoch
    comparison plus a handle return — zero device work in steady state.
  * Per-shard reads (``view_snapshot``, a replay's read-modify-write)
    go through :meth:`slice_of`, a memoized slice of the stack — the
    per-shard original arrays of cached families are deleted, not
    duplicated.  The memo is identity-keyed on the stacked tuple, so it
    costs one slice copy per publish, not per read.
  * A part that outgrows the stacked extent (directory doubling, view
    growth past the common capacity) triggers a **background re-stack**
    on the publishing thread: the old stack is embedded into a freshly
    zeroed larger stack with one ``dynamic_update_slice`` and swapped
    atomically — readers holding the old handle stay valid and are
    never blocked (the shard-level analogue of a directory doubling).

Epoch protocol (client-domain epochs): every entry records, per shard,
the highest *client* epoch published into it (``ShortcutMapper``'s
``view_epoch`` / ``trad_epoch`` domains).  A reader passes the epochs it
read **before** the call; the entry is clean for shard ``s`` when
``entry.epochs[s] >= reader_epochs[s]``.  Races are tolerated in exactly
one direction: a publish landing between the reader's epoch read and its
``get`` makes the entry *newer* than requested — served as a hit, which
is correct because publication order (arrays first, then epoch; both
before ``sc_version``) guarantees any gate-certified view is already in
the stack.  A push-owned family that *lags* the reader's epochs is a
writer-order violation and raises rather than serving stale data.

Pull-mode families remain supported for operands whose authoritative
state lives client-side (the "eh_trad" bucket arrays): ``get`` with a
``parts`` callable patches dirty shards on the read path (counted as
``lookup_refreshes``), and the client may keep the family warm
afterwards with :meth:`publish_if_present` at mutation time.

Donation/aliasing rules (DESIGN.md §4.3/§4.4): with ``donate=True`` the
publish donates the previous stacked buffer to the update-slice call on
accelerator backends, so XLA patches it in place instead of allocating a
sibling copy.  Donation deletes the old buffer, which makes every
returned stack a **loan** whose lifetime ends at the next publish — only
safe when a single thread drives lookups.  It is therefore opt-in; the
default never donates and is safe for concurrent readers (old loans and
memoized slices stay valid until released).  CPU donation would be a
warn-and-copy no-op either way, so the interpret-mode tests cannot
exercise the donating path.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["StackedOperandCache", "OperandCacheStats"]


def _backend_can_donate() -> bool:
    """XLA implements input/output aliasing on the TPU only here; CPU
    donation is a warn-and-copy no-op."""
    return jax.default_backend() == "tpu"


@jax.jit
def _refresh_slice(stacked: jax.Array, part: jax.Array,
                   shard: jax.Array) -> jax.Array:
    """stacked[shard] = part, via dynamic_update_slice (shard is traced,
    so N shards share one compiled variant per shape/dtype)."""
    start = (shard.astype(jnp.int32),) + (jnp.int32(0),) * part.ndim
    return jax.lax.dynamic_update_slice(stacked, part[None], start)


# donating twin: same computation, previous stack buffer reused in place
_refresh_slice_donated = jax.jit(
    lambda stacked, part, shard: _refresh_slice.__wrapped__(
        stacked, part, shard),
    donate_argnums=(0,))


@jax.jit
def _embed_stack(dst: jax.Array, src: jax.Array) -> jax.Array:
    """Place the whole old stack at the origin of a larger zeroed stack
    (the re-stack-on-growth path; one update-slice, shapes are static)."""
    return jax.lax.dynamic_update_slice(
        dst, src, (jnp.int32(0),) * src.ndim)


@dataclass
class OperandCacheStats:
    hits: int = 0                # get() served from the stack (no device work)
    publish_refreshes: int = 0   # slices patched at publish time (writer side)
    lookup_refreshes: int = 0    # slices patched on the lookup path (pull mode)
    rebuilds: int = 0            # full (re)stacks: first build / shape growth
    resident: Dict[str, int] = field(default_factory=dict)  # bytes per family
    publish_bytes: int = 0       # bytes of the stacks publish() wrote

    @property
    def slice_refreshes(self) -> int:
        """Total slice patches, either side (back-compat aggregate)."""
        return self.publish_refreshes + self.lookup_refreshes

    def snapshot(self) -> "OperandCacheStats":
        return OperandCacheStats(self.hits, self.publish_refreshes,
                                 self.lookup_refreshes, self.rebuilds,
                                 dict(self.resident), self.publish_bytes)


@dataclass
class _Entry:
    epochs: List[int]                    # per-shard client epoch of each slice
    arrays: Tuple[jax.Array, ...]        # the stacked (N, ...) tensors
    part_shapes: Tuple[tuple, ...]       # per-shard extents (without N axis)
    part_dtypes: Tuple = field(default_factory=tuple)
    published: List[bool] = field(default_factory=list)  # shard has real data


class StackedOperandCache:
    """Primary storage of stacked ``(N, ...)`` lookup operands.

    Push-owned families ("eh_view", "kv_view"): writers call
    :meth:`publish` per shard from the mapper thread before the shard's
    ``sc_version`` moves; the lookup path calls ``get(family, epochs)``
    with no parts and receives the stacked handle after a pure epoch
    check.  Pull-mode families ("eh_trad"): ``get(family, epochs,
    parts)`` patches dirty shards on the read path, exactly the PR 4
    contract, and mutators may keep the stack warm with
    :meth:`publish_if_present`.

    Thread safety: one lock serializes all mutation (publish, pull
    refresh, re-stack); the push-mode ``get`` and :meth:`slice_of` are
    lock-free — they read the entry's epoch list before its arrays
    tuple, the writer stores arrays before epochs, and both stores are
    GIL-atomic, so a racing reader can only observe newer-arrays-than-
    epoch (a hit it was entitled to), never the reverse.

    ``donate=True`` opts into in-place publishes on accelerator backends
    (see the module docstring's aliasing rules): single-reader drivers
    only — a donating publish deletes the buffers a concurrent reader
    may still be about to dispatch with.
    """

    def __init__(self, num_shards: int, *, donate: bool = False):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self.num_shards = num_shards
        self.donate = bool(donate)
        self.stats = OperandCacheStats()
        self._entries: Dict[str, _Entry] = {}
        # identity-keyed per-(family, shard) slice memo: one slice copy
        # per publish, not per snapshot read
        self._slices: Dict[tuple, tuple] = {}
        self._lock = threading.Lock()

    # -- the lookup path -----------------------------------------------------

    def get(self, family: str, epochs: Sequence[int],
            parts: Optional[Callable[[int], Tuple[jax.Array, ...]]] = None
            ) -> Tuple[jax.Array, ...]:
        """Stacked operand tuple for ``family``, current to ``epochs``.

        Without ``parts`` (push-owned family) this is the zero-copy hot
        path: epoch comparison + handle return, lock-free; a lagging
        entry is a writer-order violation and raises.  With ``parts``
        (pull mode) dirty shards are patched here and counted as
        ``lookup_refreshes``."""
        epochs = [int(e) for e in epochs]
        if len(epochs) != self.num_shards:
            raise ValueError(f"{len(epochs)} epochs for "
                             f"{self.num_shards} shards")
        ent = self._entries.get(family)
        if ent is not None:
            eps = ent.epochs              # epochs BEFORE arrays (see class doc)
            if all(eps[s] >= epochs[s] for s in range(self.num_shards)):
                self.stats.hits += 1
                return ent.arrays
        if parts is None:
            lag = ([] if ent is None else
                   [s for s in range(self.num_shards)
                    if ent.epochs[s] < epochs[s]])
            raise RuntimeError(
                f"operand family {family!r} is publish-owned but "
                f"{'was never published' if ent is None else f'lags the reader on shards {lag}'}"
                f": publish() must run on the mapper thread before "
                f"sc_version is published (writer-order violation)")
        with self._lock:
            ent = self._entries.get(family)
            if ent is None:
                return self._rebuild(family, epochs, parts)
            dirty = [s for s in range(self.num_shards)
                     if epochs[s] > ent.epochs[s]]
            if not dirty:
                self.stats.hits += 1
                return ent.arrays
            arrays = list(ent.arrays)
            new_epochs = list(ent.epochs)
            refresh = self._refresh_fn()
            try:
                for s in dirty:
                    p = tuple(parts(s))
                    if (tuple(a.shape for a in p) != ent.part_shapes
                            or tuple(a.dtype for a in p)
                            != ent.part_dtypes):
                        # shape changed (e.g. directory growth): restack
                        return self._rebuild(family, epochs, parts,
                                             prebuilt={s: p})
                    sidx = jnp.int32(s)
                    for j, a in enumerate(p):
                        arrays[j] = refresh(arrays[j], a, sidx)
                    new_epochs[s] = max(new_epochs[s], epochs[s])
                    self.stats.lookup_refreshes += 1
            except BaseException:
                if refresh is _refresh_slice_donated:
                    # the old buffers may already be donated away; drop
                    # the entry so the next get rebuilds from scratch
                    self._drop(family)
                raise
            # commit arrays before epochs, only once every dirty slice
            # refreshed — a parts() exception mid-loop must not leave
            # the entry claiming freshness over the old arrays
            for s in dirty:
                ent.published[s] = True
            ent.arrays = tuple(arrays)
            ent.epochs = new_epochs
            return ent.arrays

    # -- the publish path (writer side, mapper thread) -----------------------

    def publish(self, family: str, shard: int,
                parts: Sequence[jax.Array], *, epoch: int) -> None:
        """Write one shard's operand tuple straight into the stack.

        Called from the shard's mapper thread (or the ``pump()`` caller)
        **before** the shard's ``sc_version`` is published, carrying the
        client epoch the publication corresponds to (the mapper's
        ``next_view_epoch`` during a replay).  Creates the family on
        first publish (other shards start zeroed and unpublished); grows
        the stacked extent in place when the part outgrew it; pads a
        smaller part up to the extent (rows past the shard's own logical
        size are never indexed — the kernels slot by per-shard
        depth/log2 operands)."""
        parts = tuple(parts)
        if not 0 <= shard < self.num_shards:
            raise ValueError(f"shard {shard} of {self.num_shards}")
        if not parts:
            raise ValueError(f"family {family!r}: empty part tuple")
        with self._lock:
            ent = self._entries.get(family)
            if ent is None:
                ent = self._create_zeroed(family, parts)
            if len(parts) != len(ent.arrays):
                raise ValueError(
                    f"family {family!r}: {len(parts)} parts for a "
                    f"{len(ent.arrays)}-part family")
            if tuple(a.dtype for a in parts) != ent.part_dtypes:
                raise ValueError(f"family {family!r}: part dtypes changed")
            shapes = tuple(tuple(a.shape) for a in parts)
            if any(len(s) != len(e)
                   for s, e in zip(shapes, ent.part_shapes)):
                raise ValueError(f"family {family!r}: part ranks changed")
            if any(d > e for sh, ext in zip(shapes, ent.part_shapes)
                   for d, e in zip(sh, ext)):
                self._restack_grow(family, ent, shapes)
            parts = tuple(self._pad_to_extent(a, ext)
                          for a, ext in zip(parts, ent.part_shapes))
            refresh = self._refresh_fn()
            arrays = list(ent.arrays)
            sidx = jnp.int32(shard)
            try:
                for j, a in enumerate(parts):
                    arrays[j] = refresh(arrays[j], a, sidx)
            except BaseException:
                if refresh is _refresh_slice_donated:
                    self._drop(family)
                raise
            ent.arrays = tuple(arrays)     # arrays first, then epoch
            ent.published[shard] = True
            ent.epochs[shard] = max(ent.epochs[shard], int(epoch))
            self.stats.publish_refreshes += 1
            # without donation each refresh writes a whole new stack
            self.stats.publish_bytes += sum(int(a.nbytes) for a in arrays)

    def publish_if_present(self, family: str, shard: int,
                           parts: Callable[[], Tuple[jax.Array, ...]], *,
                           epoch: int) -> None:
        """Keep a pull-built family warm from the mutation path: publish
        only when the family already exists (a lookup built it), so a
        write-heavy phase that never routes through the family pays
        nothing for it."""
        if family in self._entries:
            self.publish(family, shard, tuple(parts()), epoch=epoch)

    def touch(self, family: str, shard: int, *, epoch: int) -> None:
        """Advance a shard's epoch without new data — a replay whose
        merged work was empty (nothing stale) still owes the reader an
        epoch so the entry never lags a gate-certified version."""
        with self._lock:
            ent = self._entries.get(family)
            if ent is not None:
                ent.epochs[shard] = max(ent.epochs[shard], int(epoch))

    def seed(self, family: str, per_shard_parts: Sequence[Sequence], *,
             epoch: int = 0) -> None:
        """Build a family in one shot from uniform per-shard part tuples
        (init path — e.g. the KV manager's zeroed views); every shard is
        marked published at ``epoch``."""
        per = [tuple(p) for p in per_shard_parts]
        if len(per) != self.num_shards:
            raise ValueError(f"{len(per)} part tuples for "
                             f"{self.num_shards} shards")
        with self._lock:
            widths = {len(p) for p in per}
            if len(widths) != 1:
                raise ValueError(f"family {family!r}: ragged part tuples "
                                 f"{sorted(widths)}")
            stacked = tuple(jnp.stack([p[j] for p in per])
                            for j in range(widths.pop()))
            self._install(family, _Entry(
                epochs=[int(epoch)] * self.num_shards, arrays=stacked,
                part_shapes=tuple(tuple(a.shape) for a in per[0]),
                part_dtypes=tuple(a.dtype for a in per[0]),
                published=[True] * self.num_shards))

    # -- per-shard views of the stack ---------------------------------------

    def handle(self, family: str) -> Optional[Tuple[jax.Array, ...]]:
        """The stacked tuple itself (or None) — no epoch check; the
        population hook (``view_arrays``) and tests use this."""
        ent = self._entries.get(family)
        return None if ent is None else ent.arrays

    def slice_of(self, family: str, shard: int
                 ) -> Optional[Tuple[jax.Array, ...]]:
        """One shard's operand tuple as slices of the stack — the only
        per-shard materialization left (``view_snapshot``, replay
        read-modify-write).  Memoized on the stacked tuple's identity:
        steady-state snapshots return the cached slices with zero device
        work; the copy is paid once per publish.  Internally consistent
        by construction — every array comes from ONE stacked tuple."""
        ent = self._entries.get(family)
        if ent is None:
            return None
        arrays = ent.arrays                      # single read: swap is atomic
        key = (family, shard)
        memo = self._slices.get(key)
        if memo is not None and memo[0] is arrays:
            return memo[1]
        sl = tuple(a[shard] for a in arrays)
        self._slices[key] = (arrays, sl)
        return sl

    def published(self, family: str) -> Optional[List[bool]]:
        """Per-shard "holds real data" flags (False = still the zeroed
        placeholder); None before the family exists."""
        ent = self._entries.get(family)
        return None if ent is None else list(ent.published)

    # -- bookkeeping ---------------------------------------------------------

    def epochs(self, family: str) -> Optional[List[int]]:
        """The per-shard client epochs the cached slices are current to
        (test / introspection hook); None before the family exists."""
        ent = self._entries.get(family)
        return None if ent is None else list(ent.epochs)

    def resident_bytes(self) -> Dict[str, int]:
        """Device bytes resident per family (the stacks are the primary
        and only persistent storage; memoized slices are transient)."""
        return dict(self.stats.resident)

    def invalidate(self, family: Optional[str] = None) -> None:
        """Drop one family (or all).  A push-owned family loses its
        derived data: shards read as unpublished (clients demote to
        their traditional/paged route) until their next create replay
        republishes; a pull family simply rebuilds on the next get."""
        with self._lock:
            for fam in ([family] if family is not None
                        else list(self._entries)):
                self._drop(fam)

    def __contains__(self, family: str) -> bool:
        return family in self._entries

    # -- internals (call with self._lock held) -------------------------------

    def _refresh_fn(self):
        return (_refresh_slice_donated
                if self.donate and _backend_can_donate()
                else _refresh_slice)

    def _install(self, family: str, ent: _Entry) -> None:
        self._entries[family] = ent
        self.stats.rebuilds += 1
        self.stats.resident[family] = sum(int(a.nbytes) for a in ent.arrays)

    def _drop(self, family: str) -> None:
        self._entries.pop(family, None)
        self.stats.resident.pop(family, None)
        for s in range(self.num_shards):
            self._slices.pop((family, s), None)

    def _create_zeroed(self, family: str, parts: Tuple) -> _Entry:
        stacked = tuple(
            jnp.zeros((self.num_shards,) + tuple(a.shape), a.dtype)
            for a in parts)
        ent = _Entry(
            epochs=[0] * self.num_shards, arrays=stacked,
            part_shapes=tuple(tuple(a.shape) for a in parts),
            part_dtypes=tuple(a.dtype for a in parts),
            published=[False] * self.num_shards)
        self._install(family, ent)
        return ent

    def _restack_grow(self, family: str, ent: _Entry,
                      shapes: Tuple[tuple, ...]) -> None:
        """Background re-stack on growth: embed the old stack into a
        larger zeroed one (elementwise-max extents) and swap atomically.
        Runs on the publishing thread; readers holding the old handle
        are never blocked and never see a torn stack."""
        new_ext = tuple(tuple(max(d, e) for d, e in zip(sh, ext))
                        for sh, ext in zip(shapes, ent.part_shapes))
        grown = []
        for old, ext in zip(ent.arrays, new_ext):
            if tuple(old.shape[1:]) == ext:
                grown.append(old)
                continue
            dst = jnp.zeros((self.num_shards,) + ext, old.dtype)
            grown.append(_embed_stack(dst, old))
        ent.arrays = tuple(grown)
        ent.part_shapes = new_ext
        self.stats.rebuilds += 1
        self.stats.resident[family] = sum(int(a.nbytes) for a in grown)

    @staticmethod
    def _pad_to_extent(a: jax.Array, ext: tuple) -> jax.Array:
        if tuple(a.shape) == tuple(ext):
            return a
        return jnp.pad(a, [(0, e - d) for d, e in zip(a.shape, ext)])

    def _rebuild(self, family: str, epochs: List[int],
                 parts: Callable[[int], Tuple[jax.Array, ...]],
                 prebuilt: Optional[dict] = None) -> Tuple[jax.Array, ...]:
        """Pull-mode full (re)stack: first build of a pull family, or a
        shape change discovered on the read path."""
        prebuilt = prebuilt or {}
        per_shard = [tuple(prebuilt.get(s) or parts(s))
                     for s in range(self.num_shards)]
        width = {len(p) for p in per_shard}
        if len(width) != 1:
            raise ValueError(f"family {family!r}: ragged part tuples "
                             f"{sorted(width)}")
        stacked = tuple(jnp.stack([p[j] for p in per_shard])
                        for j in range(width.pop()))
        self._install(family, _Entry(
            epochs=list(epochs), arrays=stacked,
            part_shapes=tuple(tuple(a.shape) for a in per_shard[0]),
            part_dtypes=tuple(a.dtype for a in per_shard[0]),
            published=[True] * self.num_shards))
        return stacked
