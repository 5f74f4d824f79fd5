"""Shortcut KV view: the paper's technique applied to the serving layer.

The paged cache reads through *two* indirections (block table, then block
gather).  The shortcut view pre-composes that mapping into a contiguous
per-sequence layout — ``view[l, s, t] = pool[l, table[s, t // bs], t % bs]``
— so a decode step reads it with pure address arithmetic (a dynamic-slice),
zero data-dependent indirections.  This is ``rewiring.compose`` at the KV
granularity.

Exactly like Shortcut-EH (§4.1) — and through the very same runtime
(``runtime/mapper.ShortcutMapper``, DESIGN.md §4): the paged cache stays
authoritative and synchronous; the view is replayed asynchronously from a
FIFO of *update* (append a token row) and *create* (re-linearize a
sequence) requests, is eagerly populated before publication, version-gates
every read (one version per sequence — a sequence is our directory unit),
and a fragmentation statistic (the fan-in analogue) decides routing
(:class:`~repro.runtime.mapper.FragmentationRouting`).

**Sharded mode** (``num_shards > 1``, DESIGN.md §4.2): sequences partition
across a :class:`~repro.runtime.shard_group.MapperGroup` by
``seq_id % N``, and — unlike the first sharded iteration, which kept one
whole-batch view pair behind a global view lock — the view state is
**per shard** too: shard ``s`` owns the rows of its sequences
(shard-local row ``seq_id // N``).  The PRIMARY storage is one stacked
``(N, L, seqs_per_shard, S_cap, KV, hd)`` k/v pair held by a
:class:`~repro.runtime.operand_cache.StackedOperandCache` (family
"kv_view", DESIGN.md §4.4); the
:class:`~repro.runtime.shard_group.ShardViewRegistry` is a per-shard
facade of it.  A replay thread reads its shard's memoized slice of the
stack, chains the functional updates, and publishes ONE slice write back
into the stack — at the mapper's ``next_view_epoch``, *before*
``sc_version`` moves — so the replay path acquires no cross-shard lock
(there is no view lock at all), and a reader's snapshot is drawn from
one atomically-swapped stacked tuple (it can never pair a ``view_k``
from one publication with the ``view_v`` of another).  Reads
(``get_context`` over any batch) take the stack by handle after a pure
epoch check — zero refresh work on the read path in steady state — and
gather rows with one fused two-axis gather in input order.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvcache import paged_cache as pc
from repro.runtime.mapper import FragmentationRouting, ShortcutMapper
from repro.runtime.operand_cache import StackedOperandCache
from repro.runtime.shard_group import MapperGroup, ShardViewRegistry


# -- functional core -----------------------------------------------------------

@jax.jit
def compose_seq(cache: pc.PagedKVCache, view_k: jax.Array, view_v: jax.Array,
                seq_id: jax.Array, row: jax.Array):
    """Create-request replay: linearize one sequence into its shard's view.

    view_k/view_v: (L, rows_per_shard, S_cap, KV, hd); ``seq_id`` indexes
    the authoritative cache, ``row`` the shard-local view row owning it
    (``seq_id // num_shards``; with one shard, ``row == seq_id``).

    Positions at or past the sequence's current length are written as
    **zeros**, not whatever the pool holds there.  Unset block-table
    entries read (via the ``maximum(…, 0)`` guard) physical block 0, and
    the tail of the last partial block carries stale rows from whatever
    sequence last recycled those blocks — both are functions of *when*
    the replay ran, so leaving them in the view made two managers
    replaying the same schedule at different times publish bit-different
    rows past ``seq_len`` (the ``test_randomized_schedule_parity``
    flake).  Masking pins every position ≥ ``seq_len`` to zero, making
    the composed row a pure function of the sequence's content."""
    table = jnp.maximum(cache.block_tables[seq_id], 0)    # (MB,)
    L = cache.k_pool.shape[0]
    bs = cache.block_size
    MB = table.shape[0]
    kv_shape = cache.k_pool.shape[3:]
    k_lin = cache.k_pool[:, table].reshape((L, MB * bs) + kv_shape)
    v_lin = cache.v_pool[:, table].reshape((L, MB * bs) + kv_shape)
    cap = view_k.shape[2]
    live = (jnp.arange(cap) < cache.seq_lens[seq_id])[:, None, None]
    k_row = jnp.where(live, k_lin[:, :cap], 0)
    v_row = jnp.where(live, v_lin[:, :cap], 0)
    return (view_k.at[:, row, :].set(k_row),
            view_v.at[:, row, :].set(v_row))


@jax.jit
def append_to_view(view_k: jax.Array, view_v: jax.Array, rows: jax.Array,
                   positions: jax.Array, new_k: jax.Array,
                   new_v: jax.Array):
    """Update-request replay: write one token row per sequence
    (the per-slot ``mmap`` analogue) at the given shard-local rows.
    new_k/new_v: (L, B, KV, hd)."""
    return (view_k.at[:, rows, positions].set(new_k),
            view_v.at[:, rows, positions].set(new_v))


@jax.jit
def slice_context(view_k: jax.Array, view_v: jax.Array, rows: jax.Array):
    """The shortcut access path: a gather on the *row* axis only —
    token positions are pure address arithmetic (contiguous stream).
    Returns (L, B, KV, S, hd) (attention-native layout)."""
    return (view_k[:, rows].transpose(0, 1, 3, 2, 4),
            view_v[:, rows].transpose(0, 1, 3, 2, 4))


@jax.jit
def stacked_context(stack_k: jax.Array, stack_v: jax.Array,
                    sid: jax.Array, rows: jax.Array):
    """:func:`slice_context` lifted to the stacked primary
    ``(N, L, rows, S_cap, KV, hd)``: one fused two-axis gather in input
    order, serving single- and cross-shard batches identically.
    Returns (L, B, KV, S, hd)."""
    k = stack_k[sid, :, rows]               # (B, L, S_cap, KV, hd)
    v = stack_v[sid, :, rows]
    return (jnp.transpose(k, (1, 0, 3, 2, 4)),
            jnp.transpose(v, (1, 0, 3, 2, 4)))


# -- host orchestration ----------------------------------------------------------

class ShortcutKVManager:
    """Maintains the shortcut view alongside an authoritative paged cache —
    a thin client of the (sharded) shortcut-maintenance runtime.

    A read routes through the shortcut only when every sequence in the
    batch is in sync *and* the batch fragmentation exceeds
    ``frag_threshold`` (below it, the paged gather streams
    nearly-contiguous blocks anyway, and maintenance would be pure
    overhead — the TLB-thrashing lesson of §3.2 mapped to DMA terms).

    ``num_shards`` partitions sequences across independent mappers AND
    independent view tensors (``seq_id % num_shards`` router, shard-local
    view row ``seq_id // num_shards``); the default 1 is exactly the
    previous single-mapper behaviour.  A custom ``routing`` policy is
    shared across shards — pass ``None`` for independent per-shard
    :class:`FragmentationRouting` instances.
    """

    def __init__(self, cache: pc.PagedKVCache, seq_capacity: int, *,
                 frag_threshold: float = 0.25, poll_interval: float = 0.025,
                 async_mapper: bool = False, routing=None,
                 num_shards: int = 1):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        L, _, bs, KV, hd = cache.k_pool.shape
        max_seqs = cache.block_tables.shape[0]
        self.cache = cache
        self.num_shards = num_shards
        self.seqs_per_shard = -(-max_seqs // num_shards)
        # The stacked (N, L, rows, S_cap, KV, hd) view pair is the
        # PRIMARY storage (family "kv_view", DESIGN.md §4.4): replay
        # threads publish their shard's slice straight into it at
        # publish time, readers take the whole stack (cross-shard
        # get_context) or a memoized slice of it (per-shard snapshot /
        # replay read-modify-write) — no per-shard duplicates exist.
        self.operands = StackedOperandCache(num_shards)
        self.views = ShardViewRegistry(self.operands, family="kv_view")
        zk = jnp.zeros((L, self.seqs_per_shard, seq_capacity, KV, hd),
                       cache.k_pool.dtype)
        zv = jnp.zeros_like(zk)
        # seed every shard published-at-zero: the all-zero view is a
        # valid (empty) publication, so first replays take the update
        # path exactly as before
        self.operands.seed("kv_view", [(zk, zv)] * num_shards)
        self._view_shape = tuple(zk.shape)
        self.group = MapperGroup(
            [ShortcutMapper(
                replay_create=lambda snap, reqs, shard=i:
                    self._replay_create(snap, reqs, shard),
                replay_update=lambda snap, reqs, shard=i:
                    self._replay_update(snap, reqs, shard),
                snapshot=lambda: self.cache,
                view_arrays=lambda shard=i: self.views.arrays(shard),
                routing=routing or FragmentationRouting(float(frag_threshold)),
                poll_interval=poll_interval, async_mapper=async_mapper,
                name=f"kv-mapper-{i}")
             for i in range(num_shards)],
            router=lambda seq_id: int(seq_id) % num_shards,
            views=self.views)

    # -- delegated bookkeeping (kept for API compatibility) ------------------

    @property
    def mapper(self) -> ShortcutMapper:
        """The first (with ``num_shards=1``: the only) mapper — the
        pre-sharding single-mapper API surface."""
        return self.group[0]

    @property
    def routed_shortcut(self) -> int:
        return self.group.routed_shortcut

    @property
    def routed_paged(self) -> int:
        return self.group.routed_fallback

    @property
    def frag_threshold(self):
        return self.group[0].threshold

    @frag_threshold.setter
    def frag_threshold(self, value: float) -> None:
        for m in self.group:
            m.threshold = value

    @property
    def stats(self):
        return self.group.stats

    # -- sharding helpers ----------------------------------------------------

    def _by_shard(self, seq_ids: np.ndarray) -> dict:
        """{shard: [seq ids]} preserving batch order within each shard."""
        out: dict = {}
        for s in np.asarray(seq_ids).tolist():
            out.setdefault(self.group.route(int(s)), []).append(int(s))
        return out

    @contextlib.contextmanager
    def _shard_locks(self, shards):
        """Hold the involved shards' runtime locks (ascending order — the
        lock hierarchy that makes multi-shard mutations deadlock-free).
        Main-thread (authoritative) mutations only; the replay path never
        enters here."""
        with contextlib.ExitStack() as stack:
            for r in sorted(shards):
                stack.enter_context(self.group[r].lock)
            yield

    # -- main-thread (serving) API -----------------------------------------

    def prefill(self, seq_ids: np.ndarray, k: jax.Array, v: jax.Array):
        """Synchronous paged write + async create request per sequence,
        enqueued on each sequence's owning shard."""
        seq_ids = np.asarray(seq_ids)
        by_shard = self._by_shard(seq_ids)
        with self._shard_locks(by_shard):
            self.cache = pc.write_prefill(
                self.cache, jnp.asarray(seq_ids), k, v)
            # submit under the same locks that assigned the versions:
            # requests then enter each shard's FIFO in version order, so
            # a replayed later version can never publish in_sync while an
            # earlier-version request is still unsubmitted
            for r, keys in by_shard.items():
                self.group[r].submit_create(keys, self.group[r].record(keys),
                                            payload=np.asarray(keys))

    def append(self, seq_ids: np.ndarray, new_k: jax.Array,
               new_v: jax.Array):
        """Synchronous paged append + async view-row update request on
        each sequence's owning shard (payload sliced per shard)."""
        seq_ids = np.asarray(seq_ids)
        # partition through the group router — the one key->shard map
        # every operation shares
        shard_of = np.asarray([self.group.route(int(s)) for s in seq_ids])
        by_shard = {r: [int(s) for s in seq_ids[shard_of == r]]
                    for r in sorted(set(shard_of.tolist()))}
        with self._shard_locks(by_shard):
            # positions must be read under the shard locks, atomically
            # with the authoritative append: a racing append to the same
            # sequence would otherwise hand two update requests the same
            # (stale) position and the view would lose a token row
            positions = np.asarray(self.cache.seq_lens)[seq_ids]
            self.cache = pc.append_tokens(
                self.cache, jnp.asarray(seq_ids), new_k, new_v)
            # submit under the locks (see prefill): version order ==
            # FIFO order per shard
            for r, keys in by_shard.items():
                idx = np.nonzero(shard_of == r)[0]
                self.group[r].submit_update(
                    keys, self.group[r].record(keys),
                    payload=(seq_ids[idx], positions[idx],
                             new_k[:, idx], new_v[:, idx]))

    def release(self, seq_ids: np.ndarray):
        """Synchronous release; the per-sequence views become permanently
        stale until the next prefill recreates them."""
        by_shard = self._by_shard(np.asarray(seq_ids))
        with self._shard_locks(by_shard):
            self.cache = pc.release_seqs(self.cache, jnp.asarray(seq_ids))
            for r, keys in by_shard.items():
                self.group[r].invalidate(keys)

    def in_sync(self, seq_ids: np.ndarray) -> bool:
        return self.group.in_sync(self._by_shard(seq_ids))

    def fragmentation(self, seq_ids: np.ndarray) -> float:
        return float(pc.fragmentation(self.cache, jnp.asarray(seq_ids)))

    def route(self, seq_ids: np.ndarray) -> str:
        """'shortcut' | 'paged' — version gate (across the involved
        shards) + fragmentation cost model."""
        if self.group.gate(self.fragmentation(seq_ids),
                           self._by_shard(seq_ids)):
            return "shortcut"
        return "paged"

    def get_context(self, seq_ids: np.ndarray, route: Optional[str] = None):
        """Materialized (k_ctx, v_ctx) for decode + the route taken.

        The shortcut path reads per-shard view tensors: a batch confined
        to one shard is a single row-gather on that shard's arrays; a
        batch spanning shards gathers from the device-resident stacked
        pair held by the operand cache (one fused two-axis gather in
        input order — no argsort, no per-call stacking; the cache
        refreshes only slices whose shard published since the last
        batch)."""
        seq_ids = np.asarray(seq_ids)
        if seq_ids.size == 0:
            # empty batch: no fragmentation statistic, no gather, no
            # route counters, no operand-cache traffic — nothing may
            # touch the views (shapes come from the recorded extent)
            L, _, S, KV, hd = self._view_shape
            empty = jnp.zeros((L, 0, KV, S, hd), self.cache.k_pool.dtype)
            return empty, empty, route or "paged"
        route = route or self.route(seq_ids)
        # batch-level decision -> group-level counter (a multi-shard
        # batch must not skew shard 0's per-shard stats)
        self.group.count_route(route == "shortcut")
        if route == "shortcut":
            k, v = self._shortcut_context(seq_ids)
        else:
            k, v = pc.gather_context(self.cache, jnp.asarray(seq_ids))
        return k, v, route

    def _shortcut_context(self, seq_ids: np.ndarray):
        """View read in input order, straight off the stacked primary.

        One fused two-axis gather ``stack[sid, :, row]`` serves single-
        and multi-shard batches alike — input order falls out of the
        index arrays, and the stack needs no per-call refresh: replays
        published their slices into it BEFORE bumping ``view_epoch`` and
        ``sc_version``, so ``get`` here is an epoch check plus a handle
        return (a stack older than what the route gate certified cannot
        be served; a publish racing this read only makes the stack
        newer).  Epochs are read before the handle, per the protocol."""
        epochs = [m.view_epoch for m in self.group]
        stack_k, stack_v = self.operands.get("kv_view", epochs)
        sid = seq_ids % self.num_shards
        rows = seq_ids // self.num_shards
        return stacked_context(stack_k, stack_v, jnp.asarray(sid),
                               jnp.asarray(rows))

    def seq_lens(self, seq_ids: np.ndarray) -> np.ndarray:
        return np.asarray(self.cache.seq_lens)[seq_ids]

    # -- maintenance (delegated to the runtime) ------------------------------

    def pump(self) -> int:
        return self.group.pump()

    def wait_in_sync(self, seq_ids: np.ndarray, timeout: float = 30.0):
        return self.group.wait_in_sync(self._by_shard(seq_ids), timeout)

    def close(self):
        self.group.close()

    # -- replay callables (the only KV-specific maintenance code) ------------
    #
    # Lock-free: each replay runs on its shard's single mapper (thread or
    # pump caller), reads its shard's memoized slice of the stacked
    # primary, chains the functional updates, and publishes ONE slice
    # write back into the stack — at the mapper's next_view_epoch,
    # before sc_version moves (zero-copy publish, DESIGN.md §4.4).  No
    # other shard's slice is read or written — concurrent shard replays
    # never serialize on anything but the cache's brief patch lock.

    def _replay_create(self, cache: pc.PagedKVCache, requests,
                       shard: int = 0) -> None:
        vk, vv = self.views.snapshot(shard)
        for r in requests:
            for s in np.asarray(r.payload):
                vk, vv = compose_seq(
                    cache, vk, vv, jnp.int32(int(s)),
                    jnp.int32(int(s) // self.num_shards))
            self.group[shard].stats.slots_remapped += len(r.versions)
        self.views.publish(shard, (vk, vv),
                           epoch=self.group[shard].next_view_epoch)

    def _replay_update(self, cache: pc.PagedKVCache, requests,
                       shard: int = 0) -> None:
        vk, vv = self.views.snapshot(shard)
        for r in requests:
            seq_ids, positions, new_k, new_v = r.payload
            rows = np.asarray(seq_ids) // self.num_shards
            vk, vv = append_to_view(
                vk, vv, jnp.asarray(rows),
                jnp.asarray(positions), new_k, new_v)
            self.group[shard].stats.slots_remapped += len(r.versions)
        self.views.publish(shard, (vk, vv),
                           epoch=self.group[shard].next_view_epoch)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
