"""Sharded Shortcut-EH: the paper's index partitioned for scale.

The shortcut directory of §4.1 is a single-node construct; this module
partitions the key space by the **top ``log2(N)`` bits of the directory
hash** into N shards, each a *full* :class:`~repro.core.shortcut_eh.ShortcutEH`
(own bucket pool, own traditional directory, own composed view, own
mapper) registered in a :class:`~repro.runtime.shard_group.MapperGroup`.
Because the directory uses MSB indexing, the shard-local directories are
exactly the N contiguous slices of the one big directory the flat index
would have built — the partition is a *refinement*, not a different
structure, which is why a sharded index answers every lookup bit-for-bit
identically to a flat one over the same trace.

What sharding buys (DESIGN.md §4):

  * **shard-local maintenance** — splits, doublings, create/update
    requests, version gates and route decisions touch exactly one
    shard's mapper; a doubling in shard 3 never collapses shard 5's
    pending updates nor gates its reads (the §5 shootdown concern,
    confined);
  * **one-dispatch batched lookup** — a key batch is bucketized per
    shard with a single stable ``argsort`` pass, padded to a static
    per-shard capacity (bounded size set => bounded jit variants), and
    resolved by ONE ``pallas_call`` whose grid iterates shards
    (``kernels/eh_lookup.sharded_eh_lookup``), then scattered back to
    input order.  The stacked operands are **device-resident**
    (``runtime/operand_cache``, DESIGN.md §4.3): refreshed per dirty
    shard on publish epochs, not re-stacked per call; shards whose
    gates disagree resolve in the same dispatch through the per-shard
    routed kernel (``sharded_routed_lookup``).

``num_shards=1`` is the flat index, the paper's own: the same class, the
same operand cache and the same ``lookup_batched``, at a leading shard
axis of 1.

What it does not buy: a smaller per-shard view.  Each shard's
directory and composed view keep ``2**global_depth`` rows, of which only
``2**(global_depth - shard_bits)`` are reachable (the slot keeps the
shard bits), so the VMEM-resident kernels cap the whole index near 2^20
slots whatever N is.  ``avg_fan_in`` counts the unreachable slots too.

Skew note: within shard s every key shares its top ``shard_bits`` hash
bits, so the first ``shard_bits`` doublings of a shard's directory are
degenerate (both halves of each split land on one side until local
depths exceed ``shard_bits``).  Correctness and the I1–I5 invariants are
untouched; budget ``max_global_depth`` per shard accordingly (the flat
equivalent depth, not depth - shard_bits).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import extendible_hashing as eh
from repro.core import hashing
from repro.core.shortcut_eh import ShortcutEH
from repro.runtime.mapper import GLOBAL_VIEW, MaintenanceStats
from repro.runtime.operand_cache import StackedOperandCache
# The generic cross-shard batching helpers live with the sharded runtime
# (shared with the KV manager's cross-shard get_context); re-exported
# here because they are part of this module's historical public API.
from repro.runtime.shard_group import (MapperGroup, pad_batch,
                                       partition_by_shard, shard_order)

__all__ = ["ShardedShortcutEH", "partition_by_shard", "shard_of_keys",
           "shard_order"]

# key tile of the lookup kernels (a multiple of the 128 lanes)
_TILE = 256


def shard_of_keys(keys: np.ndarray, shard_bits: int,
                  key_bits: int = 32) -> np.ndarray:
    """Shard index per key: the top ``shard_bits`` of the directory hash
    (host twin of ``hashing.hash_dir`` + MSB slot rule)."""
    if shard_bits == 0:
        return np.zeros(np.asarray(keys).shape, np.int64)
    h = hashing.hash_dir_np(keys, key_bits)
    return (h >> np.uint64(32 - shard_bits)).astype(np.int64)


def _trad_parts(states):
    """Operand-cache part builder for the traditional family: one
    shard's ``(directory, bucket_keys, bucket_vals, global_depth)``
    drawn from the consistent per-shard state snapshots.  Shapes are
    static (the directory is allocated at ``max_global_depth``), so this
    family never rebuilds after its first stack.  Built lazily by the
    first traditional-routed batched lookup (pull mode), then kept warm
    by ``insert``'s write-time push — a shortcut-routed steady state
    never holds this stack at all."""
    def parts(s):
        st = states[s]
        return (st.directory, st.bucket_keys, st.bucket_vals,
                st.global_depth)
    return parts


class ShardedShortcutEH:
    """Shortcut-EH partitioned N ways; ``num_shards=1`` is the flat index.

    Each shard's ``capacity``/``max_global_depth``/``bucket_slots`` equal
    the constructor arguments (capacity is per shard — sizing it as the
    flat index's keeps the sharded index at least as drop-free as the
    flat one under any skew, the precondition for bit-for-bit parity).

    ``key_bits``/``value_bits`` (32 or 64, default 32) fix the width of
    keys and values for every shard (:class:`ShortcutEH`).  At 64 bits
    the device holds each as two uint32 words: ``lookup_batched`` splits
    its keys once per call and joins the answers into a numpy uint64
    array, timed by ``split_seconds`` with the shards' insert splits.
    A key or value array whose dtype is wider than the width raises
    ``ValueError``: it is never wrapped.
    """

    def __init__(self, max_global_depth: int, bucket_slots: int,
                 capacity: int, *, num_shards: int = 1,
                 fan_in_threshold: float = 8.0,
                 poll_interval: float = 0.025, async_mapper: bool = False,
                 key_bits: int = 32, value_bits: int = 32):
        if num_shards < 1 or num_shards & (num_shards - 1):
            raise ValueError(f"num_shards must be a power of two, "
                             f"got {num_shards}")
        self.num_shards = num_shards
        self.shard_bits = num_shards.bit_length() - 1
        self.key_bits, self.value_bits = key_bits, value_bits
        self._key_type = hashing.word_type(key_bits)
        self._value_type = hashing.word_type(value_bits)
        self._split_seconds = 0.0           # lookup_batched's own
        # primary storage of the stacked lookup operands (families
        # "eh_view" / "eh_trad", DESIGN.md §4.4): replays publish their
        # shard's slice straight into the stack at publish time, so the
        # batched lookup path is an epoch check + handle return with
        # zero device work in steady state, and per-shard views exist
        # only as memoized slices of the stack (no duplicates)
        self.operands = StackedOperandCache(num_shards)
        self.shards = [
            ShortcutEH(max_global_depth, bucket_slots, capacity,
                       self.operands, i,
                       fan_in_threshold=fan_in_threshold,
                       poll_interval=poll_interval,
                       async_mapper=async_mapper,
                       key_bits=key_bits, value_bits=value_bits)
            for i in range(num_shards)]
        self.group = MapperGroup(
            [s.mapper for s in self.shards],
            router=lambda key: int(self.shard_of([key])[0]))

    # -- routing -------------------------------------------------------------

    def shard_of(self, keys) -> np.ndarray:
        """Vectorized key -> shard index (top hash bits)."""
        return shard_of_keys(self._keys(keys), self.shard_bits,
                             self.key_bits)

    def _keys(self, keys) -> np.ndarray:
        return np.asarray(hashing.check_width(keys, self.key_bits, "key"),
                          self._key_type)

    def _answer(self, out: np.ndarray):
        """Answers in input order as the caller gets them: a device
        array at 32-bit values, a numpy uint64 array at 64."""
        return jnp.asarray(out) if self.value_bits == 32 else out

    # -- main-thread API ----------------------------------------------------

    def insert(self, keys, values) -> None:
        """Partition the batch and insert into each owning shard.

        Strictly shard-local: each sub-insert takes only its shard's
        lock, bumps only its shard's version, and enqueues maintenance
        only on its shard's queue."""
        keys = self._keys(keys)
        values = np.asarray(
            hashing.check_width(values, self.value_bits, "value"),
            self._value_type)
        if self.num_shards == 1:
            self.shards[0].insert(keys, values)
            return
        sid = self.shard_of(keys)
        order, counts, starts = shard_order(sid, self.num_shards)
        for s in range(self.num_shards):
            c = int(counts[s])
            if c:
                idx = order[starts[s]:starts[s] + c]
                self.shards[s].insert(keys[idx], values[idx])

    def lookup_batched(self, keys) -> jax.Array:
        """Fused cross-shard lookup: ONE Pallas dispatch for all shards,
        fed from the device-resident operand cache.

        Each shard routes independently (its own gate, its own view):
        an all-shortcut batch takes the shortcut kernel, an all-
        traditional batch the traditional kernel, and a *mixed* batch
        the per-shard routed kernel — still one ``pallas_call``; a
        gate-rejecting shard no longer demotes the others.  The stacked
        operands come from :class:`StackedOperandCache` keyed by the
        shards' publish epochs, so a batch against an unchanged index
        uploads nothing and a replay-churned batch re-uploads only the
        dirty shards' slices.  Returns values in input order.

        Profiler spans, one per step: ``lookup.bucketize``,
        ``lookup.gate``, ``lookup.operands``, ``lookup.dispatch``,
        ``lookup.wait`` (results to the host) and ``lookup.scatter``; at
        64-bit keys ``lookup.split`` after the bucketize (the keys into
        words), at 64-bit values after the wait (the answers' words
        joined)."""
        from repro.kernels.eh_lookup import (sharded_eh_lookup,
                                             sharded_routed_lookup,
                                             sharded_shortcut_lookup)
        keys = self._keys(keys)
        if keys.size == 0:
            # no padding, no operand refresh, no dispatch, no route
            # counters — an empty batch must not touch the device
            return self._answer(np.empty(0, self._value_type))
        with TraceAnnotation("lookup.bucketize"):
            sid = shard_of_keys(keys, self.shard_bits, self.key_bits)
            order, counts, starts = shard_order(sid, self.num_shards)
            cap = pad_batch(int(counts.max()))
            padded, counts, order, rank = partition_by_shard(
                keys, sid, self.num_shards, cap,
                order=order, counts=counts, starts=starts)
        if self.key_bits == 64:
            with TraceAnnotation("lookup.split"):
                t = time.perf_counter()
                padded = hashing.split_words(padded)      # (N, 2, cap)
                self._split_seconds += time.perf_counter() - t
        # Gate every shard FIRST (each policy decides exactly once — no
        # short-circuit), then read publish epochs/flags: replays
        # publish into the stack BEFORE bumping view_epoch and BEFORE
        # sc_version, so any view a gate certifies is already resident
        # at a covering epoch — get("eh_view", epochs) below is a pure
        # epoch check + handle return, never a patch.  The traditional
        # family stays pull-mode: built lazily here from the per-shard
        # state snapshots (read AFTER the epochs, so an epoch can only
        # under-describe its snapshot), kept warm by insert's push.
        with TraceAnnotation("lookup.gate"):
            gates = [s.mapper.gate(s.avg_fan_in(), [GLOBAL_VIEW])
                     for s in self.shards]
            view_epochs = [s.view_epoch for s in self.shards]
            state_epochs = [s.state_epoch for s in self.shards]
            states = [s.state for s in self.shards]
            pub = self.operands.published("eh_view")
            shortcut_ok = [g and pub is not None and pub[i]
                           for i, g in enumerate(gates)]
            involved = [int(s) for s in np.nonzero(counts)[0]]
            for s in involved:
                self.group.count_route(shortcut_ok[s], shard=s)
            n_sc = sum(1 for s in involved if shortcut_ok[s])
        with TraceAnnotation("lookup.operands"):
            if n_sc:
                view_ops = self.operands.get("eh_view", view_epochs)
            if n_sc < len(involved):
                trad_ops = self.operands.get(
                    "eh_trad", state_epochs, _trad_parts(states))
        with TraceAnnotation("lookup.dispatch"):
            keys_dev = jnp.asarray(padded)
            if n_sc == len(involved):
                res = sharded_shortcut_lookup(keys_dev, *view_ops,
                                              tile=_TILE)
            elif n_sc == 0:
                res = sharded_eh_lookup(keys_dev, *trad_ops, tile=_TILE)
            else:
                flags = jnp.asarray(
                    [0 if ok else 1 for ok in shortcut_ok], jnp.int32)
                res = sharded_routed_lookup(keys_dev, *trad_ops,
                                            *view_ops, flags, tile=_TILE)
        with TraceAnnotation("lookup.wait"):
            res = np.asarray(res)
        if self.value_bits == 64:
            with TraceAnnotation("lookup.split"):
                t = time.perf_counter()
                res = hashing.join_words(res)             # (N, cap)
                self._split_seconds += time.perf_counter() - t
        with TraceAnnotation("lookup.scatter"):
            out = np.empty(keys.size, self._value_type)
            out[order] = res[sid[order], rank]
            return self._answer(out)

    # -- aggregated bookkeeping ----------------------------------------------

    @property
    def stats(self) -> MaintenanceStats:
        return self.group.stats

    def per_shard_stats(self) -> list:
        return self.group.per_shard_stats()

    @property
    def routed_shortcut(self) -> int:
        return self.group.routed_shortcut

    @property
    def routed_traditional(self) -> int:
        return self.group.routed_fallback

    @property
    def keys_in_place(self) -> int:
        return sum(s.keys_in_place for s in self.shards)

    @property
    def keys_scanned(self) -> int:
        return sum(s.keys_scanned for s in self.shards)

    @property
    def buckets_touched(self) -> int:
        return sum(s.buckets_touched for s in self.shards)

    @property
    def split_seconds(self) -> float:
        """Host seconds spent splitting 64-bit keys and values into
        words and joining answers from them; 0 at 32 bits."""
        return self._split_seconds + sum(s.split_seconds
                                         for s in self.shards)

    def num_entries(self) -> int:
        return sum(int(eh.eh_num_entries(s.state)) for s in self.shards)

    def avg_fan_in(self) -> float:
        return float(np.mean([s.avg_fan_in() for s in self.shards]))

    def in_sync(self) -> bool:
        return all(s.in_sync() for s in self.shards)

    def pump(self, max_requests: int = 1 << 30) -> int:
        return self.group.pump(max_requests)

    def wait_in_sync(self, timeout: float = 30.0) -> bool:
        return self.group.wait_in_sync(timeout=timeout)

    def close(self) -> None:
        self.group.close()

    # -- verification --------------------------------------------------------

    def check_invariants(self) -> dict:
        """Per-shard structural invariants I1–I5 plus the cross-shard
        S1: every live key is stored in the shard its hash routes to."""
        out = {"ok": True, "errors": [], "shards": []}
        for s, shard in enumerate(self.shards):
            rep = eh.check_invariants(shard.state)
            out["shards"].append(rep)
            if not rep["ok"]:
                out["ok"] = False
                out["errors"] += [f"shard {s}: {e}" for e in rep["errors"]]
            bk = eh.host_keys(shard.state)
            live = bk[bk != hashing.all_ones(self.key_bits)]
            if live.size:
                owners = shard_of_keys(live, self.shard_bits, self.key_bits)
                if not (owners == s).all():
                    out["ok"] = False
                    out["errors"].append(
                        f"S1: shard {s} holds foreign keys "
                        f"{live[owners != s][:4].tolist()}")
        return out

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
