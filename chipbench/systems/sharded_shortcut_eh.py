"""The sharded Shortcut-EH index (``repro.core.sharded_eh``) as the
harness drives it: through its public entry points, ``insert`` for
updates (returning is the acknowledgement) and ``lookup_batched`` for
reads, with the mapper on its own threads.

The index holds 32-bit keys and values (ROADMAP R3): a configuration of
any other width is refused at construction, not wrapped into 32 bits."""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

EMPTY = np.uint32(0xFFFFFFFF)
ROUTES = ("traditional", "shortcut")


class System:
    def __init__(self, cfg: dict):
        widths = (int(cfg.get("key_bits", 32)),
                  int(cfg.get("value_bits", 32)))
        if widths != (32, 32):
            raise ValueError(
                f"the index holds 32-bit keys and values (ROADMAP R3); the "
                f"configuration asks for {widths[0]}-bit keys and "
                f"{widths[1]}-bit values")
        from repro.core.sharded_eh import ShardedShortcutEH
        self.cfg = cfg
        self.idx = ShardedShortcutEH(
            int(cfg["max_global_depth"]), int(cfg["bucket_slots"]),
            int(cfg["capacity"]), num_shards=int(cfg["num_shards"]),
            fan_in_threshold=float(cfg["fan_in_threshold"]),
            async_mapper=bool(cfg["async_mapper"]))

    # -- the timed path ------------------------------------------------------

    def insert(self, keys: np.ndarray, values: np.ndarray) -> None:
        self.idx.insert(keys, values)

    def lookup(self, keys: np.ndarray):
        return self.idx.lookup_batched(keys)

    # -- set-up and checks ---------------------------------------------------

    def wait_in_sync(self, timeout: float) -> bool:
        return self.idx.wait_in_sync(timeout=timeout)

    def force_route(self, route: Optional[str]) -> None:
        """Warm-up and checks only: pin every shard's gate to one route
        (``None`` restores the configured threshold).  The shortcut
        route still needs the shard's view in sync."""
        threshold = {None: float(self.cfg["fan_in_threshold"]),
                     "traditional": 0.0, "shortcut": math.inf}[route]
        for shard in self.idx.shards:
            shard.fan_in_threshold = threshold

    def warm_replay_shapes(self) -> None:
        """Build the update replay's programs for every padded chunk of
        remapped slots it can meet (``ShortcutEH._replay_update`` pads the
        stale slots to one of ``_CHUNK_SIZES``; how many are stale depends
        on how many requests the mapper merges), so that none compiles
        in the measured window."""
        from repro.core import rewiring
        from repro.core.shortcut_eh import _CHUNK_SIZES
        for shard in self.idx.shards:
            view = shard.view_snapshot()
            if view is None:
                continue
            st = shard.state
            rows = view[0].shape[0]
            for n in _CHUNK_SIZES:
                zeros = np.zeros(n, np.int32)
                for v, pool in ((view[0], st.bucket_keys),
                                (view[1], st.bucket_vals)):
                    rewiring.remap_slots(v, pool, zeros,
                                         zeros).block_until_ready()
                if n >= rows:
                    break

    def counters(self) -> dict:
        stats = self.idx.stats
        return {"routed_shortcut": self.idx.routed_shortcut,
                "routed_traditional": self.idx.routed_traditional,
                "replay_seconds": stats.replay_seconds,
                "populate_seconds": stats.populate_seconds}

    def entries(self) -> int:
        return self.idx.num_entries()

    def dropped(self) -> int:
        return sum(int(s.state.dropped) for s in self.idx.shards)

    def layout(self):
        """Where each stored key lives, from the index's state: returns
        ``(keys, shard, bucket, slot)`` with ``keys`` sorted and ``slot``
        each key's directory slot in its shard, by the index's own
        directory hash at the shard's global depth.  Read outside the
        timed region."""
        from repro.core import hashing
        keys, shard, bucket, slot = [], [], [], []
        for s, sh in enumerate(self.idx.shards):
            st = sh.state
            nb = int(st.num_buckets)
            bk = np.asarray(st.bucket_keys[:nb])
            live = bk != EMPTY
            b = np.nonzero(live)[0]
            k = bk[live]
            keys.append(k)
            bucket.append(b)
            shard.append(np.full(b.size, s))
            slot.append(np.asarray(hashing.dir_slot(
                hashing.hash_dir(k), st.global_depth)).astype(np.int64))
        keys = np.concatenate(keys)
        order = np.argsort(keys)
        return (keys[order], np.concatenate(shard)[order],
                np.concatenate(bucket)[order], np.concatenate(slot)[order])

    def close(self) -> None:
        self.idx.close()
