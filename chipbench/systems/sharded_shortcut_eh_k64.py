"""The sharded Shortcut-EH index (``repro.core.sharded_eh``) at the
configuration's key and value widths, 64 bits included: the harness
drives it as ``sharded_shortcut_eh`` does, through ``insert`` and
``lookup_batched``, with the mapper on its own threads.

The index is built with ``key_bits`` and ``value_bits``: a program that
cannot hold such keys refuses the argument at construction, rather than
run with wrapped keys.  Keys and values go in and come out as numpy
arrays of their width; on the device each 64-bit word is two uint32
words, a bucket row ``2 * bucket_slots`` lanes of them."""
from __future__ import annotations

import numpy as np

from chipbench.systems import sharded_shortcut_eh as base


class System(base.System):
    def __init__(self, cfg: dict):
        from repro.core.sharded_eh import ShardedShortcutEH
        self.cfg = cfg
        self.idx = ShardedShortcutEH(
            int(cfg["max_global_depth"]), int(cfg["bucket_slots"]),
            int(cfg["capacity"]), num_shards=int(cfg["num_shards"]),
            fan_in_threshold=float(cfg["fan_in_threshold"]),
            async_mapper=bool(cfg["async_mapper"]),
            key_bits=int(cfg.get("key_bits", 32)),
            value_bits=int(cfg.get("value_bits", 32)))

    def counters(self) -> dict:
        """The base adapter's counters, plus the host's word split and
        join (``split_seconds``) and the bytes the mapper's replays
        published into the operand stacks (``publish_bytes``)."""
        return {**super().counters(),
                "split_seconds": self.idx.split_seconds,
                "publish_bytes": self.idx.operands.stats.publish_bytes}

    def layout(self):
        """Where each stored key lives, from the index's state: returns
        ``(keys, shard, bucket, slot)`` with ``keys`` (rejoined from
        their words) sorted and ``slot`` each key's directory slot in its
        shard, by the index's own directory hash of both words at the
        shard's global depth.  Read outside the timed region."""
        import jax.numpy as jnp

        from repro.core import extendible_hashing as eh
        from repro.core import hashing
        bits = self.idx.key_bits
        keys, shard, bucket, slot = [], [], [], []
        for s, sh in enumerate(self.idx.shards):
            st = sh.state
            bk = eh.host_keys(st)
            live = bk != hashing.all_ones(bits)
            b = np.nonzero(live)[0]
            k = bk[live]
            words = jnp.asarray(hashing.split_words(k) if bits == 64 else k)
            h = hashing.hash_dir(hashing.words(words, st.key_words))
            keys.append(k)
            bucket.append(b)
            shard.append(np.full(b.size, s))
            slot.append(np.asarray(hashing.dir_slot(
                h, st.global_depth)).astype(np.int64))
        keys = np.concatenate(keys)
        order = np.argsort(keys)
        return (keys[order], np.concatenate(shard)[order],
                np.concatenate(bucket)[order], np.concatenate(slot)[order])
