"""Quickstart: the paper's technique end-to-end in 60 lines.

Builds a Shortcut-EH index, shows the async maintenance / version gating /
fan-in routing cycle, and compares both access paths — then the same idea
one level up, on a paged KV cache.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.sharded_eh import ShardedShortcutEH


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    keys = (rng.choice(2**31 - 1, size=5000, replace=False)
            + 1).astype(np.uint32)
    vals = np.arange(5000, dtype=np.uint32)

    # the index (one shard: the paper's flat index): traditional
    # directory (authoritative, synchronous) + shortcut directory (async
    # replica, hardware-friendly access path)
    with ShardedShortcutEH(12, 64, 4096, num_shards=1,
                           async_mapper=True) as index:
        shard = index.shards[0]
        index.insert(keys[:4000], vals[:4000])
        print(f"inserted 4000; versions (trad, shortcut) = "
              f"{shard.versions()}  in_sync={index.in_sync()}")

        # lookups are correct immediately — routed via the traditional
        # path until the mapper catches up
        out = np.asarray(index.lookup_batched(keys[:1000]))
        assert (out == vals[:1000]).all()
        print(f"lookup wave 1 ok; routed shortcut? "
              f"{index.routed_shortcut > 0}")

        assert index.wait_in_sync()
        print(f"mapper caught up; versions = {shard.versions()}  "
              f"avg fan-in = {index.avg_fan_in():.2f}")

        out = np.asarray(index.lookup_batched(keys[:4000]))
        assert (out == vals[:4000]).all()
        print(f"lookup wave 2 ok; routed shortcut? "
              f"{index.routed_shortcut > 0}")

        # an insert burst makes the shortcut stale again (Fig 8)
        index.insert(keys[4000:], vals[4000:])
        print(f"after burst: in_sync={index.in_sync()} "
              f"(lookups keep working via the traditional path)")
        out = np.asarray(index.lookup_batched(keys))
        assert (out == vals).all()
        assert index.wait_in_sync()
        print(f"resynced: {shard.versions()}; "
              f"maintenance stats: {index.stats}")


if __name__ == "__main__":
    main()
