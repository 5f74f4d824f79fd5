"""Figure 7: insertion (a) and lookup (b) across HT / HTI / CH / EH /
Shortcut-EH.

Paper: 100M inserts then 100M random hit-lookups; 4KB buckets; resize at
35% load.  Default scale 1/100.  Reproduction targets:
  7a — HT shows rehash staircases, HTI flattens them, EH/Shortcut-EH
       distribute resizing smoothly, CH is cheapest, and Shortcut-EH's
       maintenance overhead over EH is small (paper: ~8%);
  7b — Shortcut-EH ~ HT > EH > CH > HTI on lookups.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, sync, timeit, unique_keys
from repro.core import baselines as bl
from repro.core import extendible_hashing as eh
from repro.core.shortcut_eh import ShortcutEH


def run(scale: float = 1.0 / 100):
    n = max(20_000, int(100_000_000 * scale * 0.01))  # entries
    n_lookup = n
    batch = max(2_000, n // 16)
    rng = np.random.default_rng(4)
    keys = unique_keys(rng, n)
    vals = np.arange(n, dtype=np.uint32)
    probe = jnp.asarray(rng.choice(keys, n_lookup))
    rows = []
    max_log2 = int(np.ceil(np.log2(n / 0.30)))
    bucket_slots = 512  # 4KB of (k,v) u32 pairs

    def insert_curve(name, create, insert_many, lookup_many, state):
        t_accum = 0.0
        curve = []
        for i in range(0, n, batch):
            kb = jnp.asarray(keys[i:i + batch])
            vb = jnp.asarray(vals[i:i + batch])
            t0 = time.perf_counter()
            state = insert_many(state, kb, vb)
            sync(jax.tree.leaves(state)[0]) if hasattr(
                state, "_fields") else None
            t_accum += time.perf_counter() - t0
            curve.append(t_accum)
        rows.append(Row("fig7a", f"{name}_total_insert", t_accum, "s",
                        f"curve={['%.3f' % c for c in curve[::4]]}"))
        t_lk = timeit(lookup_many, state, probe) / n_lookup * 1e9
        rows.append(Row("fig7b", f"{name}_lookup", t_lk, "ns/lookup"))
        return state

    import jax
    # HT
    insert_curve("HT", None, bl.ht_insert_many, bl.ht_lookup_many,
                 bl.ht_create(max_log2, initial_size_log2=9))
    # HTI
    insert_curve("HTI", None, bl.hti_insert_many, bl.hti_lookup_many,
                 bl.hti_create(max_log2, initial_size_log2=9))
    # CH: fixed 1GB-analogue table (scaled), 128B buckets (16 pairs)
    insert_curve("CH", None, bl.ch_insert_many, bl.ch_lookup_many,
                 bl.ch_create(table_log2=max(8, max_log2 - 4),
                              capacity=max(n // 8, 1024),
                              bucket_slots=16))
    # EH
    eh_capacity = max(64, int(n / (bucket_slots * 0.3)) * 4)
    insert_curve("EH", None, lambda s, k, v: eh.eh_insert_many(s, k, v)[0],
                 eh.eh_lookup_many,
                 eh.eh_create(max_global_depth=16,
                              bucket_slots=bucket_slots,
                              capacity=eh_capacity))

    # Shortcut-EH: synchronous inserts + async maintenance (pumped),
    # lookups routed per the version/fan-in gate
    sc = ShortcutEH(max_global_depth=16, bucket_slots=bucket_slots,
                    capacity=eh_capacity)
    t_accum = 0.0
    for i in range(0, n, batch):
        t0 = time.perf_counter()
        sc.insert(keys[i:i + batch], vals[i:i + batch])
        t_accum += time.perf_counter() - t0
    t_maint0 = time.perf_counter()
    sc.pump()
    t_maint = time.perf_counter() - t_maint0
    rows.append(Row("fig7a", "ShortcutEH_total_insert", t_accum, "s",
                    f"maintenance_async={t_maint:.3f}s"))
    assert sc.in_sync()
    t_lk = timeit(lambda p: sc.lookup(p), probe) / n_lookup * 1e9
    rows.append(Row("fig7b", "ShortcutEH_lookup", t_lk, "ns/lookup",
                    f"routed_shortcut={sc.use_shortcut()}"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
