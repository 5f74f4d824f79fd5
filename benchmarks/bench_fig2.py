"""Figure 2: lookup cost vs number of indexed leaf nodes, traditional
(two dependent indirections) vs shortcut (one).

The paper sweeps 2^8..2^21 4KB leaves under 10^7 uniform accesses; we
sweep a scaled range.  Reproduction target: the shortcut curve sits below
the traditional curve, and the gap grows with the directory size (random
gathers through an extra level dominate)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, timeit, unique_keys
from repro.core import extendible_hashing as eh


def run(scale: float = 1.0 / 64):
    n_access = max(10_000, int(10_000_000 * scale))
    rng = np.random.default_rng(1)
    rows = []
    for leaves_log2 in (8, 10, 12, 14):
        n_keys = (1 << leaves_log2) * 2   # ~2 entries per 4-slot bucket
        keys = unique_keys(rng, n_keys)
        st = eh.eh_create(max_global_depth=leaves_log2 + 2,
                          bucket_slots=4, capacity=1 << (leaves_log2 + 1))
        st, _, _ = eh.eh_insert_many(
            st, jnp.asarray(keys),
            jnp.asarray(np.arange(n_keys, dtype=np.uint32)))
        g = int(st.global_depth)
        vk, vv = eh.compose_shortcut(st, 1 << g)
        probe = jnp.asarray(rng.choice(keys, n_access))
        t_trad = timeit(eh.eh_lookup_many, st, probe) / n_access * 1e9
        t_short = timeit(eh.shortcut_lookup_many, vk, vv,
                         st.global_depth, probe) / n_access * 1e9
        rows += [
            Row("fig2", f"traditional_leaves_2^{leaves_log2}", t_trad,
                "ns/lookup", f"global_depth={g}"),
            Row("fig2", f"shortcut_leaves_2^{leaves_log2}", t_short,
                "ns/lookup", f"speedup={t_trad / max(t_short, 1e-9):.2f}x"),
        ]
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
