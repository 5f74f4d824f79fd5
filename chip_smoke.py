#!/usr/bin/env python3
"""Bring-up smoke of the sharded Shortcut-EH index on one TPU.

Drives the index's main path once through its public entry points
(``ShardedShortcutEH.insert`` and ``lookup_batched``) at the fused
kernels' size cap — 16 shards, 64-slot buckets, directories of 2^14
slots, 2^19 distinct keys made from ``--seed`` — and compares every
answer exactly
with a plain host oracle (sorted numpy arrays, independent of the
package):

  (a) load, before any mapper pump: every shard is out of sync, so
      ``lookup_batched`` takes the traditional kernel;
  (b) pump half of the shards: mixed gates take the per-shard routed
      kernel;
  (c) pump all shards: ``lookup_batched`` takes the shortcut kernel;
  (d) a short load into an index with the mapper threads on, then
      ``wait_in_sync``.

Each lookup phase runs ``--batches`` batches of ``--batch`` keys, half
hits and half misses.  The phase times printed are host-clock smoke
timings that include compilation; they are not benchmark numbers.

  python3 chip_smoke.py [--seed 0]

Exits non-zero, without a result line, when JAX finds no TPU or any
check fails.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np

MISS = 0xFFFFFFFF
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def fmix32(x):
    """MurmurHash3's 32-bit finalizer: a bijection on uint32."""
    x = x.astype(np.uint32, copy=True)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def make_keys(seed: int, n: int):
    """``n`` distinct uint32 keys, none 0 or 0xFFFFFFFF: the bijection of
    ``n + 2`` consecutive inputs from a seed-chosen start, less the two
    excluded values."""
    start = np.uint32((seed * 0x9E3779B9 + 0x632BE5AB) & 0xFFFFFFFF)
    keys = fmix32(np.arange(n + 2, dtype=np.uint32) + start)
    return keys[(keys != 0) & (keys != np.uint32(MISS))][:n]


class Oracle:
    """The loaded key -> value map as sorted arrays."""

    def __init__(self, keys, vals):
        order = np.argsort(keys)
        self.keys, self.vals = keys[order], vals[order]

    def __call__(self, q):
        i = np.minimum(np.searchsorted(self.keys, q), self.keys.size - 1)
        return np.where(self.keys[i] == q, self.vals[i], np.uint32(MISS))


class CompileCounter:
    """Counts executables built (compiled or read from the persistent
    cache) per jitted function name, through ``jax.monitoring``."""

    def __init__(self):
        self.by_fun: dict = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._built)
        jax.monitoring.register_event_listener(self._event)

    def _built(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            name = kw.get("fun_name", "?")
            self.by_fun[name] = self.by_fun.get(name, 0) + 1

    def _event(self, event, **kw):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self):
        return dict(self.by_fun), self.cache_hits


def mib(n: int) -> str:
    return f"{n / 2**20:.1f} MiB"


def run(args) -> None:
    from repro.core.sharded_eh import ShardedShortcutEH
    from repro.kernels import eh_lookup as kernels

    N, half = args.shards, args.batch // 2
    counter = CompileCounter()
    rng = np.random.default_rng(args.seed)
    keys = make_keys(args.seed, 2 * args.keys)
    load_keys, miss_keys = keys[:args.keys], keys[args.keys:]
    load_vals = np.arange(1, args.keys + 1, dtype=np.uint32)
    oracle = Oracle(load_keys, load_vals)

    def batch(pool_hits, pool_misses, n_each):
        q = np.concatenate([rng.choice(pool_hits, n_each, replace=False),
                            rng.choice(pool_misses, n_each, replace=False)])
        return q[rng.permutation(q.size)]

    def lookup_phase(idx, name, kernel, want_sc, want_trad):
        t0 = time.perf_counter()
        sc0, tr0 = idx.routed_shortcut, idx.routed_traditional
        for _ in range(args.batches):
            q = batch(load_keys, miss_keys, half)
            got = np.asarray(idx.lookup_batched(q))
            bad = int((got != oracle(q)).sum())
            check(bad == 0, f"phase {name}: {bad} of {q.size} answers "
                            f"differ from the oracle")
        d_sc = idx.routed_shortcut - sc0
        d_tr = idx.routed_traditional - tr0
        print(f"phase {name}: {kernel}: {args.batches} x {args.batch} keys "
              f"match the oracle; route delta (shortcut, traditional) = "
              f"({d_sc}, {d_tr}); {time.perf_counter() - t0:.2f} s")
        check((d_sc, d_tr) == (want_sc * args.batches,
                               want_trad * args.batches),
              f"phase {name}: route counters ({d_sc}, {d_tr}) do not show "
              f"{kernel}")

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    idx = ShardedShortcutEH(args.depth, args.slots, args.capacity,
                            num_shards=N, fan_in_threshold=float("inf"))
    with idx:
        # (a) load, then look up before any pump
        sub = np.bincount(idx.shard_of(load_keys), minlength=N)
        before = counter.snapshot()
        t0 = time.perf_counter()
        idx.insert(load_keys, load_vals)
        check(idx.num_entries() == args.keys,
              f"{idx.num_entries()} entries after loading {args.keys} keys")
        after = counter.snapshot()
        built = {k: v - before[0].get(k, 0) for k, v in after[0].items()
                 if v != before[0].get(k, 0)}
        print(f"load: {args.keys} keys in {time.perf_counter() - t0:.2f} s "
              f"(smoke timing, host clock, compilation included)")
        print(f"load: {sum(built.values())} executables built "
              f"({after[1] - before[1]} from the persistent cache); "
              f"eh_insert_many: {built.get('jit(eh_insert_many)', 0)} for "
              f"{len(set(sub.tolist()))} distinct per-shard sub-batch "
              f"lengths")
        fan_in = idx.avg_fan_in()
        print(f"load: mean per-shard fan-in {fan_in:.2f} over the full "
              f"2^{args.depth}-slot directory")
        lookup_phase(idx, "a", "sharded_eh_lookup", 0, N)

        # (b) pump half of the shards: the routed kernel
        for shard in idx.shards[:N // 2]:
            shard.pump()
        lookup_phase(idx, "b", "sharded_routed_lookup", N // 2, N - N // 2)

        # (c) pump all: the shortcut kernel
        idx.pump()
        check(idx.in_sync(), "index not in sync after pump()")
        lookup_phase(idx, "c", "sharded_shortcut_lookup", N, 0)
        check(idx.wait_in_sync(), "wait_in_sync() returned False")
        t0 = time.perf_counter()
        inv = idx.check_invariants()
        check(inv["ok"], f"invariants: {inv['errors'][:4]}")
        check(idx.num_entries() == args.keys, "entries lost")
        print(f"invariants I1-I5 and S1 hold; {idx.num_entries()} entries, "
              f"none dropped ({time.perf_counter() - t0:.2f} s)")

        # every lookup entry point lowers to a compiled Mosaic kernel
        trad = idx.operands.handle("eh_trad")
        view = idx.operands.handle("eh_view")
        k2 = jnp.zeros((N, 256), jnp.uint32)
        flags = jnp.zeros((N,), jnp.int32)
        lowered = {
            "sharded_eh_lookup": kernels.sharded_eh_lookup.lower(k2, *trad),
            "sharded_routed_lookup": kernels.sharded_routed_lookup.lower(
                k2, *trad, *view, flags),
            "sharded_shortcut_lookup":
                kernels.sharded_shortcut_lookup.lower(k2, *view),
        }
        for name, low in lowered.items():
            check("tpu_custom_call" in low.as_text(),
                  f"{name} does not lower to a TPU kernel")
        print(f"lowered: {', '.join(lowered)} contain tpu_custom_call")

        resident = idx.operands.resident_bytes()
        state = sum(int(a.nbytes) for s in idx.shards
                    for a in jax.tree.leaves(s.state))
        stats = dev.memory_stats() or {}
        print("device bytes: operand stacks "
              + ", ".join(f"{k} {mib(v)}" for k, v in sorted(resident.items()))
              + f"; per-shard EH states {mib(state)}; bytes_limit "
              f"{mib(stats.get('bytes_limit', 0))}; in use "
              f"{mib(stats.get('bytes_in_use', 0))}; peak "
              f"{mib(stats.get('peak_bytes_in_use', 0))}")

    # (d) a short load with the mapper threads on
    t0 = time.perf_counter()
    n_short = args.batch
    short = Oracle(load_keys[:n_short], load_vals[:n_short])
    with ShardedShortcutEH(args.depth, args.slots, args.capacity,
                           num_shards=N, fan_in_threshold=float("inf"),
                           async_mapper=True) as aidx:
        aidx.insert(load_keys[:n_short], load_vals[:n_short])
        check(aidx.wait_in_sync(timeout=600.0),
              "async mappers did not catch up")
        q = batch(load_keys[:n_short], miss_keys, half)
        sc0 = aidx.routed_shortcut
        got = np.asarray(aidx.lookup_batched(q))
        check((got == short(q)).all(), "phase d differs from the oracle")
        check(aidx.routed_shortcut == sc0 + N,
              "phase d did not take the shortcut")
        check(aidx.num_entries() == n_short, "phase d dropped inserts")
    print(f"phase d: mapper threads: {n_short} keys loaded, in sync, "
          f"{q.size} lookups match the oracle; "
          f"{time.perf_counter() - t0:.2f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--keys", type=int, default=1 << 19)
    ap.add_argument("--batch", type=int, default=1 << 16)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--depth", type=int, default=14,
                    help="max global depth of each shard's directory")
    ap.add_argument("--slots", type=int, default=64,
                    help="slots per bucket")
    ap.add_argument("--capacity", type=int, default=2048,
                    help="bucket pool size per shard")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform})",
              file=sys.stderr)
        return 2
    print(f"compile cache: {cache}")
    t0 = time.perf_counter()
    try:
        run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.2f} s (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
