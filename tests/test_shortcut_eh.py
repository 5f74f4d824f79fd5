"""Shortcut-EH orchestration on the flat index
(``ShardedShortcutEH(num_shards=1)``): version gating, async
maintenance, fan-in routing — the paper's §4.1 architecture — and the
one lookup path, ``lookup_batched``, against the jnp reference on both
forced routes at both widths."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import extendible_hashing as eh
from repro.core import hashing
from repro.core.sharded_eh import ShardedShortcutEH

from conftest import miss_probe, unique_keys, wide_keys


def flat(*args, **kw):
    return ShardedShortcutEH(*args, num_shards=1, **kw)


def routed(idx, keys):
    """``lookup_batched`` and the (shortcut, traditional) route deltas."""
    sc0, tr0 = idx.routed_shortcut, idx.routed_traditional
    out = np.asarray(idx.lookup_batched(keys))
    return out, (idx.routed_shortcut - sc0, idx.routed_traditional - tr0)


def test_out_of_sync_until_pumped(rng):
    keys = unique_keys(rng, 200)
    sc = flat(8, 16, 256)
    sc.insert(keys, np.arange(200, dtype=np.uint32))
    assert not sc.in_sync()          # maintenance is asynchronous
    # lookups still correct via the traditional path
    out, route = routed(sc, keys)
    np.testing.assert_array_equal(out, np.arange(200, dtype=np.uint32))
    assert route == (0, 1)
    sc.pump()
    assert sc.in_sync()
    out, route = routed(sc, keys)
    np.testing.assert_array_equal(out, np.arange(200, dtype=np.uint32))
    assert route == (1, 0)


def test_versions_monotone_and_gate(rng):
    keys = unique_keys(rng, 300)
    sc = flat(8, 16, 256)
    for i in range(0, 300, 100):
        sc.insert(keys[i:i + 100],
                  np.arange(i, i + 100, dtype=np.uint32))
        trad, short = sc.shards[0].versions()
        assert short < trad
        sc.pump()
        trad, short = sc.shards[0].versions()
        assert short == trad


def test_fan_in_routing(rng):
    """High fan-in (few buckets, wide directory) must route traditional
    (the TLB-thrashing lesson, §3.2)."""
    keys = unique_keys(rng, 40)
    sc = flat(8, 64, 256, fan_in_threshold=8.0)
    sc.insert(keys, np.arange(40, dtype=np.uint32))
    sc.pump()
    # force a wide directory by doubling manually: insert nothing more —
    # instead check the routing rule directly on both regimes
    if sc.avg_fan_in() <= 8.0:
        assert routed(sc, keys)[1] == (1, 0)
    sc.shards[0].fan_in_threshold = 0.5  # now even fan-in 1 is "too high"
    if sc.avg_fan_in() > 0.5:
        out, route = routed(sc, keys)
        assert route == (0, 1)
        np.testing.assert_array_equal(out, np.arange(40, dtype=np.uint32))


def test_async_mapper_thread(rng):
    keys = unique_keys(rng, 400)
    with flat(8, 16, 512, poll_interval=0.005, async_mapper=True) as sc:
        for i in range(0, 400, 100):
            sc.insert(keys[i:i + 100],
                      np.arange(i, i + 100, dtype=np.uint32))
        assert sc.wait_in_sync(timeout=30.0)
        out = np.asarray(sc.lookup_batched(keys))
        np.testing.assert_array_equal(out, np.arange(400, dtype=np.uint32))
        assert sc.stats.creates >= 1
        assert sc.stats.populate_seconds >= 0.0


def test_create_collapses_stale_updates(rng):
    """A doubling enqueues a create request and pops outdated updates
    (paper §4.1); correctness must hold regardless of interleaving."""
    keys = unique_keys(rng, 600)
    sc = flat(9, 8, 1024)
    for i in range(0, 600, 50):  # many small batches: splits + doublings
        sc.insert(keys[i:i + 50], np.arange(i, i + 50, dtype=np.uint32))
    sc.pump()
    assert sc.in_sync()
    out = np.asarray(sc.lookup_batched(keys))
    np.testing.assert_array_equal(out, np.arange(600, dtype=np.uint32))
    report = eh.check_invariants(sc.shards[0].state)
    assert report["ok"], report["errors"]


@pytest.mark.parametrize("route", ["traditional", "shortcut"])
@pytest.mark.parametrize("key_bits", [32, 64])
def test_flat_index_matches_reference(rng, key_bits, route):
    """The bound single-shard path the chip runs: ``lookup_batched`` at
    ``num_shards=1`` on a forced route answers hits and misses as the
    jnp reference (``eh.eh_lookup_many``) and a dict do, and the route
    counters show the forced route."""
    miss = hashing.all_ones(key_bits)
    if key_bits == 32:
        drawn = unique_keys(rng, 500)
        keys, absent = drawn[:400], drawn[400:]
    else:
        keys = wide_keys(rng, 400)
        absent = miss_probe(rng, keys)
    vals = wide_keys(rng, 400, key_bits)
    upd = rng.choice(keys, 100)
    upd_vals = wide_keys(rng, 100, key_bits)
    table = dict(zip(keys.tolist(), vals.tolist()))
    table.update(zip(upd.tolist(), upd_vals.tolist()))
    probe = rng.permutation(np.concatenate([keys, absent]))
    want = np.asarray([table.get(int(k), miss) for k in probe],
                      hashing.word_type(key_bits))
    with flat(9, 8, 512, key_bits=key_bits, value_bits=key_bits) as idx:
        idx.insert(keys, vals)         # splits and doublings
        idx.insert(upd, upd_vals)      # overwrites, repeats included
        idx.pump()
        idx.shards[0].fan_in_threshold = {"traditional": 0.0,
                                          "shortcut": math.inf}[route]
        got, counts = routed(idx, probe)
        assert counts == ((1, 0) if route == "shortcut" else (0, 1))
        words = (jnp.asarray(probe) if key_bits == 32
                 else jnp.asarray(hashing.split_words(probe)))
        ref = np.asarray(eh.eh_lookup_many(idx.shards[0].state, words))
        if key_bits == 64:
            ref = hashing.join_words(ref)
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(got, want)
        assert (got == miss).sum() == absent.size


def test_views_live_only_in_the_stack(rng):
    """After a create replay and after an update replay, the shard's
    view is the operand cache's slice of the stacked ``eh_view``
    family: no other copy of it exists."""
    keys = unique_keys(rng, 200)

    def assert_view_is_the_stack_row(idx):
        view = idx.shards[0].view_snapshot()
        row = idx.operands.slice_of("eh_view", 0)
        assert view[0] is row[0] and view[1] is row[1]
        assert view[2] == int(row[2])

    with flat(8, 16, 256) as idx:
        assert idx.shards[0].view_snapshot() is None
        idx.insert(keys, np.arange(200, dtype=np.uint32))
        idx.pump()
        assert (idx.stats.creates, idx.stats.updates) == (1, 0)
        assert_view_is_the_stack_row(idx)
        # overwrite present keys: no split, so an update replay remaps
        new = np.arange(1000, 1050, dtype=np.uint32)
        idx.insert(keys[:50], new)
        idx.pump()
        assert (idx.stats.creates, idx.stats.updates) == (1, 1)
        assert_view_is_the_stack_row(idx)
        out, route = routed(idx, keys[:50])
        assert route == (1, 0)
        np.testing.assert_array_equal(out, new)
