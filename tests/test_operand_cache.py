"""Publish-owned operand cache (runtime/operand_cache) and per-shard
routed fused lookup: publish/touch/seed semantics and the writer-order
contract, pull-mode epoch/refresh/rebuild semantics, grow-past-extent
re-stacks with live readers, routed-kernel parity for ``two_level``
vectors in {all-true, all-false, mixed}, the empty-batch
short-circuits, and cache coherence under concurrent async replays (no
torn stacks; a slice older than the epoch the gate certified is never
served; steady-state lookups patch zero slices)."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import extendible_hashing as eh
from repro.core.sharded_eh import ShardedShortcutEH
from repro.kernels import eh_lookup as kmod
from repro.runtime.operand_cache import StackedOperandCache

from conftest import unique_keys


# ---------------------------------------------------------------------------
# Unit semantics of the cache itself.
# ---------------------------------------------------------------------------

class TestCacheUnit:
    def _parts(self, data, calls=None):
        def parts(s):
            if calls is not None:
                calls.append(s)
            return tuple(jnp.asarray(a) for a in data[s])
        return parts

    def test_build_hit_and_dirty_refresh(self):
        cache = StackedOperandCache(3)
        data = [(np.full((4,), s, np.int32), np.full((2, 2), s, np.float32))
                for s in range(3)]
        calls = []
        out = cache.get("fam", [0, 0, 0], self._parts(data, calls))
        assert sorted(calls) == [0, 1, 2]           # first build touches all
        assert cache.stats.rebuilds == 1
        np.testing.assert_array_equal(np.asarray(out[0])[1], 1)
        # clean get: parts never invoked, same arrays served
        calls.clear()
        out2 = cache.get("fam", [0, 0, 0], self._parts(data, calls))
        assert calls == [] and cache.stats.hits == 1
        assert all(a is b for a, b in zip(out, out2))
        # dirty shard 1: only its part is read, only its slice changes
        data[1] = (np.full((4,), 7, np.int32), np.full((2, 2), 7, np.float32))
        out3 = cache.get("fam", [0, 5, 0], self._parts(data, calls))
        assert calls == [1]
        assert cache.stats.slice_refreshes == 1
        np.testing.assert_array_equal(np.asarray(out3[0]),
                                      [[0] * 4, [7] * 4, [2] * 4])
        np.testing.assert_array_equal(np.asarray(out3[1])[0], 0.0)

    def test_stale_epoch_restores_refresh(self):
        """Epoch comparison is inequality, not order: a reader that
        recorded a newer tuple under an older epoch (the allowed race
        direction) refreshes again on the next get — never serves
        stale."""
        cache = StackedOperandCache(2)
        data = [(np.zeros(3, np.int32),), (np.zeros(3, np.int32),)]
        cache.get("f", [4, 0], self._parts(data))
        data[0] = (np.ones(3, np.int32),)
        out = cache.get("f", [5, 0], self._parts(data))
        np.testing.assert_array_equal(np.asarray(out[0])[0], 1)

    def test_shape_change_rebuilds_family(self):
        cache = StackedOperandCache(2)
        data = [(np.zeros((2, 2), np.float32),),
                (np.ones((2, 2), np.float32),)]
        cache.get("f", [0, 0], self._parts(data))
        # shard 0 doubled: both shards restack at the new shape
        data = [(np.zeros((4, 2), np.float32),),
                (np.ones((4, 2), np.float32),)]
        calls = []
        out = cache.get("f", [1, 0], self._parts(data, calls))
        assert cache.stats.rebuilds == 2
        assert sorted(calls) == [0, 1]
        assert out[0].shape == (2, 4, 2)
        # and the family is clean again at the new epochs
        cache.get("f", [1, 0], self._parts(data))
        assert cache.stats.hits == 1

    def test_failed_refresh_commits_nothing(self):
        """A parts() exception mid-refresh must not leave the entry
        claiming freshness for the shards patched before the failure:
        epochs and arrays commit together, after the whole loop."""
        cache = StackedOperandCache(2)
        data = [(np.zeros(3, np.int32),), (np.ones(3, np.int32),)]
        cache.get("f", [0, 0], self._parts(data))
        data[0] = (np.full(3, 5, np.int32),)

        def bad_parts(s):
            if s == 1:
                raise RuntimeError("boom")
            return tuple(jnp.asarray(a) for a in data[s])

        with pytest.raises(RuntimeError):       # both shards dirty
            cache.get("f", [1, 1], bad_parts)
        assert cache.epochs("f") == [0, 0]      # nothing committed
        out = cache.get("f", [1, 1], self._parts(data))
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      [[5, 5, 5], [1, 1, 1]])

    def test_donate_flag_safe_on_cpu(self):
        """donate=True falls back to the non-donating refresh off
        accelerators; semantics are unchanged."""
        cache = StackedOperandCache(2, donate=True)
        data = [(np.zeros(3, np.int32),), (np.ones(3, np.int32),)]
        old = cache.get("f", [0, 0], self._parts(data))
        data[1] = (np.full(3, 9, np.int32),)
        out = cache.get("f", [0, 3], self._parts(data))
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      [[0, 0, 0], [9, 9, 9]])
        # the pre-refresh loan is still readable (no donation on CPU)
        np.testing.assert_array_equal(np.asarray(old[0]),
                                      [[0, 0, 0], [1, 1, 1]])

    def test_epoch_arity_checked_and_invalidate(self):
        cache = StackedOperandCache(2)
        with pytest.raises(ValueError):
            cache.get("f", [0], lambda s: (jnp.zeros(1),))
        data = [(np.zeros(2, np.int32),), (np.zeros(2, np.int32),)]
        cache.get("f", [0, 0], self._parts(data))
        assert "f" in cache and cache.epochs("f") == [0, 0]
        cache.invalidate("f")
        assert "f" not in cache and cache.epochs("f") is None
        cache.get("f", [0, 0], self._parts(data))
        assert cache.stats.rebuilds == 2


# ---------------------------------------------------------------------------
# The publish path: writers patch the stack at publish time; the lookup
# path is an epoch check plus a handle return.
# ---------------------------------------------------------------------------

class TestPublishPath:
    def test_first_publish_creates_family_zeroed(self):
        cache = StackedOperandCache(3)
        cache.publish("v", 1, (jnp.full((4,), 7, jnp.int32),), epoch=5)
        assert cache.published("v") == [False, True, False]
        assert cache.epochs("v") == [0, 5, 0]
        stack, = cache.handle("v")
        np.testing.assert_array_equal(
            np.asarray(stack), [[0] * 4, [7] * 4, [0] * 4])
        assert cache.stats.publish_refreshes == 1
        assert cache.stats.rebuilds == 1          # the zeroed creation
        assert cache.resident_bytes()["v"] == stack.nbytes

    def test_get_without_parts_is_epoch_check_plus_handle(self):
        cache = StackedOperandCache(2)
        cache.publish("v", 0, (jnp.ones((2,), jnp.int32),), epoch=3)
        cache.publish("v", 1, (jnp.full((2,), 2, jnp.int32),), epoch=1)
        out = cache.get("v", [3, 1])
        assert out is cache.handle("v")           # the stack itself
        assert cache.stats.hits == 1
        assert cache.stats.lookup_refreshes == 0
        # a newer entry than requested is still a hit (allowed race
        # direction: publish landed between epoch read and get)
        assert cache.get("v", [2, 0]) is out

    def test_lagging_push_family_is_writer_order_violation(self):
        cache = StackedOperandCache(2)
        with pytest.raises(RuntimeError, match="never published"):
            cache.get("v", [0, 0])
        cache.publish("v", 0, (jnp.zeros((2,), jnp.int32),), epoch=1)
        with pytest.raises(RuntimeError, match="lags the reader"):
            cache.get("v", [1, 2])

    def test_touch_advances_epoch_without_data(self):
        cache = StackedOperandCache(2)
        cache.touch("v", 0, epoch=9)              # no family yet: no-op
        assert "v" not in cache
        cache.publish("v", 0, (jnp.ones((2,), jnp.int32),), epoch=1)
        before = cache.handle("v")
        cache.touch("v", 0, epoch=4)
        assert cache.epochs("v") == [4, 0]
        assert cache.handle("v") is before        # no device work
        cache.touch("v", 0, epoch=2)              # epochs only move forward
        assert cache.epochs("v") == [4, 0]

    def test_seed_publishes_every_shard(self):
        cache = StackedOperandCache(2)
        z = jnp.zeros((3, 2), jnp.float32)
        cache.seed("kv", [(z, z), (z, z)])
        assert cache.published("kv") == [True, True]
        assert cache.epochs("kv") == [0, 0]
        k, v = cache.get("kv", [0, 0])
        assert k.shape == (2, 3, 2) and v.shape == (2, 3, 2)

    def test_publish_validates_part_count_dtype_rank(self):
        cache = StackedOperandCache(2)
        cache.publish("v", 0, (jnp.zeros((2,), jnp.int32),), epoch=1)
        with pytest.raises(ValueError, match="parts for"):
            cache.publish("v", 0, (jnp.zeros((2,), jnp.int32),) * 2,
                          epoch=2)
        with pytest.raises(ValueError, match="dtypes changed"):
            cache.publish("v", 0, (jnp.zeros((2,), jnp.float32),), epoch=2)
        with pytest.raises(ValueError, match="ranks changed"):
            cache.publish("v", 0, (jnp.zeros((2, 2), jnp.int32),), epoch=2)
        with pytest.raises(ValueError, match="shard"):
            cache.publish("v", 2, (jnp.zeros((2,), jnp.int32),), epoch=2)

    def test_smaller_part_pads_to_extent(self):
        cache = StackedOperandCache(2)
        cache.publish("v", 0, (jnp.full((4,), 1, jnp.int32),), epoch=1)
        cache.publish("v", 1, (jnp.full((2,), 2, jnp.int32),), epoch=1)
        stack, = cache.get("v", [1, 1])
        np.testing.assert_array_equal(
            np.asarray(stack), [[1, 1, 1, 1], [2, 2, 0, 0]])

    def test_grow_past_extent_restacks_without_blocking_readers(self):
        """A part outgrowing the stacked extent embeds the old stack in
        a larger zeroed one and swaps atomically: the reader's old
        handle stays valid and bit-identical, the new stack carries the
        old slices at the origin plus the grown part."""
        cache = StackedOperandCache(2)
        cache.publish("v", 0, (jnp.full((2, 2), 3, jnp.int32),), epoch=1)
        cache.publish("v", 1, (jnp.full((2, 2), 4, jnp.int32),), epoch=1)
        old, = cache.get("v", [1, 1])
        old_copy = np.asarray(old).copy()
        built = cache.stats.rebuilds
        # shard 0 doubles its first axis (a directory doubling)
        cache.publish("v", 0, (jnp.full((4, 2), 5, jnp.int32),), epoch=2)
        assert cache.stats.rebuilds == built + 1
        np.testing.assert_array_equal(np.asarray(old), old_copy)
        new, = cache.get("v", [2, 1])
        assert new.shape == (2, 4, 2)
        np.testing.assert_array_equal(np.asarray(new[0]), 5)
        # shard 1 kept its data, zero-padded past its own extent
        np.testing.assert_array_equal(np.asarray(new[1][:2]), 4)
        np.testing.assert_array_equal(np.asarray(new[1][2:]), 0)
        assert cache.resident_bytes()["v"] == new.nbytes

    def test_slice_of_memoized_per_publish(self):
        cache = StackedOperandCache(2)
        assert cache.slice_of("v", 0) is None
        cache.publish("v", 0, (jnp.full((3,), 1, jnp.int32),), epoch=1)
        s1 = cache.slice_of("v", 0)
        assert cache.slice_of("v", 0) is s1       # steady state: memo hit
        np.testing.assert_array_equal(np.asarray(s1[0]), 1)
        cache.publish("v", 1, (jnp.full((3,), 2, jnp.int32),), epoch=1)
        s2 = cache.slice_of("v", 0)
        assert s2 is not s1                       # stack swapped: new slice
        np.testing.assert_array_equal(np.asarray(s2[0]), 1)
        np.testing.assert_array_equal(
            np.asarray(cache.slice_of("v", 1)[0]), 2)

    def test_publish_bytes_count_each_stack_a_publish_writes(self):
        cache = StackedOperandCache(3)
        parts = (jnp.ones((4, 8), jnp.uint32), jnp.ones((4,), jnp.int32))
        cache.publish("v", 1, parts, epoch=1)
        stack_bytes = sum(a.nbytes for a in cache.handle("v"))
        assert stack_bytes == 3 * (4 * 8 * 4 + 4 * 4)
        assert cache.stats.publish_bytes == stack_bytes
        cache.publish("v", 2, parts, epoch=1)
        assert cache.stats.publish_bytes == 2 * stack_bytes
        # a lookup-path hit and an empty replay's touch write nothing
        cache.get("v", [0, 1, 1])
        cache.touch("v", 0, epoch=2)
        assert cache.stats.hits == 1
        assert cache.stats.publish_bytes == 2 * stack_bytes
        # publish_if_present goes through publish and is counted there
        cache.publish_if_present("v", 0, lambda: parts, epoch=3)
        cache.publish_if_present("absent", 0, lambda: parts, epoch=1)
        assert cache.stats.publish_bytes == 3 * stack_bytes
        assert cache.stats.snapshot().publish_bytes == 3 * stack_bytes

    def test_publish_if_present_only_warms_existing(self):
        cache = StackedOperandCache(2)
        calls = []

        def parts():
            calls.append(1)
            return (jnp.zeros((2,), jnp.int32),)

        cache.publish_if_present("t", 0, parts, epoch=1)
        assert calls == [] and "t" not in cache   # never built: no cost
        cache.get("t", [0, 0],
                  lambda s: (jnp.full((2,), s, jnp.int32),))
        cache.publish_if_present("t", 0, parts, epoch=1)
        assert calls == [1] and cache.epochs("t") == [1, 0]

    def test_invalidate_resets_published_flags_and_resident(self):
        cache = StackedOperandCache(2)
        cache.publish("v", 0, (jnp.zeros((2,), jnp.int32),), epoch=1)
        cache.invalidate("v")
        assert cache.published("v") is None
        assert "v" not in cache.resident_bytes()
        assert cache.slice_of("v", 0) is None

    def test_concurrent_readers_during_publish_churn(self):
        """One writer thread publishes growing slices while readers spin
        on slice_of/get: every observed slice must be internally
        consistent (keys and vals from the SAME publication) and the
        epoch contract must hold — get at an epoch the writer already
        stored never raises and never serves older data."""
        cache = StackedOperandCache(2)
        cache.publish("v", 0, (jnp.zeros((4,), jnp.int32),
                               jnp.zeros((4,), jnp.int32)), epoch=0)
        cache.publish("v", 1, (jnp.zeros((4,), jnp.int32),
                               jnp.zeros((4,), jnp.int32)), epoch=0)
        published = [0, 0]                        # writer-side epochs
        errors = []
        stop = threading.Event()

        def reader():
            try:
                while not stop.is_set():
                    eps = list(published)         # epochs BEFORE get
                    k, v = cache.get("v", eps)
                    for s in range(2):
                        a, b = np.asarray(k[s]), np.asarray(v[s])
                        assert np.array_equal(b, -a), "torn slice"
                        # each publication's first element IS its epoch
                        assert a[0] >= eps[s], \
                            "stale slice served past its epoch"
                    sl = cache.slice_of("v", 0)
                    assert np.array_equal(np.asarray(sl[1]),
                                          -np.asarray(sl[0]))
            except Exception as e:                # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            for e in range(1, 40):
                s = e % 2
                n = 4 + (e // 8) * 2              # periodic growth
                a = jnp.arange(e, e + n, dtype=jnp.int32)
                cache.publish("v", s, (a, -a), epoch=e)
                published[s] = e                  # arrays before epochs
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60.0)
        assert not errors, errors
        assert cache.stats.lookup_refreshes == 0  # readers never patched


# ---------------------------------------------------------------------------
# Routed kernel parity: per-shard two_level in {all-true, all-false, mixed}.
# ---------------------------------------------------------------------------

def _stacked_shards(rng, n_shards, keys_per_shard=160):
    """N independent EH states + composed views, stacked (views padded
    to the common slot capacity, exactly as the cache does)."""
    states, views, probes = [], [], []
    for s in range(n_shards):
        st = eh.eh_create(8, 8, 256)
        k = unique_keys(rng, keys_per_shard)
        v = (np.arange(keys_per_shard, dtype=np.uint32)
             + np.uint32(s * 10_000))
        st, _, _ = eh.eh_insert_many(st, jnp.asarray(k), jnp.asarray(v))
        vs = max(1, 1 << int(st.global_depth))
        vk, vv = eh.compose_shortcut(st, vs)
        states.append(st)
        views.append((vk, vv, vs.bit_length() - 1))
        probes.append(k[:64])
    v_cap = max(v[0].shape[0] for v in views)
    pads = [(jnp.pad(v[0], ((0, v_cap - v[0].shape[0]), (0, 0))),
             jnp.pad(v[1], ((0, v_cap - v[1].shape[0]), (0, 0))), v[2])
            for v in views]
    ops = dict(
        keys=jnp.stack([jnp.asarray(p, jnp.uint32) for p in probes]),
        dirs=jnp.stack([st.directory for st in states]),
        bks=jnp.stack([st.bucket_keys for st in states]),
        bvs=jnp.stack([st.bucket_vals for st in states]),
        gds=jnp.asarray([int(st.global_depth) for st in states], jnp.int32),
        vks=jnp.stack([p[0] for p in pads]),
        vvs=jnp.stack([p[1] for p in pads]),
        vls=jnp.asarray([p[2] for p in pads], jnp.int32))
    return ops


class TestRoutedKernelParity:
    @pytest.mark.parametrize("flags", [
        [1, 1, 1, 1],                    # all-true: every shard two-level
        [0, 0, 0, 0],                    # all-false: every shard shortcut
        [1, 0, 0, 1], [0, 1, 1, 0],      # mixed-sync groups
    ])
    def test_matches_static_kernels(self, rng, flags):
        o = _stacked_shards(rng, 4)
        ref = kmod.sharded_eh_lookup(o["keys"], o["dirs"], o["bks"],
                                     o["bvs"], o["gds"], tile=128)
        via_view = kmod.sharded_shortcut_lookup(o["keys"], o["vks"],
                                                o["vvs"], o["vls"], tile=128)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(via_view))
        got = kmod.sharded_routed_lookup(
            o["keys"], o["dirs"], o["bks"], o["bvs"], o["gds"],
            o["vks"], o["vvs"], o["vls"],
            jnp.asarray(flags, jnp.int32), tile=128)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_stacked_single_shard_select_matches_flat(self, rng):
        """The bound single-shard path (``stacked_shortcut_lookup``):
        scalar-prefetched shard id selects one slice of the stacked
        views inside the kernel — parity with the flat per-shard
        shortcut lookup, including misses, for every shard."""
        o = _stacked_shards(rng, 4)
        for s in range(4):
            keys = jnp.concatenate([
                o["keys"][s],
                jnp.asarray(unique_keys(rng, 40, lo=2**31, hi=2**32 - 2),
                            jnp.uint32)])
            ref = eh.shortcut_lookup_many(
                o["vks"][s], o["vvs"][s], int(o["vls"][s]), keys)
            got = kmod.stacked_shortcut_lookup(
                keys, o["vks"], o["vvs"], o["vls"], s, tile=128)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_slot_width_mismatch_rejected(self, rng):
        o = _stacked_shards(rng, 2)
        with pytest.raises(ValueError, match="slot widths"):
            kmod.sharded_routed_lookup(
                o["keys"], o["dirs"], o["bks"], o["bvs"], o["gds"],
                o["vks"][:, :, :4], o["vvs"][:, :, :4], o["vls"],
                jnp.zeros(2, jnp.int32), tile=128)


# ---------------------------------------------------------------------------
# The cached sharded index end to end.
# ---------------------------------------------------------------------------

def _count_kernels(monkeypatch):
    """Wrap the three sharded kernel entry points with call counters
    (lookup_batched imports them from the module at call time)."""
    counts = {"trad": 0, "shortcut": 0, "routed": 0}
    for name, attr in (("trad", "sharded_eh_lookup"),
                       ("shortcut", "sharded_shortcut_lookup"),
                       ("routed", "sharded_routed_lookup")):
        orig = getattr(kmod, attr)

        def wrapper(*a, _orig=orig, _name=name, **kw):
            counts[_name] += 1
            return _orig(*a, **kw)

        monkeypatch.setattr(kmod, attr, wrapper)
    return counts


class TestCachedShardedLookup:
    def test_steady_state_hits_cache(self, rng):
        keys = unique_keys(rng, 600)
        vals = np.arange(600, dtype=np.uint32)
        with ShardedShortcutEH(12, 8, 2048, num_shards=4) as idx:
            idx.insert(keys, vals)
            idx.pump()
            np.testing.assert_array_equal(
                np.asarray(idx.lookup_batched(keys)), vals)
            built = idx.operands.stats.rebuilds
            pubs = idx.operands.stats.publish_refreshes
            for _ in range(3):          # unchanged index: zero device work
                np.testing.assert_array_equal(
                    np.asarray(idx.lookup_batched(keys)), vals)
            assert idx.operands.stats.hits >= 3
            assert idx.operands.stats.rebuilds == built
            assert idx.operands.stats.publish_refreshes == pubs
            # THE acceptance invariant: refreshes moved off the lookup
            # path entirely — replays published at write time instead
            assert idx.operands.stats.lookup_refreshes == 0
            assert pubs > 0

    def test_refresh_happens_at_publish_not_lookup(self, rng):
        keys = unique_keys(rng, 600)
        vals = np.arange(600, dtype=np.uint32)
        with ShardedShortcutEH(12, 8, 2048, num_shards=4) as idx:
            idx.insert(keys, vals)
            idx.pump()
            idx.lookup_batched(keys)                  # warm
            # dirty exactly one shard (a single-key insert touches only
            # the owning shard's mapper and state)
            target = unique_keys(rng, 1, lo=2**31, hi=2**32 - 2)
            idx.insert(target, np.asarray([999_999], np.uint32))
            pubs = idx.operands.stats.publish_refreshes
            idx.pump()                                # replay publishes HERE
            assert idx.operands.stats.publish_refreshes > pubs
            out = np.asarray(idx.lookup_batched(
                np.concatenate([keys, target])))
            np.testing.assert_array_equal(out[:-1], vals)
            assert out[-1] == 999_999
            # the lookup itself patched nothing: the slice landed on the
            # mapper thread at publish time, before sc_version moved
            assert idx.operands.stats.lookup_refreshes == 0

    def test_gate_certified_view_never_stale(self, rng):
        """Insert → pump → lookup must see the new key through the
        cached shortcut path: the replay bumped the shard's epoch before
        publishing the version the gate certifies, so the cache cannot
        serve the pre-insert slice."""
        keys = unique_keys(rng, 400)
        vals = np.arange(400, dtype=np.uint32)
        with ShardedShortcutEH(12, 8, 2048, num_shards=2) as idx:
            idx.insert(keys[:200], vals[:200])
            idx.pump()
            idx.lookup_batched(keys[:200])            # warm both families
            for i in range(200, 400, 50):
                idx.insert(keys[i:i + 50], vals[i:i + 50])
                idx.pump()
                assert idx.in_sync()
                got = np.asarray(idx.lookup_batched(keys[:i + 50]))
                np.testing.assert_array_equal(got, vals[:i + 50])

    def test_mixed_gates_resolve_in_one_routed_dispatch(
            self, rng, monkeypatch):
        keys = unique_keys(rng, 800)
        vals = np.arange(800, dtype=np.uint32)
        with ShardedShortcutEH(12, 8, 2048, num_shards=4) as idx:
            idx.insert(keys, vals)
            idx.pump()
            assert idx.in_sync()
            # shards 1 and 2 refuse the shortcut (threshold below any
            # possible fan-in), 0 and 3 accept
            idx.shards[1].fan_in_threshold = -1.0
            idx.shards[2].fan_in_threshold = -1.0
            counts = _count_kernels(monkeypatch)
            misses = unique_keys(rng, 100, lo=2**31, hi=2**32 - 2)
            probe = np.concatenate([keys, misses])
            got = np.asarray(idx.lookup_batched(probe))
            assert counts == {"trad": 0, "shortcut": 0, "routed": 1}, \
                "a mixed-sync group must fuse into ONE routed dispatch"
            expect = np.concatenate(
                [vals, np.full(100, 0xFFFFFFFF, np.uint32)])
            np.testing.assert_array_equal(got, expect)
            # flipping every shard traditional uses the static kernel
            for s in idx.shards:
                s.fan_in_threshold = -1.0
            got = np.asarray(idx.lookup_batched(probe))
            np.testing.assert_array_equal(got, expect)
            assert counts["trad"] == 1 and counts["routed"] == 1

    def test_empty_batch_short_circuits(self, rng, monkeypatch):
        keys = unique_keys(rng, 200)
        with ShardedShortcutEH(12, 8, 2048, num_shards=2) as idx:
            idx.insert(keys, np.arange(200, dtype=np.uint32))
            idx.pump()
            counts = _count_kernels(monkeypatch)
            routed = (idx.routed_shortcut, idx.routed_traditional)
            stats = idx.operands.stats.snapshot()
            out = idx.lookup_batched(np.empty(0, np.uint32))
            assert out.shape == (0,) and out.dtype == jnp.uint32
            out = idx.lookup(np.empty(0, np.uint32))
            assert out.shape == (0,)
            assert sum(counts.values()) == 0          # no dispatch at all
            assert (idx.routed_shortcut, idx.routed_traditional) == routed
            after = idx.operands.stats                # cache untouched
            assert (after.hits, after.rebuilds, after.slice_refreshes) == \
                (stats.hits, stats.rebuilds, stats.slice_refreshes)


class TestKVEmptyBatch:
    def test_get_context_empty_returns_without_device_work(self, rng):
        from repro.kvcache import paged_cache as pc
        from repro.kvcache.shortcut_cache import ShortcutKVManager
        L, nb, bs, KV, hd, max_seqs, cap = 2, 32, 4, 2, 8, 4, 32
        cache = pc.cache_create(L, nb, bs, KV, hd, max_seqs, cap // bs,
                                dtype=jnp.float32)
        with ShortcutKVManager(cache, seq_capacity=cap,
                               num_shards=2) as mgr:
            routed = (mgr.routed_shortcut, mgr.routed_paged)
            k, v, route = mgr.get_context(np.empty(0, np.int64))
            assert k.shape == (L, 0, KV, cap, hd)
            assert v.shape == (L, 0, KV, cap, hd)
            assert route in ("shortcut", "paged")
            assert (mgr.routed_shortcut, mgr.routed_paged) == routed
            # an explicitly requested route is echoed back
            _, _, route = mgr.get_context(np.empty(0, np.int64),
                                          route="shortcut")
            assert route == "shortcut"


# ---------------------------------------------------------------------------
# Cache coherence under concurrent async replays (satellite acceptance:
# randomized parity with mappers publishing mid-stream; no torn stacks;
# a slice older than the gate-certified epoch is never served).
# ---------------------------------------------------------------------------

class TestAsyncCoherence:
    @pytest.mark.parametrize("num_shards", [2, 4])
    def test_randomized_parity_with_publishing_mappers(self, rng,
                                                       num_shards):
        """Inserts are synchronous (authoritative), replays land on the
        mapper threads whenever they land: every batched lookup must
        still read its own writes — the version gate demotes stale
        shards to the traditional path per shard, and any shortcut slice
        the cache serves must be at least as new as the gate certified.
        A torn stack (keys slice from one publication, vals from
        another) or a stale cached slice breaks oracle parity."""
        keys = unique_keys(rng, 900)
        vals = np.arange(900, dtype=np.uint32)
        misses = unique_keys(rng, 120, lo=2**31, hi=2**32 - 2)
        oracle = {}
        idx = ShardedShortcutEH(12, 8, 2048, num_shards=num_shards,
                                async_mapper=True, poll_interval=0.001)
        try:
            step = 90
            for i in range(0, 900, step):
                kb, vb = keys[i:i + step], vals[i:i + step]
                idx.insert(kb, vb)
                oracle.update(zip(kb.tolist(), vb.tolist()))
                probe = np.concatenate([keys[:i + step], misses])
                perm = rng.permutation(probe.size)
                probe = probe[perm]
                expect = np.asarray(
                    [oracle.get(int(k), 0xFFFFFFFF) for k in probe],
                    np.uint32)
                for _ in range(3):      # replays race these lookups
                    got = np.asarray(idx.lookup_batched(probe))
                    np.testing.assert_array_equal(got, expect)
            assert idx.wait_in_sync(timeout=60.0)
            got = np.asarray(idx.lookup_batched(keys))
            np.testing.assert_array_equal(got, vals)
            # the steady-state read after sync is served from cache
            h0 = idx.operands.stats.hits
            np.testing.assert_array_equal(
                np.asarray(idx.lookup_batched(keys)), vals)
            assert idx.operands.stats.hits > h0
        finally:
            idx.close()

    def test_concurrent_readers_share_cache_consistently(self, rng):
        """Two reader threads hammer lookup_batched while the main
        thread inserts and async mappers replay: the cache lock must
        keep every served stack internally consistent (parity holds in
        every reader at every step)."""
        keys = unique_keys(rng, 600)
        vals = np.arange(600, dtype=np.uint32)
        idx = ShardedShortcutEH(12, 8, 2048, num_shards=2,
                                async_mapper=True, poll_interval=0.001)
        idx.insert(keys[:300], vals[:300])
        idx.pump()
        errors = []
        stop = threading.Event()

        def reader(seed):
            r = np.random.default_rng(seed)
            known = keys[:300]
            try:
                while not stop.is_set():
                    probe = r.choice(known, 64)
                    got = np.asarray(idx.lookup_batched(probe))
                    want = np.asarray(
                        [vals[np.nonzero(keys == k)[0][0]] for k in probe],
                        np.uint32)
                    np.testing.assert_array_equal(got, want)
            except Exception as e:      # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=reader, args=(s,))
                   for s in (1, 2)]
        for t in threads:
            t.start()
        try:
            for i in range(300, 600, 60):
                idx.insert(keys[i:i + 60], vals[i:i + 60])
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60.0)
            idx.close()
        assert not errors, errors
