"""Sharded shortcut runtime (core/sharded_eh + runtime/shard_group):
oracle parity across shard counts, per-shard invariants, shard-local
maintenance, and MapperGroup independence."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import extendible_hashing as eh
from repro.core import hashing
from repro.core.sharded_eh import (ShardedShortcutEH, partition_by_shard,
                                   shard_of_keys)
from repro.runtime.mapper import (GLOBAL_VIEW, FanInRouting,
                                  ShortcutMapper)
from repro.runtime.shard_group import MapperGroup

from conftest import unique_keys


def _mixed_trace(rng, n=1200):
    """Mixed insert/probe trace: bursts of inserts interleaved with
    probes of everything seen so far plus guaranteed misses."""
    keys = unique_keys(rng, n)
    vals = np.arange(n, dtype=np.uint32)
    misses = unique_keys(rng, 200, lo=2**31, hi=2**32 - 2)
    return keys, vals, misses


def _keys_for_shard(rng, shard, shard_bits, n):
    """Rejection-sample keys whose hash routes them to ``shard``."""
    out = []
    while len(out) < n:
        cand = unique_keys(rng, 4 * n)
        cand = cand[shard_of_keys(cand, shard_bits) == shard]
        out.extend(cand.tolist())
    return np.unique(np.asarray(out[:n], np.uint32))


class TestOracleParity:
    @pytest.mark.parametrize("num_shards", [1, 2, 8])
    def test_matches_dict_and_flat_index(self, rng, num_shards):
        """Same trace through a dict oracle, the flat (N=1) index, and
        the sharded index: results bit-for-bit identical at every step."""
        keys, vals, misses = _mixed_trace(rng)
        oracle = {}
        flat = ShardedShortcutEH(12, 8, 2048, num_shards=1)
        sharded = ShardedShortcutEH(12, 8, 2048, num_shards=num_shards)
        step = 300
        for i in range(0, len(keys), step):
            kb, vb = keys[i:i + step], vals[i:i + step]
            oracle.update(zip(kb.tolist(), vb.tolist()))
            flat.insert(kb, vb)
            sharded.insert(kb, vb)
            # probe BEFORE maintenance (stale views): traditional routes
            probe = np.concatenate([keys[:i + step], misses])
            got = np.asarray(sharded.lookup_batched(probe))
            want = np.asarray(flat.lookup_batched(probe))
            np.testing.assert_array_equal(got, want)
            flat.pump()
            sharded.pump()
            assert sharded.in_sync()
            # probe AFTER maintenance (shortcut-eligible routes)
            got = np.asarray(sharded.lookup_batched(probe))
            np.testing.assert_array_equal(got, np.asarray(
                flat.lookup_batched(probe)))
            expect = np.asarray(
                [oracle.get(int(k), 0xFFFFFFFF) for k in probe], np.uint32)
            np.testing.assert_array_equal(got, expect)
        flat.close()
        sharded.close()

    @pytest.mark.parametrize("num_shards", [2, 8])
    def test_batched_kernel_path_matches(self, rng, num_shards):
        keys, vals, misses = _mixed_trace(rng, n=900)
        sharded = ShardedShortcutEH(12, 8, 2048, num_shards=num_shards)
        sharded.insert(keys, vals)
        probe = np.concatenate([keys, misses])
        # stale: traditional fused kernel resolves all shards, each as
        # the jnp reference does on that shard's state
        got = np.asarray(sharded.lookup_batched(probe))
        sid = sharded.shard_of(probe)
        want = np.empty_like(got)
        for s, shard in enumerate(sharded.shards):
            want[sid == s] = np.asarray(eh.eh_lookup_many(
                shard.state, jnp.asarray(probe[sid == s])))
        np.testing.assert_array_equal(got, want)
        sharded.pump()
        # in sync: shortcut fused kernel (when views are shape-uniform)
        got = np.asarray(sharded.lookup_batched(probe))
        expect = np.concatenate([vals, np.full(len(misses), 0xFFFFFFFF,
                                               np.uint32)])
        np.testing.assert_array_equal(got, expect)
        sharded.close()


class TestInsertCounters:
    """``keys_in_place`` / ``keys_scanned``: how ``eh_insert_many`` took
    each inserted key (present keys overwritten in place, absent ones run
    through the sequential loop), on one shard and summed over shards."""

    @pytest.mark.parametrize("batch", ["present", "fresh", "mixed"])
    @pytest.mark.parametrize("num_shards", [1, 4])
    def test_counts_follow_presence(self, rng, batch, num_shards):
        idx = ShardedShortcutEH(10, 8, 1024, num_shards=num_shards)
        keys = unique_keys(rng, 400)
        idx.insert(keys[:200], np.arange(200, dtype=np.uint32))
        assert (idx.keys_in_place, idx.keys_scanned) == (0, 200)
        kb = {"present": rng.choice(keys[:200], 300),   # with repeats
              "fresh": keys[200:],
              "mixed": np.concatenate([keys[150:250], keys[150:250]])}[batch]
        idx.insert(kb, np.ones(kb.size, np.uint32))
        fresh = int(np.isin(kb, keys[200:]).sum())
        assert idx.keys_scanned == 200 + fresh
        assert idx.keys_in_place == kb.size - fresh
        assert idx.keys_in_place == sum(s.keys_in_place for s in idx.shards)
        assert idx.keys_scanned == sum(s.keys_scanned for s in idx.shards)
        if num_shards > 1:
            assert sum(s.keys_scanned > 0 for s in idx.shards) > 1
        idx.close()


class TestTouchedBuckets:
    """``ShortcutEH.insert`` hands ``submit_update`` the touched set
    ``eh_insert_many`` returns: the one the host used to find by hashing
    the batch again against the new state."""

    @staticmethod
    def _host_touched(state, keys, bits):
        words = jnp.asarray(keys if bits == 32 else
                            hashing.split_words(keys))
        slots = eh.dir_slot(eh.hash_dir(hashing.words(words, bits // 32)),
                            state.global_depth)
        return np.unique(np.asarray(state.directory[slots]))

    def _spy(self, shard, bits, got, want):
        insert, submit = shard.insert, shard.mapper.submit_update
        last = {}

        def spied_insert(keys, values):
            last["keys"] = keys
            insert(keys, values)

        def spied_submit(views, versions, payload=None):
            got.append(payload)
            want.append(self._host_touched(shard.state, last["keys"], bits))
            submit(views, versions, payload=payload)

        shard.insert = spied_insert
        shard.mapper.submit_update = spied_submit

    @pytest.mark.parametrize("bits", [32, 64])
    @pytest.mark.parametrize("num_shards", [1, 4])
    def test_payload_and_counter(self, rng, bits, num_shards):
        kt = hashing.word_type(bits)
        keys = np.unique(rng.integers(1, 2**bits - 2, 1200, dtype=kt,
                                      endpoint=False))[:500]
        keys = rng.permutation(keys)
        got, want = [], []
        with ShardedShortcutEH(10, 8, 1024, num_shards=num_shards,
                               key_bits=bits, value_bits=bits) as idx:
            for shard in idx.shards:
                self._spy(shard, bits, got, want)
            table = {}
            batches = [keys[:300],                          # load
                       rng.choice(keys[:300], 400),         # present
                       np.concatenate([rng.choice(keys[:300], 100),
                                       keys[300:]])]        # mixed
            for batch in batches:
                vals = rng.integers(0, 2**bits - 1, batch.size, dtype=kt)
                idx.insert(batch, vals)
                table.update(zip(batch.tolist(), vals.tolist()))
                idx.pump()
                assert idx.in_sync()
            assert len(got) >= num_shards          # the present batch
            for g, w in zip(got, want):
                assert g.dtype == np.int32
                np.testing.assert_array_equal(g, w)
            assert idx.buckets_touched == sum(g.size for g in got)
            assert idx.buckets_touched == sum(s.buckets_touched
                                              for s in idx.shards)
            probe = np.concatenate([keys, rng.integers(
                2**(bits - 1), 2**bits - 2, 100, dtype=kt)])
            miss = hashing.all_ones(bits)
            expect = np.asarray([table.get(int(k), miss) for k in probe],
                                kt)
            for threshold in (math.inf, 0.0):      # shortcut, traditional
                for shard in idx.shards:
                    shard.fan_in_threshold = threshold
                sc0 = idx.routed_shortcut
                np.testing.assert_array_equal(
                    np.asarray(idx.lookup_batched(probe)), expect)
                assert (idx.routed_shortcut > sc0) == (threshold > 0)


class TestShardLocality:
    @pytest.mark.parametrize("num_shards", [2, 8])
    def test_per_shard_invariants(self, rng, num_shards):
        keys, vals, _ = _mixed_trace(rng)
        with ShardedShortcutEH(12, 8, 2048,
                               num_shards=num_shards) as sharded:
            for i in range(0, len(keys), 150):  # small batches: splits
                sharded.insert(keys[i:i + 150], vals[i:i + 150])
            sharded.pump()
            report = sharded.check_invariants()   # I1-I5 + S1 per shard
            assert report["ok"], report["errors"]
            assert len(report["shards"]) == num_shards
            total = sharded.num_entries()
            assert total == len(keys)

    def test_maintenance_confined_to_owning_shard(self, rng):
        """Inserts routed to shard 0 must not touch shard 1's versions,
        queue, or MaintenanceStats (the paper's §5 shootdown cost,
        confined)."""
        shard_bits = 1
        k0 = _keys_for_shard(rng, 0, shard_bits, 300)
        with ShardedShortcutEH(10, 8, 1024, num_shards=2) as sharded:
            sharded.insert(k0, np.arange(len(k0), dtype=np.uint32))
            s0, s1 = sharded.per_shard_stats()
            m0, m1 = sharded.group[0], sharded.group[1]
            assert m0.trad_version(GLOBAL_VIEW) > 0
            assert m1.trad_version(GLOBAL_VIEW) == 0   # never bumped
            sharded.pump()
            assert (s0.creates + s0.updates) >= 1
            assert s1.creates == s1.updates == 0       # no replay at all
            assert s1.slots_remapped == 0
            # lookups for shard-0 keys are correct and shard 1 untouched
            out = np.asarray(sharded.lookup_batched(k0))
            np.testing.assert_array_equal(
                out, np.arange(len(k0), dtype=np.uint32))


class _Toy:
    """Minimal per-shard runtime client (mirrors test_mapper.ToyClient)."""

    def __init__(self):
        self.data = {}
        self.view = {}
        self.mapper = ShortcutMapper(
            replay_create=lambda snap, reqs: self.view.update(snap),
            replay_update=self._replay_update,
            snapshot=lambda: dict(self.data),
            view_arrays=tuple, routing=FanInRouting(8.0))

    def _replay_update(self, snap, requests):
        for r in requests:
            k, v = r.payload
            self.view[k] = v

    def put(self, key, val, kind="update"):
        with self.mapper.lock:
            self.data[key] = val
            versions = self.mapper.record([GLOBAL_VIEW])
        if kind == "create":
            self.mapper.submit_create([GLOBAL_VIEW], versions)
        else:
            self.mapper.submit_update([GLOBAL_VIEW], versions,
                                      payload=(key, val))


class TestMapperGroup:
    def test_create_does_not_collapse_other_shards_updates(self):
        """The collapse scope is one shard: a create on shard 0 leaves
        shard 1's pending updates alone, and shard 0's staleness does
        not gate shard 1's reads."""
        toys = [_Toy(), _Toy()]
        group = MapperGroup([t.mapper for t in toys],
                            router=lambda k: int(k) % 2)
        toys[1].put(3, "b")                      # pending update, shard 1
        toys[0].put(0, "a", kind="create")       # create, shard 0
        assert group[0].stats.collapsed == 0
        assert group[1].stats.collapsed == 0     # NOT collapsed cross-shard
        # shard 1 can catch up independently of shard 0
        group[1].pump()
        assert group.in_sync({1: [GLOBAL_VIEW]})
        assert not group.in_sync({0: [GLOBAL_VIEW]})
        assert not group.in_sync()               # group-wide gate still down
        assert toys[1].view == {3: "b"}
        group.pump()
        assert group.in_sync()
        assert toys[0].view == {0: "a"}

    def test_aggregated_stats_and_route_counts(self):
        toys = [_Toy(), _Toy(), _Toy()]
        group = MapperGroup([t.mapper for t in toys],
                            router=lambda k: int(k) % 3)
        for i in range(6):
            toys[i % 3].put(i, i)
        assert group.pump() == 6
        agg = group.stats
        assert agg.updates == sum(t.mapper.stats.updates for t in toys) >= 3
        group.count_route(True)                # batch-level: group counter
        group.count_route(False, shard=2)      # shard-attributed
        assert group.routed_shortcut == 1 and group.routed_fallback == 1
        assert group[2].routed_fallback == 1
        # a batch-level decision must NOT skew any member's stats
        # (the old default credited every multi-shard batch to shard 0)
        assert all(m.routed_shortcut == 0 for m in group)

    def test_router_bounds_checked(self):
        group = MapperGroup([_Toy().mapper], router=lambda k: 5)
        with pytest.raises(IndexError):
            group.route("anything")
        with pytest.raises(ValueError):
            MapperGroup([])

    def test_gate_requires_every_involved_policy(self):
        toys = [_Toy(), _Toy()]
        group = MapperGroup([t.mapper for t in toys])
        toys[0].put(0, "a")
        toys[1].put(1, "b")
        group.pump()
        group[1].threshold = 0.5       # shard 1's policy now refuses 1.0
        assert group.gate(1.0, {0: [GLOBAL_VIEW]})
        assert not group.gate(1.0, {0: [GLOBAL_VIEW], 1: [GLOBAL_VIEW]})


class TestPartition:
    def test_partition_roundtrip(self, rng):
        keys = unique_keys(rng, 500)
        sid = shard_of_keys(keys, 2)
        cap = int(np.bincount(sid, minlength=4).max())
        padded, counts, order, rank = partition_by_shard(keys, sid, 4, cap)
        assert counts.sum() == keys.size
        # every key sits in its shard's row, and scatter-back restores it
        out = np.empty(keys.size, keys.dtype)
        out[order] = padded[sid[order], rank]
        np.testing.assert_array_equal(out, keys)
        for s in range(4):
            row = padded[s, :counts[s]]
            assert (shard_of_keys(row, 2) == s).all()

    def test_shard_of_matches_directory_msb(self, rng):
        """Shard routing IS the directory's MSB rule: shard bits are the
        top bits of hash_dir, so the shard partition refines the flat
        directory partition."""
        keys = unique_keys(rng, 256)
        h = np.asarray(eh.hash_dir(jnp.asarray(keys)))
        np.testing.assert_array_equal(
            shard_of_keys(keys, 3), (h >> np.uint32(29)).astype(np.int64))


class TestShardedKV:
    def test_sharded_manager_matches_paged(self, rng):
        """num_shards=2 KV manager: parity with the paged path and
        shard-independent sync (a prefill on shard-0 seqs does not gate
        shard-1 seqs)."""
        from repro.kvcache import paged_cache as pc
        from repro.kvcache.shortcut_cache import ShortcutKVManager
        L, nb, bs, KV, hd, max_seqs, cap = 2, 32, 4, 2, 8, 4, 32
        cache = pc.cache_create(L, nb, bs, KV, hd, max_seqs, cap // bs,
                                dtype=jnp.float32)
        mgr = ShortcutKVManager(cache, seq_capacity=cap, num_shards=2)
        T = 12
        k = jnp.asarray(rng.normal(size=(L, 2, T, KV, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(L, 2, T, KV, hd)), jnp.float32)
        mgr.prefill(np.asarray([1, 3]), k, v)      # both shard 1 (odd)
        mgr.pump()
        assert mgr.in_sync(np.asarray([1, 3]))
        shard1_creates = mgr.group[1].stats.creates
        assert shard1_creates >= 1
        mgr.prefill(np.asarray([0, 2]), k, v)      # both shard 0 (even)
        assert not mgr.in_sync(np.asarray([0, 2]))   # shard 0 stale...
        assert mgr.in_sync(np.asarray([1, 3]))       # ...shard 1 not gated
        assert mgr.group[0].trad_version(1) == 0     # seq 1 not on shard 0
        mgr.pump()
        assert mgr.in_sync(np.asarray([0, 2]))
        # parity of both access paths after sync
        ks, vs, route = mgr.get_context(np.asarray([0, 2]),
                                        route="shortcut")
        kp, vp, _ = mgr.get_context(np.asarray([0, 2]), route="paged")
        np.testing.assert_allclose(np.asarray(ks)[:, :, :, :T],
                                   np.asarray(kp)[:, :, :, :T],
                                   rtol=0, atol=0)
        # shard-0 maintenance stayed on shard 0's mapper: shard 1's
        # replay count did not move when shard 0 caught up
        assert mgr.group[0].stats.creates >= 1
        assert mgr.group[1].stats.creates == shard1_creates
        mgr.close()
