"""The generic shortcut-maintenance runtime (``runtime/mapper.py``):
version monotonicity, create-collapses-updates batching, async/pump
equivalence, routing policies, and EH<->KV client parity."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.shortcut_eh import ShortcutEH
from repro.kvcache import paged_cache as pc
from repro.kvcache.shortcut_cache import ShortcutKVManager
from repro.runtime.mapper import (CREATE, GLOBAL_VIEW, UPDATE,
                                  FanInRouting, FragmentationRouting,
                                  HysteresisRouting, Request, ShortcutMapper)
from repro.runtime.shard_group import MapperGroup

from conftest import unique_keys


class ToyClient:
    """Minimal runtime client: authoritative dict, dict-replica view."""

    def __init__(self, **kw):
        self.data = {}
        self.view = {}
        self.create_calls = 0
        self.update_keys = []
        self.mapper = ShortcutMapper(
            replay_create=self._replay_create,
            replay_update=self._replay_update,
            snapshot=lambda: dict(self.data),
            view_arrays=tuple,
            routing=kw.pop("routing", FanInRouting(8.0)), **kw)

    def put(self, key, val, kind="update"):
        with self.mapper.lock:
            self.data[key] = val
            versions = self.mapper.record([GLOBAL_VIEW])
        if kind == "create":
            self.mapper.submit_create([GLOBAL_VIEW], versions)
        else:
            self.mapper.submit_update([GLOBAL_VIEW], versions,
                                      payload=(key, val))

    def _replay_create(self, snap, requests):
        self.create_calls += 1
        self.view = dict(snap)

    def _replay_update(self, snap, requests):
        for r in requests:
            key, val = r.payload
            self.view[key] = val
            self.update_keys.append(key)


class TestVersioning:
    def test_monotone_and_gated(self):
        t = ToyClient()
        for i in range(3):
            t.put(f"k{i}", i)
            trad, sc = t.mapper.versions(GLOBAL_VIEW)
            assert sc < trad and not t.mapper.in_sync([GLOBAL_VIEW])
            t.mapper.pump()
            trad, sc = t.mapper.versions(GLOBAL_VIEW)
            assert sc == trad == i + 1
            assert t.mapper.in_sync([GLOBAL_VIEW])
        assert t.view == t.data

    def test_publish_never_decreases(self):
        t = ToyClient()
        t.put("a", 1)
        t.put("b", 2)
        t.mapper.pump()
        sc_after = t.mapper.sc_version(GLOBAL_VIEW)
        # a stale request (older version) must not move sc_version back
        t.mapper.submit_update([GLOBAL_VIEW], [1], payload=("a", 1))
        t.mapper.pump()
        assert t.mapper.sc_version(GLOBAL_VIEW) == sc_after

    def test_invalidate_desyncs(self):
        t = ToyClient()
        t.put("a", 1)
        t.mapper.pump()
        assert t.mapper.in_sync([GLOBAL_VIEW])
        with t.mapper.lock:
            t.mapper.invalidate([GLOBAL_VIEW])
        assert not t.mapper.in_sync([GLOBAL_VIEW])
        assert t.mapper.sc_version(GLOBAL_VIEW) == -1


class TestCollapse:
    def test_create_collapses_pending_updates_at_enqueue(self):
        t = ToyClient()
        t.put("a", 1)
        t.put("b", 2)
        t.put("c", 3, kind="create")    # drains + pops the two updates
        assert t.mapper.stats.collapsed == 2
        t.mapper.pump()
        assert t.create_calls == 1
        assert t.update_keys == []      # stale updates never replayed
        assert t.view == {"a": 1, "b": 2, "c": 3}
        assert t.mapper.in_sync([GLOBAL_VIEW])

    def test_batch_side_collapse_catches_races(self):
        """An update that races past the enqueue-time drain (older version,
        behind a create in the FIFO) is dropped by the batch-side rule."""
        t = ToyClient()
        with t.mapper.lock:
            (v1,) = t.mapper.record([GLOBAL_VIEW])
            t.data["x"] = 1
            (v2,) = t.mapper.record([GLOBAL_VIEW])
            t.data["y"] = 2
        t.mapper._queue.put(Request(CREATE, {GLOBAL_VIEW: v2}))
        t.mapper.submit_update([GLOBAL_VIEW], [v1], payload=("x", 1))
        t.mapper.pump()
        assert t.update_keys == []
        assert t.mapper.stats.collapsed == 1
        assert t.mapper.in_sync([GLOBAL_VIEW])

    def test_newer_update_survives_create(self):
        """FIFO order: create, then a *newer* update — both replay, the
        update after the create."""
        t = ToyClient()
        t.put("a", 1, kind="create")
        t.put("b", 2)                   # newer than the create
        t.mapper.pump()
        assert t.create_calls == 1
        assert t.update_keys == ["b"]
        assert t.view == {"a": 1, "b": 2}

    def test_per_key_collapse_is_not_global(self):
        """A create for one view key must not collapse another key's
        pending update (the KV cache relies on this)."""
        t = ToyClient()
        with t.mapper.lock:
            (vs0,) = t.mapper.record(["seq0"])
            (vs1,) = t.mapper.record(["seq1"])
        t.mapper.submit_update(["seq1"], [vs1], payload=("s1", 1))
        t.mapper.submit_create(["seq0"], [vs0])
        assert t.mapper.stats.collapsed == 0
        t.mapper.pump()
        assert t.update_keys == ["s1"]
        assert t.mapper.in_sync(["seq0", "seq1"])


class TestLag:
    """``lag_seconds`` / ``lag_requests``: submit-to-publish time of every
    request, collapsed ones included."""

    @staticmethod
    def _old_update(t, key, val, age_s):
        """Enqueue an update as if it had been submitted ``age_s`` ago."""
        with t.mapper.lock:
            t.data[key] = val
            (v,) = t.mapper.record([GLOBAL_VIEW])
        t.mapper._queue.put(Request(UPDATE, {GLOBAL_VIEW: v}, (key, val),
                                    submitted=time.perf_counter() - age_s))

    def test_one_update_is_one_lagged_request(self):
        t = ToyClient()
        t.put("a", 1)
        assert t.mapper.stats.lag_requests == 0
        t.mapper.pump()
        assert t.mapper.stats.lag_requests == 1
        assert t.mapper.stats.lag_seconds > 0

    def test_lag_runs_from_submit_to_publish(self):
        t = ToyClient()
        self._old_update(t, "a", 1, age_s=5.0)
        t.mapper.pump()
        assert t.mapper.stats.lag_requests == 1
        assert 5.0 <= t.mapper.stats.lag_seconds < 60.0

    def test_collapsed_update_counts_when_its_create_publishes(self):
        t = ToyClient()
        self._old_update(t, "a", 1, age_s=5.0)
        t.put("b", 2, kind="create")      # pops the update at enqueue
        assert t.mapper.stats.collapsed == 1
        assert t.mapper.stats.lag_requests == 0
        t.mapper.pump()
        assert t.update_keys == []
        assert t.mapper.stats.lag_requests == 2
        assert t.mapper.stats.lag_seconds >= 5.0

    def test_batch_side_collapse_is_counted(self):
        t = ToyClient()
        with t.mapper.lock:
            (v1,) = t.mapper.record([GLOBAL_VIEW])
            (v2,) = t.mapper.record([GLOBAL_VIEW])
        t.mapper._queue.put(Request(CREATE, {GLOBAL_VIEW: v2}))
        t.mapper.submit_update([GLOBAL_VIEW], [v1], payload=("x", 1))
        t.mapper.pump()
        assert t.mapper.stats.collapsed == 1
        assert t.mapper.stats.lag_requests == 2

    def test_group_stats_sum_both_counters(self):
        a, b = ToyClient(), ToyClient()
        group = MapperGroup([a.mapper, b.mapper])
        a.put("x", 1)
        b.put("y", 2)
        b.put("z", 3)
        group.pump()
        agg = group.stats
        assert agg.lag_requests == 3
        assert agg.lag_seconds == pytest.approx(
            a.mapper.stats.lag_seconds + b.mapper.stats.lag_seconds)


class TestAsyncEquivalence:
    def test_async_mapper_matches_pump(self, rng):
        """The mapper thread and the synchronous pump() surrogate must
        converge to identical shortcut views."""
        keys = unique_keys(rng, 300)
        vals = np.arange(300, dtype=np.uint32)
        results = {}
        for mode in ("pump", "async"):
            with ShortcutEH(max_global_depth=8, bucket_slots=16,
                            capacity=512, poll_interval=0.003,
                            async_mapper=(mode == "async")) as sc:
                for i in range(0, 300, 60):
                    sc.insert(keys[i:i + 60], vals[i:i + 60])
                if mode == "pump":
                    sc.pump()
                assert sc.wait_in_sync(timeout=30.0)
                results[mode] = (np.array(sc.view_keys),
                                 np.array(sc.view_vals),
                                 sc.versions())
        np.testing.assert_array_equal(results["pump"][0],
                                      results["async"][0])
        np.testing.assert_array_equal(results["pump"][1],
                                      results["async"][1])
        assert results["pump"][2] == results["async"][2]


class FailingClient(ToyClient):
    def _replay_update(self, snap, requests):
        raise ValueError("replay broke")


class TestThreadFailure:
    def test_replay_exception_reraised_in_caller(self):
        """A replay that raises on the mapper thread ends that thread;
        pump, wait_in_sync and close re-raise it in the caller instead
        of leaving reads on the traditional path without a sign."""
        t = FailingClient(async_mapper=True, poll_interval=0.001)
        t.put("a", 1)
        with pytest.raises(RuntimeError, match="replay broke") as err:
            t.mapper.wait_in_sync([GLOBAL_VIEW], timeout=30.0)
        assert isinstance(err.value.__cause__, ValueError)
        assert not t.mapper.in_sync([GLOBAL_VIEW])
        with pytest.raises(RuntimeError, match="replay broke"):
            t.mapper.pump()
        with pytest.raises(RuntimeError, match="replay broke"):
            t.mapper.close()

    def test_group_close_closes_every_member_then_raises(self):
        bad = FailingClient(async_mapper=True, poll_interval=0.001)
        good = ToyClient(async_mapper=True, poll_interval=0.001)
        group = MapperGroup([bad.mapper, good.mapper])
        bad.put("a", 1)
        good.put("b", 2)
        with pytest.raises(RuntimeError, match="replay broke"):
            group.wait_in_sync(timeout=30.0)
        assert good.mapper.wait_in_sync(timeout=30.0)
        with pytest.raises(RuntimeError, match="replay broke"):
            group.close()
        assert good.mapper._thread is None


class TestRoutingPolicies:
    def test_fan_in_flips_at_threshold(self):
        pol = FanInRouting(8.0)
        assert pol.decide(8.0) and pol.decide(1.0)
        assert not pol.decide(8.0 + 1e-9)

    def test_fragmentation_flips_at_threshold(self):
        pol = FragmentationRouting(0.25)
        assert pol.decide(0.25) and pol.decide(1.0)
        assert not pol.decide(0.25 - 1e-9)

    def test_hysteresis_holds_between_bands(self):
        pol = HysteresisRouting(FanInRouting(6.0), FanInRouting(10.0))
        assert not pol.decide(7.0)      # never engaged, above enter band
        assert pol.decide(5.0)          # engages
        assert pol.decide(9.0)          # holds inside the band
        assert not pol.decide(11.0)     # disengages past exit
        assert not pol.decide(9.0)      # and stays off inside the band

    def test_gate_requires_sync_and_policy(self):
        t = ToyClient(routing=FanInRouting(8.0))
        t.put("a", 1)
        assert not t.mapper.gate(1.0, [GLOBAL_VIEW])   # out of sync
        t.mapper.pump()
        assert t.mapper.gate(1.0, [GLOBAL_VIEW])
        assert not t.mapper.gate(9.0, [GLOBAL_VIEW])   # policy refuses

    def test_eh_accepts_custom_routing(self, rng):
        keys = unique_keys(rng, 50)
        sc = ShortcutEH(max_global_depth=8, bucket_slots=64, capacity=128,
                        routing=HysteresisRouting(FanInRouting(6.0),
                                                  FanInRouting(10.0)))
        sc.insert(keys, np.arange(50, dtype=np.uint32))
        sc.pump()
        out = np.asarray(sc.lookup(keys))
        np.testing.assert_array_equal(out, np.arange(50, dtype=np.uint32))
        assert sc.fan_in_threshold is None   # no scalar threshold to report
        with pytest.raises(AttributeError):
            sc.fan_in_threshold = 4.0


class TestClientParity:
    """EH and KV drive the SAME runtime class and must show identical
    maintenance semantics: stale until pumped, in sync after, shortcut
    and fallback reads agree."""

    def test_same_runtime_class(self, rng):
        sc = ShortcutEH(max_global_depth=8, bucket_slots=16, capacity=64)
        cache = pc.cache_create(2, 64, 4, 2, 8, 4, 16, dtype=jnp.float32)
        mgr = ShortcutKVManager(cache, seq_capacity=64)
        assert type(sc.mapper) is ShortcutMapper
        assert type(mgr.mapper) is ShortcutMapper

    def test_parity_stale_then_sync_then_agree(self, rng):
        # EH client
        keys = unique_keys(rng, 120)
        sc = ShortcutEH(max_global_depth=8, bucket_slots=16, capacity=256)
        sc.insert(keys, np.arange(120, dtype=np.uint32))
        eh_stale = not sc.in_sync()
        sc.pump()
        from repro.core import extendible_hashing as eh
        trad = np.asarray(eh.eh_lookup_many(sc.state, jnp.asarray(keys)))
        short = np.asarray(eh.shortcut_lookup_many(
            sc.view_keys, sc.view_vals, sc.state.global_depth,
            jnp.asarray(keys)))
        # KV client
        cache = pc.cache_create(2, 64, 4, 2, 8, 4, 16, dtype=jnp.float32)
        mgr = ShortcutKVManager(cache, seq_capacity=64)
        k = jnp.asarray(rng.normal(size=(2, 2, 8, 2, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 2, 8, 2, 8)).astype(np.float32))
        mgr.prefill(np.array([0, 1]), k, v)
        kv_stale = not mgr.in_sync(np.array([0, 1]))
        mgr.pump()
        kp, vp, _ = mgr.get_context(np.array([0, 1]), route="paged")
        ks, vs, _ = mgr.get_context(np.array([0, 1]), route="shortcut")

        assert eh_stale and kv_stale           # parity: async by default
        assert sc.in_sync() and mgr.in_sync(np.array([0, 1]))
        np.testing.assert_array_equal(trad, short)
        sl = int(mgr.seq_lens(np.array([0]))[0])
        np.testing.assert_allclose(np.asarray(kp[:, :, :, :sl]),
                                   np.asarray(ks[:, :, :, :sl]))
        np.testing.assert_allclose(np.asarray(vp[:, :, :, :sl]),
                                   np.asarray(vs[:, :, :, :sl]))
        # both published their maintenance through the runtime stats
        assert sc.mapper.stats.creates + sc.mapper.stats.updates >= 1
        assert mgr.mapper.stats.creates >= 1
