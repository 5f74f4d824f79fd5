"""The paper's §4.2 baselines (HT / HTI / CH) against a dict oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, never hard-fail
from hypothesis import given, settings, strategies as st

from repro.core import baselines as bl

from conftest import unique_keys

MISS = 0xFFFFFFFF


class TestHT:
    def test_roundtrip_with_rehash(self, rng):
        keys = unique_keys(rng, 400)
        vals = np.arange(400, dtype=np.uint32)
        state = bl.ht_create(max_size_log2=12, initial_size_log2=4)
        state = bl.ht_insert_many(state, jnp.asarray(keys),
                                  jnp.asarray(vals))
        assert int(state.dropped) == 0
        assert int(state.size_log2) > 4  # rehashed at least once
        out = np.asarray(bl.ht_lookup_many(state, jnp.asarray(keys)))
        np.testing.assert_array_equal(out, vals)

    def test_misses(self, rng):
        keys = unique_keys(rng, 100)
        state = bl.ht_create(max_size_log2=12)
        state = bl.ht_insert_many(state, jnp.asarray(keys[:50]),
                                  jnp.asarray(np.arange(50, dtype=np.uint32)))
        out = np.asarray(bl.ht_lookup_many(state, jnp.asarray(keys[50:])))
        assert (out == MISS).all()


class TestHTI:
    def test_roundtrip_through_migration(self, rng):
        keys = unique_keys(rng, 600)
        vals = np.arange(600, dtype=np.uint32)
        state = bl.hti_create(max_size_log2=13, initial_size_log2=4)
        # insert in small batches so lookups hit mid-migration states
        for i in range(0, 600, 60):
            state = bl.hti_insert_many(
                state, jnp.asarray(keys[i:i + 60]),
                jnp.asarray(vals[i:i + 60]), migrate_batch=16)
            out = np.asarray(bl.hti_lookup_many(
                state, jnp.asarray(keys[:i + 60])))
            np.testing.assert_array_equal(out, vals[:i + 60])
        assert int(state.dropped) == 0

    def test_migration_completes(self, rng):
        keys = unique_keys(rng, 300)
        state = bl.hti_create(max_size_log2=12, initial_size_log2=4)
        state = bl.hti_insert_many(state, jnp.asarray(keys),
                                   jnp.asarray(np.arange(300, dtype=np.uint32)),
                                   migrate_batch=64)
        # keep inserting nothing; drive migration with repeat lookups?
        # migration advances on insert; a drained state has old_count==0
        # after enough batches:
        state = bl.hti_insert_many(state, jnp.asarray(keys[:1]),
                                   jnp.asarray(np.zeros(1, np.uint32)),
                                   migrate_batch=1 << 12)
        assert not bool(state.migrating)
        assert int(state.old_count) == 0


class TestCH:
    def test_roundtrip_with_chains(self, rng):
        keys = unique_keys(rng, 500)
        vals = np.arange(500, dtype=np.uint32)
        # tiny table -> long chains
        state = bl.ch_create(table_log2=4, capacity=256, bucket_slots=8)
        state = bl.ch_insert_many(state, jnp.asarray(keys),
                                  jnp.asarray(vals))
        assert int(state.dropped) == 0
        out = np.asarray(bl.ch_lookup_many(state, jnp.asarray(keys)))
        np.testing.assert_array_equal(out, vals)
        assert int(state.num_buckets) > 16  # chains actually formed


class TestCrossOracle:
    @settings(deadline=None, max_examples=10)
    @given(st.lists(st.integers(min_value=1, max_value=2**31 - 1),
                    min_size=1, max_size=150, unique=True))
    def test_all_tables_agree(self, keys):
        """HT, HTI, CH, EH answer identically for any key set."""
        from repro.core import extendible_hashing as eh
        keys = np.asarray(keys, np.uint32)
        vals = np.arange(len(keys), dtype=np.uint32)
        kj, vj = jnp.asarray(keys), jnp.asarray(vals)
        ht = bl.ht_insert_many(bl.ht_create(12), kj, vj)
        hti = bl.hti_insert_many(bl.hti_create(12), kj, vj)
        ch = bl.ch_insert_many(bl.ch_create(6, 512), kj, vj)
        ehs, _, _ = eh.eh_insert_many(
            eh.eh_create(10, 8, 1024), kj, vj)
        a = np.asarray(bl.ht_lookup_many(ht, kj))
        b = np.asarray(bl.hti_lookup_many(hti, kj))
        c = np.asarray(bl.ch_lookup_many(ch, kj))
        d = np.asarray(eh.eh_lookup_many(ehs, kj))
        np.testing.assert_array_equal(a, vals)
        np.testing.assert_array_equal(b, vals)
        np.testing.assert_array_equal(c, vals)
        np.testing.assert_array_equal(d, vals)
