"""64-bit keys and values through the whole Shortcut-EH path, against a
plain reference: a dict of Python ints, filled from seeded random
uint64 keys and values whose 32-bit words are never 0 or all ones.

Covered at 64 bits: ``eh_insert_many`` (fresh keys through splits and
directory doublings, then overwrites with the last occurrence winning),
``ShortcutEH``, ``ShardedShortcutEH`` at one and two shards on both
forced routes of ``lookup_batched``, the Pallas kernels in interpret
mode against their XLA twin, and keys that share one word with a stored
key (each must read the miss marker).  At 32 bits one test pins results
and state to what they were before wide keys."""
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import extendible_hashing as eh
from repro.core import hashing
from repro.core.sharded_eh import ShardedShortcutEH
from repro.core.shortcut_eh import ShortcutEH
from repro.kernels import eh_lookup as kernels
from repro.kernels import ref

MISS64 = 2**64 - 1
LOW = np.uint64(0xFFFFFFFF)
# one geometry and batches of 400 and 200 pairs throughout, so that
# the tests share their compiled programs
GEOMETRY = (9, 8, 512)          # max global depth, slots, capacity


def wide(rng, n, bits=64):
    """``n`` distinct keys (or values) of ``bits`` bits, every 32-bit
    word in [1, 2**32 - 2]."""
    out = np.zeros(0, np.uint64)
    while out.size < n:
        w = rng.integers(1, 2**32 - 1, (bits // 32, 2 * n), dtype=np.uint64)
        k = w[0] if bits == 32 else (w[0] << np.uint64(32)) | w[1]
        out = np.unique(np.concatenate([out, k]))
    return rng.permutation(out)[:n].astype(hashing.word_type(bits))


def miss_probe(rng, stored):
    """Never-stored keys, half sharing their low word with a stored key,
    half their high word."""
    pick = rng.choice(stored, 64)
    fresh = wide(rng, 64, 32).astype(np.uint64)
    probe = np.concatenate([(pick[:32] & LOW) | (fresh[:32] << np.uint64(32)),
                            (pick[32:] & ~LOW) | fresh[32:]])
    return probe[~np.isin(probe, stored)]


def expect(table, keys):
    return np.asarray([table.get(int(k), MISS64) for k in keys], np.uint64)


def device_words(a):
    return jnp.asarray(hashing.split_words(a))


@pytest.fixture(scope="module")
def loaded():
    """A 64-bit state loaded with fresh keys through splits and
    doublings, then a batch of overwrites with repeats and a few fresh
    keys; the dict that matches it; and the base state."""
    rng = np.random.default_rng(64)
    keys = wide(rng, 450)
    vals = wide(rng, 450)
    base = eh.eh_create(*GEOMETRY, key_bits=64, value_bits=64)
    st, fresh, _ = eh.eh_insert_many(base, device_words(keys[:400]),
                                     device_words(vals[:400]))
    assert int(fresh) == 400
    table = dict(zip(keys[:400].tolist(), vals[:400].tolist()))
    batch = np.concatenate([rng.choice(keys[:400], 150), keys[400:]])
    batch[rng.choice(150, 30, replace=False)] = keys[0]          # repeats
    bvals = rng.integers(0, MISS64, batch.size, dtype=np.uint64)
    mid = st
    st, fresh, _ = eh.eh_insert_many(st, device_words(batch),
                                     device_words(bvals))
    assert int(fresh) == 50
    table.update(zip(batch.tolist(), bvals.tolist()))
    return rng, keys, table, base, mid, st, batch, bvals


class TestInsertMany64:
    def test_grows_and_matches_the_dict(self, loaded):
        rng, keys, table, base, _, st, _, _ = loaded
        assert int(st.global_depth) >= 6 and int(st.num_buckets) > 50
        assert int(st.dropped) == 0
        assert int(eh.eh_num_entries(st)) == len(table)
        report = eh.check_invariants(st)
        assert report["ok"], report["errors"]
        probe = np.concatenate([keys, miss_probe(rng, keys)])
        got = hashing.join_words(np.asarray(
            eh.eh_lookup_many(st, device_words(probe))))
        np.testing.assert_array_equal(got, expect(table, probe))

    def test_equals_the_sequential_insert(self, loaded):
        """The overwrite pass and the fresh loop give the state one
        ``eh_insert`` per pair in batch order gives."""
        _, _, _, _, mid, st, batch, bvals = loaded

        @jax.jit
        def sequential(s, k, v):
            return jax.lax.scan(lambda s, kv: (eh.eh_insert(s, *kv), None),
                                s, (k.T, v.T))[0]

        want = sequential(mid, device_words(batch), device_words(bvals))
        for name, a, b in zip(eh.EHState._fields, st, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)

    def test_shortcut_view_answers_alike(self, loaded):
        rng, keys, table, _, _, st, _, _ = loaded
        vk, vv = eh.compose_shortcut(st, 1 << int(st.global_depth))
        probe = np.concatenate([keys, miss_probe(rng, keys)])
        got = eh.shortcut_lookup_many(vk, vv, st.global_depth,
                                      device_words(probe))
        np.testing.assert_array_equal(hashing.join_words(np.asarray(got)),
                                      expect(table, probe))


class TestKernels64:
    def test_kernels_match_their_xla_twin(self, loaded):
        """Traditional, shortcut and (two-shard, mixed) routed kernels
        in interpret mode against ``kernels/ref``, misses included."""
        rng, keys, table, _, _, st, _, _ = loaded
        probe = np.concatenate([keys, miss_probe(rng, keys)])
        pw = device_words(probe)
        D = 1 << int(st.global_depth)
        vk, vv = eh.compose_shortcut(st, D)
        want = ref.eh_lookup_ref(pw, st.directory[:D], st.bucket_keys,
                                 st.bucket_vals, st.global_depth)
        np.testing.assert_array_equal(hashing.join_words(np.asarray(want)),
                                      expect(table, probe))
        np.testing.assert_array_equal(
            np.asarray(ref.shortcut_lookup_ref(pw, vk, vv, st.global_depth)),
            np.asarray(want))
        trad = kernels.eh_lookup(pw, st.directory[:D], st.bucket_keys,
                                 st.bucket_vals, st.global_depth, tile=128)
        short = kernels.shortcut_lookup(pw, vk, vv, st.global_depth,
                                        tile=128)
        np.testing.assert_array_equal(np.asarray(trad), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(short), np.asarray(want))
        two = lambda a: jnp.stack([a, a])                     # noqa: E731
        routed = kernels.sharded_routed_lookup(
            two(pw), two(st.directory), two(st.bucket_keys),
            two(st.bucket_vals), two(st.global_depth), two(vk), two(vv),
            two(st.global_depth), jnp.asarray([1, 0], jnp.int32), tile=128)
        for s in range(2):
            np.testing.assert_array_equal(np.asarray(routed[s]),
                                          np.asarray(want))

    def test_rank_mask_probe_compares_both_words(self, rng):
        """``hashing.probe_rows`` at two words answers as the gather
        probe does on rows where one word of a key often matches and the
        other does not, with ghosts past an EMPTY and full rows."""
        T, S = 512, 16
        keys = rng.integers(0, 3, (2, T), dtype=np.uint32)   # tiny alphabet
        row_k = rng.integers(0, 3, (T, 2 * S), dtype=np.uint32)
        empty = rng.random((T, S)) < 0.15
        row_k[:, :S][empty] = hashing.EMPTY_SENTINEL
        row_k[:, S:][empty] = hashing.EMPTY_SENTINEL
        row_v = rng.integers(0, 2**32, (T, 2 * S), dtype=np.uint32)

        def gather_probe(rk, rv, key):
            key = (key[0], key[1])
            pos = hashing.probe_positions(key, S)
            found, j = hashing.probe_hit((rk[pos], rk[S + pos]), key)
            return found, jnp.stack([jnp.where(found, rv[w * S + pos[j]],
                                               jnp.uint32(0))
                                     for w in range(2)])

        want_f, want_v = jax.vmap(gather_probe)(
            jnp.asarray(row_k), jnp.asarray(row_v), jnp.asarray(keys.T))
        found, value = hashing.probe_rows(
            jnp.asarray(row_k), jnp.asarray(row_v),
            tuple(jnp.asarray(k)[:, None] for k in keys))
        np.testing.assert_array_equal(np.asarray(found[:, 0]),
                                      np.asarray(want_f))
        for w in range(2):
            np.testing.assert_array_equal(np.asarray(value[w][:, 0]),
                                          np.asarray(want_v[:, w]))
        assert 0 < int(want_f.sum()) < T


def _routes(idx, route):
    for shard in idx.shards:
        shard.fan_in_threshold = {"traditional": 0.0,
                                  "shortcut": math.inf}[route]


class TestIndex64:
    def test_shortcut_eh(self, rng):
        keys, vals = wide(rng, 400), wide(rng, 400)
        with ShortcutEH(*GEOMETRY, key_bits=64, value_bits=64) as sc:
            sc.insert(keys, vals)
            table = dict(zip(keys.tolist(), vals.tolist()))
            upd = rng.choice(keys, 200)
            uv = rng.integers(0, MISS64, 200, dtype=np.uint64)
            sc.insert(upd, uv)
            table.update(zip(upd.tolist(), uv.tolist()))
            probe = np.concatenate([keys, miss_probe(rng, keys)])
            got = sc.lookup(probe)                      # stale: traditional
            assert got.dtype == np.uint64
            np.testing.assert_array_equal(got, expect(table, probe))
            sc.pump()
            assert sc.use_shortcut()
            np.testing.assert_array_equal(sc.lookup(probe),
                                          expect(table, probe))
            assert sc.routed_shortcut == 1 and sc.routed_traditional == 1
            assert sc.split_seconds > 0

    @pytest.mark.parametrize("num_shards", [1, 2])
    def test_sharded_both_forced_routes(self, rng, num_shards):
        keys, vals = wide(rng, 400), wide(rng, 400)
        table = dict(zip(keys.tolist(), vals.tolist()))
        with ShardedShortcutEH(*GEOMETRY, num_shards=num_shards,
                               key_bits=64, value_bits=64) as idx:
            idx.insert(keys, vals)
            assert idx.split_seconds > 0
            upd = rng.choice(keys, 200)
            uv = rng.integers(0, MISS64, 200, dtype=np.uint64)
            idx.insert(upd, uv)
            table.update(zip(upd.tolist(), uv.tolist()))
            probe = np.concatenate([keys, miss_probe(rng, keys)])
            want = expect(table, probe)
            idx.pump()
            assert idx.check_invariants()["ok"]
            assert idx.num_entries() == len(table)
            for route in ("traditional", "shortcut"):
                _routes(idx, route)
                sc0 = idx.routed_shortcut
                got = idx.lookup_batched(probe)
                assert got.dtype == np.uint64
                np.testing.assert_array_equal(got, want, err_msg=route)
                assert (idx.routed_shortcut > sc0) == (route == "shortcut")
                np.testing.assert_array_equal(idx.lookup(probe), want)
            before = idx.split_seconds
            idx.lookup_batched(probe)
            assert idx.split_seconds > before

    def test_wide_keys_with_narrow_values(self, rng):
        keys = wide(rng, 300)
        vals = rng.integers(0, 2**32 - 1, 300, dtype=np.uint32)
        with ShardedShortcutEH(8, 8, 256, num_shards=2,
                               key_bits=64) as idx:
            idx.insert(keys, vals)
            idx.pump()
            probe = np.concatenate([keys, miss_probe(rng, keys)])
            want = np.concatenate([vals, np.full(probe.size - 300,
                                                 0xFFFFFFFF, np.uint32)])
            np.testing.assert_array_equal(
                np.asarray(idx.lookup_batched(probe)), want)


class TestNoWrap:
    """A key or value array wider than the index's width is refused,
    naming the width, on every host entry point."""

    @pytest.mark.parametrize("bits", [32, 64])
    def test_wider_or_negative_keys_raise(self, bits):
        # at 32 bits a uint64 array is wider; at 64 nothing is wider, and
        # a signed array holding a negative key would wrap
        bad = (np.asarray([1 << 40, 5], np.uint64) if bits == 32
               else np.asarray([-3, 5], np.int64))
        ok = np.asarray([7, 5], hashing.word_type(bits))
        with ShardedShortcutEH(*GEOMETRY, key_bits=bits,
                               value_bits=bits) as idx:
            for call in (lambda: idx.insert(bad, ok),
                         lambda: idx.lookup(bad),
                         lambda: idx.lookup_batched(bad)):
                with pytest.raises(ValueError, match=f"{bits}-bit key"):
                    call()
            if bits == 32:
                with pytest.raises(ValueError, match="32-bit value"):
                    idx.insert(ok, bad)
            assert idx.num_entries() == 0
            if bits == 32:
                idx.insert([7, 5], [1, 2])      # a list is checked by value
                np.testing.assert_array_equal(
                    np.asarray(idx.lookup_batched(ok)), [1, 2])

    def test_the_width_is_32_or_64(self):
        with pytest.raises(ValueError, match="32 or 64"):
            ShardedShortcutEH(6, 8, 64, key_bits=48)


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def test_32_bit_results_and_state_are_unchanged():
    """At 32 bits the state after two insert batches (splits, doublings,
    overwrites with repeats), both lookup paths and the index's
    batched answers are bit for bit what they were before wide
    keys (digests recorded from that code)."""
    rng = np.random.default_rng(7)
    keys = rng.choice(np.arange(1, 2**31, dtype=np.uint32), 1000,
                      replace=False)
    vals = rng.integers(0, 2**32 - 1, 1000, dtype=np.uint32)
    st = eh.eh_create(10, 16, 512)
    st, f1, _ = eh.eh_insert_many(st, jnp.asarray(keys[:700]),
                                  jnp.asarray(vals[:700]))
    batch = np.concatenate([rng.choice(keys[:700], 300), keys[700:800]])
    bv = rng.integers(0, 2**32 - 1, batch.size, dtype=np.uint32)
    st, f2, _ = eh.eh_insert_many(st, jnp.asarray(batch), jnp.asarray(bv))
    assert (int(f1), int(f2)) == (700, 100)
    assert _digest(*st[:8]) == "20b2ebe8759f96ef"
    probe = jnp.asarray(np.concatenate(
        [keys, rng.integers(2**31, 2**32 - 2, 200, dtype=np.uint32)]))
    assert _digest(eh.eh_lookup_many(st, probe)) == "ff15332efbda783c"
    vk, vv = eh.compose_shortcut(st, 1 << int(st.global_depth))
    assert _digest(eh.shortcut_lookup_many(vk, vv, st.global_depth,
                                           probe)) == "ff15332efbda783c"
    with ShardedShortcutEH(10, 16, 512) as idx:
        idx.insert(keys[:700], vals[:700])
        idx.insert(batch, bv)
        assert _digest(idx.lookup_batched(np.asarray(probe))) \
            == "ff15332efbda783c"
        assert idx.split_seconds == 0.0
        assert _digest(*[a for s in idx.shards for a in s.state[:3]]) \
            == "9f8f511c79cc7530"
