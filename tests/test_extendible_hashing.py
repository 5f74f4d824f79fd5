"""EH core: dict-oracle equivalence, structural invariants, hypothesis
property tests, the shortcut-view equivalence (paper §2/§4), and the batch
upsert against a sequential scan of single inserts."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, never hard-fail
from hypothesis import given, settings, strategies as st

from repro.core import extendible_hashing as eh
from repro.core import hashing

from conftest import unique_keys


def build(keys, vals, *, depth=8, slots=16, capacity=512):
    state = eh.eh_create(max_global_depth=depth, bucket_slots=slots,
                         capacity=capacity)
    return eh.eh_insert_many(state, jnp.asarray(keys), jnp.asarray(vals))[0]


class TestLookup:
    def test_all_inserted_found(self, rng):
        keys = unique_keys(rng, 500)
        vals = np.arange(500, dtype=np.uint32)
        st_ = build(keys, vals)
        assert int(st_.dropped) == 0
        out = np.asarray(eh.eh_lookup_many(st_, jnp.asarray(keys)))
        np.testing.assert_array_equal(out, vals)

    def test_absent_keys_miss(self, rng):
        keys = unique_keys(rng, 300)
        st_ = build(keys[:200], np.arange(200, dtype=np.uint32))
        out = np.asarray(eh.eh_lookup_many(st_, jnp.asarray(keys[200:])))
        assert (out == 0xFFFFFFFF).all()

    def test_overwrite_updates_value(self, rng):
        keys = unique_keys(rng, 50)
        st_ = build(keys, np.arange(50, dtype=np.uint32))
        st_, _, _ = eh.eh_insert_many(
            st_, jnp.asarray(keys[:10]),
            jnp.asarray(np.full(10, 999, np.uint32)))
        out = np.asarray(eh.eh_lookup_many(st_, jnp.asarray(keys[:10])))
        assert (out == 999).all()
        # no double-count
        assert int(eh.eh_num_entries(st_)) == 50


class TestInvariants:
    @pytest.mark.parametrize("n", [10, 100, 700])
    def test_structural_invariants(self, rng, n):
        keys = unique_keys(rng, n)
        st_ = build(keys, np.arange(n, dtype=np.uint32))
        report = eh.check_invariants(st_)
        assert report["ok"], report["errors"]

    def test_directory_doubles_progressively(self, rng):
        keys = unique_keys(rng, 600)
        state = eh.eh_create(max_global_depth=8, bucket_slots=16,
                             capacity=512)
        depths = []
        for i in range(0, 600, 100):
            state, _, _ = eh.eh_insert_many(
                state, jnp.asarray(keys[i:i + 100]),
                jnp.asarray(np.arange(i, i + 100, dtype=np.uint32)))
            depths.append(int(state.global_depth))
        assert depths == sorted(depths)
        assert depths[-1] > 0


class TestShortcutView:
    """The composed view answers exactly like the traditional path."""

    @pytest.mark.parametrize("n", [50, 400])
    def test_view_equivalence(self, rng, n):
        keys = unique_keys(rng, n)
        st_ = build(keys, np.arange(n, dtype=np.uint32))
        g = int(st_.global_depth)
        vk, vv = eh.compose_shortcut(st_, 1 << g)
        probe = np.concatenate([keys, unique_keys(rng, 100, lo=2**31,
                                                  hi=2**32 - 2)])
        trad = eh.eh_lookup_many(st_, jnp.asarray(probe))
        shortcut = eh.shortcut_lookup_many(vk, vv, st_.global_depth,
                                           jnp.asarray(probe))
        np.testing.assert_array_equal(np.asarray(trad),
                                      np.asarray(shortcut))

    def test_remap_after_split_restores_equivalence(self, rng):
        """rewiring.remap_slots replay == fresh compose (update request)."""
        from repro.core import rewiring
        keys = unique_keys(rng, 400)
        st0 = build(keys[:200], np.arange(200, dtype=np.uint32))
        g0 = int(st0.global_depth)
        vk, vv = eh.compose_shortcut(st0, 1 << g0)
        st1, _, _ = eh.eh_insert_many(
            st0, jnp.asarray(keys[200:]),
            jnp.asarray(np.arange(200, 400, dtype=np.uint32)))
        if int(st1.global_depth) != g0:
            pytest.skip("directory doubled; update-request replay "
                        "does not apply (create request instead)")
        dir_np = np.asarray(st1.directory[: 1 << g0])
        slots = jnp.arange(1 << g0, dtype=jnp.int32)
        vk = rewiring.remap_slots(vk, st1.bucket_keys, slots,
                                  jnp.asarray(dir_np))
        vv = rewiring.remap_slots(vv, st1.bucket_vals, slots,
                                  jnp.asarray(dir_np))
        fresh_k, fresh_v = eh.compose_shortcut(st1, 1 << g0)
        np.testing.assert_array_equal(np.asarray(vk), np.asarray(fresh_k))
        np.testing.assert_array_equal(np.asarray(vv), np.asarray(fresh_v))


class TestHypothesis:
    @settings(deadline=None, max_examples=20)
    @given(st.lists(st.integers(min_value=1, max_value=2**31 - 1),
                    min_size=1, max_size=200, unique=True),
           st.integers(min_value=0, max_value=2**32 - 1))
    def test_matches_python_dict(self, keys, seed):
        """EH == dict for any insert sequence (values = index)."""
        keys = np.asarray(keys, np.uint32)
        vals = np.arange(len(keys), dtype=np.uint32)
        st_ = build(keys, vals, depth=10, slots=8, capacity=1024)
        oracle = dict(zip(keys.tolist(), vals.tolist()))
        out = np.asarray(eh.eh_lookup_many(st_, jnp.asarray(keys)))
        for k, got in zip(keys.tolist(), out.tolist()):
            assert got == oracle[k]
        report = eh.check_invariants(st_)
        assert report["ok"], report["errors"]

    @settings(deadline=None, max_examples=10)
    @given(st.lists(st.integers(min_value=1, max_value=2**31 - 1),
                    min_size=2, max_size=120, unique=True))
    def test_insertion_order_irrelevant(self, keys):
        keys = np.asarray(keys, np.uint32)
        vals = np.arange(len(keys), dtype=np.uint32)
        a = build(keys, vals, depth=10, slots=8, capacity=1024)
        perm = np.random.default_rng(0).permutation(len(keys))
        b = build(keys[perm], vals[perm], depth=10, slots=8, capacity=1024)
        probe = jnp.asarray(keys)
        np.testing.assert_array_equal(
            np.asarray(eh.eh_lookup_many(a, probe)),
            np.asarray(eh.eh_lookup_many(b, probe)))

    @settings(deadline=None, max_examples=10)
    @given(st.lists(st.integers(min_value=1, max_value=2**31 - 1),
                    min_size=1, max_size=150, unique=True))
    def test_fan_in_is_power_of_two_per_bucket(self, keys):
        """I2 (paper Fig 6): each bucket is referenced by exactly
        2^(g-l) contiguous slots."""
        keys = np.asarray(keys, np.uint32)
        st_ = build(keys, np.arange(len(keys), dtype=np.uint32),
                    depth=10, slots=8, capacity=1024)
        report = eh.check_invariants(st_)
        assert report["ok"], report["errors"]


# ---------------------------------------------------------------------------
# eh_insert_many against the plain sequential scan of eh_insert it replaces.
# ---------------------------------------------------------------------------

@jax.jit
def sequential_insert_many(state, keys, vals):
    """One ``eh_insert`` per pair, in batch order."""
    def body(s, kv):
        return eh.eh_insert(s, kv[0], kv[1]), None
    return jax.lax.scan(body, state, jnp.stack(
        [keys.astype(jnp.uint32), vals.astype(jnp.uint32)], axis=1))[0]


def _base(keys, *, depth, slots, capacity):
    state = eh.eh_create(max_global_depth=depth, bucket_slots=slots,
                         capacity=capacity)
    return sequential_insert_many(
        state, jnp.asarray(keys),
        jnp.arange(1, len(keys) + 1, dtype=jnp.uint32))


def _present_with_repeats(rng):
    keys = unique_keys(rng, 600)
    base = _base(keys, depth=8, slots=16, capacity=512)
    batch = rng.choice(keys[:40], 900)
    batch[rng.choice(900, 100, replace=False)] = keys[0]   # 100 times
    return base, batch


def _fresh_through_splits(rng):
    base = eh.eh_create(max_global_depth=10, bucket_slots=8, capacity=1024)
    return base, unique_keys(rng, 700)


def _dir_slots(keys, depth):
    h = np.asarray(keys, np.uint64) * hashing.HASH_C1 % 2**32
    return h >> (32 - depth)


def _fresh_split_an_overwritten_bucket(rng):
    keys = unique_keys(rng, 200)
    base = _base(keys, depth=10, slots=8, capacity=256)
    slot = functools.partial(_dir_slots, depth=int(base.global_depth))
    target = keys[0]
    cand = unique_keys(rng, 20000, lo=2**31, hi=2**32 - 2)
    same = cand[slot(cand) == slot(target)][:12]      # overflows 8 slots
    mates = keys[slot(keys) == slot(target)]
    batch = np.concatenate([[target], mates, same[:6], [target], same[6:],
                            mates[::-1], [target]]).astype(np.uint32)
    return base, batch


def _present_in_full_bucket(rng):
    keys = unique_keys(rng, 8)
    base = _base(keys, depth=8, slots=8, capacity=64)   # one full bucket
    assert int(base.counts[0]) == 8 and int(base.num_buckets) == 1
    return base, rng.choice(keys, 50)


def _capacity_exhausted(rng):
    keys = unique_keys(rng, 10)
    base = _base(keys, depth=4, slots=4, capacity=4)
    batch = np.concatenate([unique_keys(rng, 30, lo=2**31, hi=2**32 - 2),
                            rng.choice(keys, 20)])
    return base, rng.permutation(batch)


def _tile_edge(n):
    def case(rng):
        keys = unique_keys(rng, 6000)
        base = _base(keys[:3000], depth=12, slots=16, capacity=2048)
        return base, rng.choice(keys, n)     # half present, with repeats
    return case


INSERT_CASES = {
    "present_with_repeats": _present_with_repeats,
    "fresh_through_splits": _fresh_through_splits,
    "fresh_split_an_overwritten_bucket": _fresh_split_an_overwritten_bucket,
    "present_in_full_bucket": _present_in_full_bucket,
    "capacity_exhausted": _capacity_exhausted,
    **{f"length_{n}": _tile_edge(n) for n in (1, 4095, 4096, 4097)},
}


@pytest.mark.parametrize("case", INSERT_CASES)
def test_insert_many_matches_sequential_scan(rng, case):
    base, keys = INSERT_CASES[case](rng)
    keys = np.asarray(keys, np.uint32)
    vals = rng.integers(0, 2**32 - 1, keys.size, dtype=np.uint32)
    want = sequential_insert_many(base, jnp.asarray(keys),
                                  jnp.asarray(vals))
    got, fresh, _ = eh.eh_insert_many(base, jnp.asarray(keys),
                                      jnp.asarray(vals))
    for name, a, b in zip(eh.EHState._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    report = eh.check_invariants(got)
    assert report["ok"], report["errors"]
    present = np.asarray(eh.eh_lookup_many(base, jnp.asarray(keys))) \
        != 0xFFFFFFFF
    assert int(fresh) == int((~present).sum())
    # every key reads its last value in the batch, a present one always
    last = dict(zip(keys.tolist(), vals.tolist()))
    distinct = jnp.asarray(list(last), jnp.uint32)
    out = np.asarray(eh.eh_lookup_many(got, distinct))
    stored = out != 0xFFFFFFFF
    assert stored[np.asarray(eh.eh_lookup_many(base, distinct))
                  != 0xFFFFFFFF].all()
    np.testing.assert_array_equal(out[stored],
                                  np.asarray(list(last.values()))[stored])
    if case == "present_in_full_bucket":
        assert int(got.num_buckets) == 1 and int(got.counts[0]) == 8
    if case == "capacity_exhausted":
        assert int(got.dropped) > int(base.dropped)
    if case == "fresh_split_an_overwritten_bucket":
        assert int(got.num_buckets) > int(base.num_buckets)


def test_insert_many_temporaries_stay_tiled():
    """A bulk load's batch (2^19 keys into a depth-11 directory of
    2,048 buckets of 512 slots) is classified a tile at a time: the
    compiled program's temporaries stay under 64 MiB, where gathering
    every key's bucket row at once would take gigabytes."""
    state = jax.eval_shape(lambda: eh.eh_create(11, 512, 2048))
    batch = jax.ShapeDtypeStruct((1 << 19,), jnp.uint32)
    mem = eh.eh_insert_many.lower(state, batch, batch).compile() \
        .memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20, mem.temp_size_in_bytes


# ---------------------------------------------------------------------------
# The touched mask eh_insert_many returns, against the host's recompute
# on the returned state.
# ---------------------------------------------------------------------------

def _distinct(rng, n, bits):
    """``n`` distinct keys of ``bits`` bits, no word 0 or all ones."""
    w = rng.integers(1, 2**32 - 1, (2, 2 * n), dtype=np.uint64)
    k = np.unique(w[0] if bits == 32 else (w[0] << np.uint64(32)) | w[1])
    return rng.permutation(k)[:n].astype(hashing.word_type(bits))


def _device(keys, bits):
    return jnp.asarray(keys if bits == 32 else hashing.split_words(keys))


def _host_slots(keys, bits, g):
    h = hashing.hash_dir_np(keys, bits)
    return (h >> np.uint64(32 - g)).astype(np.int64) if g else \
        np.zeros(h.size, np.int64)


def _touched_present_with_repeats(rng, base, loaded, bits):
    batch = rng.choice(loaded, 200)
    batch[rng.choice(200, 40, replace=False)] = loaded[0]
    return batch


def _touched_split_without_doubling(rng, base, loaded, bits):
    """Fresh keys into the shallowest bucket, as many on each side of
    its split bit as fill that side: it splits once, and the directory
    stays as it is."""
    g = int(base.global_depth)
    nb = int(base.num_buckets)
    depth = np.asarray(base.local_depth[:nb])
    b = int(np.argmin(depth))
    l = int(depth[b])
    assert l < g
    directory = np.asarray(base.directory)

    def side(keys):
        return (hashing.hash_dir_np(keys, bits) >> np.uint64(31 - l)) & 1

    stored = eh.host_keys(base)[b]
    stored = stored[stored != hashing.all_ones(bits)]
    cand = _distinct(rng, 20000, bits)
    cand = cand[~np.isin(cand, loaded)]
    cand = cand[directory[_host_slots(cand, bits, g)] == b]
    s = base.bucket_slots
    batch = np.concatenate([
        cand[side(cand) == v][:s - int((side(stored) == v).sum())]
        for v in (0, 1)])
    assert batch.size > s - stored.size
    return rng.permutation(batch)


def _touched_doubling(rng, base, loaded, bits):
    return _distinct(rng, 600, bits)


def _touched_empty(rng, base, loaded, bits):
    return np.zeros(0, hashing.word_type(bits))


TOUCHED_CASES = {
    "present_with_repeats": _touched_present_with_repeats,
    "split_without_doubling": _touched_split_without_doubling,
    "doubling": _touched_doubling,
    "empty": _touched_empty,
}


@pytest.mark.parametrize("case", TOUCHED_CASES)
@pytest.mark.parametrize("bits", [32, 64])
def test_insert_many_touched_is_the_host_recompute(rng, bits, case):
    """``eh_insert_many``'s mask holds exactly the buckets the batch's
    keys map to in the state it returns: what the host found by hashing
    the batch again, splits and moved rows included."""
    base = eh.eh_create(9, 8, 512, bits, bits)
    loaded = _distinct(rng, 300, bits)
    base, _, _ = eh.eh_insert_many(base, _device(loaded, bits),
                                   _device(loaded, bits))
    keys = TOUCHED_CASES[case](rng, base, loaded, bits)
    got, fresh, touched = eh.eh_insert_many(base, _device(keys, bits),
                                            _device(keys, bits))
    touched = np.asarray(touched)
    assert touched.shape == (base.capacity,) and touched.dtype == bool
    g = int(got.global_depth)
    want = np.unique(np.asarray(got.directory)[_host_slots(keys, bits, g)])
    np.testing.assert_array_equal(np.flatnonzero(touched), want)
    if case == "present_with_repeats":
        assert int(fresh) == 0
        assert int(got.num_buckets) == int(base.num_buckets)
    if case == "split_without_doubling":
        assert g == int(base.global_depth)
        assert int(got.num_buckets) == int(base.num_buckets) + 1
    if case == "doubling":
        assert g > int(base.global_depth)
    if case == "empty":
        assert not touched.any()
