"""The traffic generators: YCSB's load keys, scrambled Zipf and uniform
streams, and the request pool."""
import numpy as np
import pytest

from chipbench import gen

N = 1 << 19


def test_record_keys_are_fixed_distinct_and_never_a_sentinel():
    keys = gen.record_keys(N)
    assert keys.dtype == np.uint32 and keys.size == N
    assert np.unique(keys).size == N
    assert not np.any((keys == 0) | (keys == np.uint32(0xFFFFFFFF)))
    np.testing.assert_array_equal(keys, gen.record_keys(N))
    # a prefix of a longer load is the shorter load: keys follow records
    np.testing.assert_array_equal(gen.record_keys(1000), keys[:1000])


def _fnvhash64(val: int) -> int:
    """YCSB's Utils.fnvhash64, written out as the Java does it."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) % 2**64
    return 2**64 - h if h >= 2**63 else h      # Math.abs of the signed long


def test_fnvhash64_is_ycsbs():
    xs = np.asarray([0, 1, 2, 255, 256, 10**10, 2**40 + 7], np.int64)
    got = gen.fnvhash64(xs)
    assert [int(g) for g in got] == [_fnvhash64(int(x)) for x in xs]
    assert np.all(got < np.uint64(2**63))


def test_zipf_is_ycsbs_scrambled_zipfian():
    dist = gen.ScrambledZipf(N, 0.99)
    rng = np.random.default_rng(5)
    ranks = dist.ranks(rng, 1 << 21)
    # ranks run over YCSB's 10^10 items, not over the records
    assert ranks.min() == 0 and ranks.max() > 100 * N
    assert ranks.max() <= gen.ITEM_COUNT
    # the first two ranks take 1/ZETAN and 2^-0.99/ZETAN of the draws;
    # binomial sd at 2^21 draws is under 0.00014, the tolerance 10 sd
    share0 = np.count_nonzero(ranks == 0) / ranks.size
    share1 = np.count_nonzero(ranks == 1) / ranks.size
    assert share0 == pytest.approx(1 / gen.ZETAN, abs=0.0014)
    assert share1 == pytest.approx(2 ** -0.99 / gen.ZETAN, abs=0.0014)
    # rank r goes to record fnvhash64(r) mod (recordcount + 1)
    np.testing.assert_array_equal(
        dist.records(np.arange(50)),
        [_fnvhash64(r) % (N + 1) for r in range(50)])
    # so the hottest record is rank 0's, with about 3.8% of requests
    records = dist.draw(np.random.default_rng(5), 1 << 21)
    assert records.min() >= 0 and records.max() < N
    counts = np.bincount(records, minlength=N)
    assert counts.argmax() == _fnvhash64(0) % (N + 1)
    assert counts.max() / records.size == pytest.approx(0.0378, abs=0.0014)


def test_zipf_redraws_past_the_last_record():
    # with 3 records the key chooser's range is [0, 3]; record 3 does
    # not exist and every draw of it is drawn again
    dist = gen.ScrambledZipf(3, 0.99)
    records = dist.draw(np.random.default_rng(1), 1 << 16)
    assert set(np.unique(records)) <= {0, 1, 2}
    assert np.count_nonzero(dist.records(np.arange(1000)) == 3) > 0


def test_zipf_other_constants_are_refused():
    with pytest.raises(ValueError):
        gen.ScrambledZipf(N, 0.9)


def test_uniform_covers_the_records_evenly():
    draws = gen.Uniform(1024).draw(np.random.default_rng(1), 1 << 20)
    counts = np.bincount(draws, minlength=1024)
    assert draws.min() >= 0 and draws.max() < 1024
    assert counts.min() > 800 and counts.max() < 1250


@pytest.mark.parametrize("traffic", [
    {"distribution": "zipfian", "zipf_theta": 0.99, "pool_requests": 8,
     "reads_per_request": 512, "updates_per_request": 27},
    {"distribution": "uniform", "pool_requests": 8,
     "reads_per_request": 512},
])
def test_same_seed_same_stream(traffic):
    seed = 2**31 + 12345              # larger than a signed 32-bit int
    a = gen.request_pool(seed, traffic, 4096)
    b = gen.request_pool(seed, traffic, 4096)
    c = gen.request_pool(seed + 1, traffic, 4096)
    np.testing.assert_array_equal(a.reads, b.reads)
    assert not np.array_equal(a.reads, c.reads)
    assert a.reads.shape == (8, 512)
    if a.updates is not None:
        np.testing.assert_array_equal(a.updates, b.updates)
        assert a.updates.shape == (8, 27)
    np.testing.assert_array_equal(gen.load_values(seed, 100),
                                  gen.load_values(seed, 100))


def test_update_values_are_fresh_on_every_issue():
    traffic = {"distribution": "uniform", "pool_requests": 4,
               "reads_per_request": 8, "updates_per_request": 16}
    pool = gen.request_pool(7, traffic, 1024)
    seen = np.concatenate([pool.update_values(s) for s in range(12)])
    assert np.unique(seen).size == seen.size
    assert not np.any(seen == np.uint32(gen.MISS))
    # a cycled request names the same records with new values
    assert pool.entry(1) == pool.entry(5)
    assert not np.array_equal(pool.update_values(1), pool.update_values(5))
