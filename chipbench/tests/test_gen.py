"""The traffic generators: YCSB's load keys, scrambled Zipf and uniform
streams, and the request pool, at 32- and 64-bit widths."""
import hashlib
import json
from pathlib import Path

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
    assert not np.any(seen == gen.miss(32))
    # a cycled request names the same records with new values
    assert pool.entry(1) == pool.entry(5)
    assert not np.array_equal(pool.update_values(1), pool.update_values(5))


# -- widths -----------------------------------------------------------------

PIN_SEED = 2147651003
# sha256 prefixes of each array's dtype and bytes at 32 bits, 2^19
# records, PIN_SEED: the streams every 32-bit cell has run on since the
# benchmark began
PINNED = {
    "keys": "<u4:511d5efb6748c62e",
    "values": "<u4:55029ef935348ea7",
    "ycsb-c.uniform": {"reads": "<i4:bd20912a2be156bf", "updates": None,
                       "base": 404918970, "upd": None},
    "ycsb-b.zipf": {"reads": "<i4:bc8ad1e32473a70e",
                    "updates": "<i4:f8f4cf49ac16b690", "base": 511667441,
                    "upd": "<u4:a4f6c7d595e1810f"},
}
TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def _digest(a: np.ndarray) -> str:
    a = np.ascontiguousarray(a)
    return a.dtype.str + ":" + hashlib.sha256(a.tobytes()).hexdigest()[:16]


def test_32_bit_keys_and_values_are_pinned():
    assert _digest(gen.record_keys(N, 32)) == PINNED["keys"]
    assert _digest(gen.record_keys(N)) == PINNED["keys"]
    assert _digest(gen.load_values(PIN_SEED, N, 32)) == PINNED["values"]
    assert _digest(gen.load_values(PIN_SEED, N)) == PINNED["values"]


@pytest.mark.parametrize("name", ["ycsb-c.uniform", "ycsb-b.zipf"])
def test_32_bit_request_pools_are_pinned(name):
    traffic = json.loads((TRAFFIC / f"{name}.json").read_text())
    p = gen.request_pool(PIN_SEED, traffic, N, 32)
    got = {"reads": _digest(p.reads),
           "updates": None if p.updates is None else _digest(p.updates),
           "base": p.value_base,
           "upd": None if p.updates is None else _digest(np.concatenate(
               [p.update_values(s) for s in (0, 1, 255, 256, 10**6)]))}
    assert got == PINNED[name]


def test_64_bit_keys_are_ycsbs_hashed_keys():
    keys = gen.record_keys(N, 64)
    assert keys.dtype == np.uint64 and keys.size == N
    # YCSB's insertorder=hashed: record k is "user" + fnvhash64(k); at
    # 2^19 records none is reserved and none repeats, so none is skipped
    np.testing.assert_array_equal(keys, gen.fnvhash64(np.arange(N)))
    assert [int(k) for k in keys[:3]] == [_fnvhash64(k) for k in range(3)]
    assert np.unique(keys).size == N
    assert not np.any((keys == 0) | (keys == gen.miss(64)))
    assert np.all(keys < np.uint64(2**63))
    # no two share a 32-bit word, which is why the harness probes misses
    assert np.unique(keys & np.uint64(0xFFFFFFFF)).size == N
    assert np.unique(keys >> np.uint64(32)).size == N
    # keys do not depend on a seed and follow the record numbers
    np.testing.assert_array_equal(gen.record_keys(1000, 64), keys[:1000])


def test_64_bit_keys_skip_reserved_words_and_repeats(monkeypatch):
    # a hash of 0, 0, 1, 1, 2, 2, 3, ...: record k takes the k-th value
    # that is neither reserved nor seen before
    monkeypatch.setattr(gen, "fnvhash64", lambda x: np.asarray(x) // 2)
    np.testing.assert_array_equal(gen.record_keys(3, 64), [1, 2, 3])
    monkeypatch.setattr(gen, "fnvhash64", lambda x: np.where(
        np.asarray(x) == 1, gen.miss(64), np.asarray(x, np.uint64)))
    np.testing.assert_array_equal(gen.record_keys(3, 64), [2, 3, 4])


def test_other_widths_are_refused():
    with pytest.raises(ValueError):
        gen.record_keys(8, 16)
    with pytest.raises(ValueError):
        gen.load_values(1, 8, 128)


def test_64_bit_values_come_from_the_same_seed_streams():
    seed = 2**31 + 12345
    v = gen.load_values(seed, 4096, 64)
    assert v.dtype == np.uint64 and np.all(v < gen.miss(64))
    np.testing.assert_array_equal(v, gen.load_values(seed, 4096, 64))
    assert not np.array_equal(v, gen.load_values(seed + 1, 4096, 64))
    assert v.max() > np.uint64(2**63)         # the full width is drawn
    traffic = {"distribution": "zipfian", "zipf_theta": 0.99,
               "pool_requests": 8, "reads_per_request": 512,
               "updates_per_request": 27}
    p32 = gen.request_pool(seed, traffic, 4096, 32)
    p64 = gen.request_pool(seed, traffic, 4096, 64)
    # the streams draw record numbers, the same at either width
    np.testing.assert_array_equal(p32.reads, p64.reads)
    np.testing.assert_array_equal(p32.updates, p64.updates)
    u = np.concatenate([p64.update_values(s) for s in range(20)])
    assert u.dtype == np.uint64 and np.unique(u).size == u.size
    assert not np.any(u == gen.miss(64))


@pytest.mark.parametrize("bits", [32, 64])
def test_update_values_wrap_below_the_miss_marker(bits):
    top = 2**bits - 1                           # the miss marker
    pool = gen.RequestPool(np.zeros((1, 1), np.int32),
                           np.zeros((1, 4), np.int32), top - 2, bits)
    got = pool.update_values(0)
    assert got.dtype == gen.word(bits)
    assert [int(x) for x in got] == [top - 2, top - 1, 0, 1]
    assert [int(x) for x in pool.update_values(1)] == [2, 3, 4, 5]


def test_miss_probe_shares_one_word_and_is_never_stored():
    keys = gen.record_keys(N, 64)
    probe = gen.miss_probe(PIN_SEED, keys, 4096)
    assert probe.dtype == np.uint64 and probe.size == 4096
    np.testing.assert_array_equal(probe, gen.miss_probe(PIN_SEED, keys, 4096))
    assert not np.any(np.isin(probe, keys))
    assert not np.any((probe == 0) | (probe == gen.miss(64)))
    low, high = np.uint64(0xFFFFFFFF), np.uint64(32)
    assert np.all(np.isin(probe[:2048] & low, keys & low))
    assert np.all(np.isin(probe[2048:] >> high, keys >> high))
