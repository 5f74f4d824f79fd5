"""The peaks table and the lookup byte count."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import gen, harness, roofline

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "eh-flat-4k.json"
SEED = 2**33 + 17


def test_v5e_peaks_and_unknown_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p.hbm_bytes_per_s == 819e9
    assert p.bf16_flops_per_s == 197e12 and p.int8_ops_per_s == 393e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_bytes_count_each_distinct_bucket_once_and_no_padding():
    # six keys over two shards: shard 0 resolves buckets {4, 7}, shard 1
    # bucket {4} (numbered per shard, so it is a third distinct bucket);
    # five keys are stored; slots: shard 0 {1, 2, 3}, shard 1 {1}
    shard = np.array([0, 0, 0, 1, 1, 0])
    bucket = np.array([4, 7, 4, 4, 4, 7])
    slot = np.array([1, 2, 3, 1, 1, 2])
    hit = np.array([1, 1, 1, 1, 0, 1], bool)
    b = roofline.lookup_bytes(shard, bucket, slot, hit, bucket_slots=64)
    assert b.keys == 6 * 4 and b.results == 6 * 4
    assert b.rows == 3 * 64 * 4
    assert b.hit_values == 5 * 4
    assert b.directory == 4 * 4
    assert b.total(0.0) == 24 + 24 + 768 + 20
    assert b.total(1.0) == 24 + 24 + 768 + 20 + 16
    assert b.total(0.5) == 24 + 24 + 768 + 20 + 8


@pytest.fixture(scope="module")
def tiny_index():
    """A tiny flat index loaded with the 32-bit record keys; its layout
    and keys."""
    from chipbench.systems.sharded_shortcut_eh import System
    cfg = json.loads(CONFIG.read_text())
    cfg.update(records=4096, bucket_slots=64, max_global_depth=10,
               capacity=256)
    keys = gen.record_keys(4096)
    system = System(cfg)
    try:
        system.insert(keys, gen.load_values(SEED, 4096))
        assert system.wait_in_sync(60)
        return cfg, keys, system.layout()
    finally:
        system.close()


def test_dir_slots_take_the_top_bits_of_the_hash(tiny_index):
    # the slot each stored key is counted at is the system's own: the
    # top global-depth bits of the index's directory hash
    from repro.core import hashing
    _, keys, (skeys, shard, bucket, slot) = tiny_index
    np.testing.assert_array_equal(np.sort(keys), skeys)
    assert np.all(shard == 0)
    depth = 7                       # the tiny load's 95 buckets
    assert slot.max() == 2 ** depth - 1
    want = [hashing.hash_dir_host(k) >> (32 - depth) for k in skeys]
    np.testing.assert_array_equal(slot, want)


def test_32_bit_window_bytes_equal_the_parents(tiny_index):
    # the counts every 32-bit cell's roofline read before the widths
    # were carried, on the tiny index and a fixed window
    cfg, keys, layout = tiny_index
    pool = gen.request_pool(SEED, {"distribution": "zipfian",
                                   "zipf_theta": 0.99, "pool_requests": 6,
                                   "reads_per_request": 96}, 4096)
    got = harness._lookup_bytes(layout, pool, keys[pool.reads],
                                range(3, 20), cfg)
    assert [(w, b.keys, b.results, b.rows, b.hit_values, b.directory)
            for w, b in got] == [
        (3, 384, 384, 14080, 384, 248), (3, 384, 384, 14336, 384, 244),
        (2, 384, 384, 15360, 384, 284), (3, 384, 384, 15360, 384, 264),
        (3, 384, 384, 13312, 384, 236), (3, 384, 384, 14848, 384, 272)]


def test_32_bit_counts_on_a_fixed_layout_are_unchanged():
    rng = np.random.default_rng(20260)
    n = 5000
    b = roofline.lookup_bytes(rng.integers(0, 4, n), rng.integers(0, 300, n),
                              rng.integers(0, 1024, n), rng.random(n) < 0.9,
                              512)
    assert (b.keys, b.results, b.rows, b.hit_values, b.directory) == (
        20000, 20000, 2422784, 18016, 11424)


def test_64_bit_keys_and_values_count_eight_bytes():
    # the request of the hand count above, at 8-byte keys and values:
    # the directory entries (bucket numbers) stay 4 bytes
    shard = np.array([0, 0, 0, 1, 1, 0])
    bucket = np.array([4, 7, 4, 4, 4, 7])
    slot = np.array([1, 2, 3, 1, 1, 2])
    hit = np.array([1, 1, 1, 1, 0, 1], bool)
    b = roofline.lookup_bytes(shard, bucket, slot, hit, bucket_slots=256,
                              key_bytes=8, value_bytes=8)
    assert b.keys == 6 * 8 and b.results == 6 * 8
    assert b.rows == 3 * 256 * 8             # three 2 KB key rows
    assert b.hit_values == 5 * 8
    assert b.directory == 4 * 4
    assert b.total(1.0) == 48 + 48 + 6144 + 40 + 16
    # mixed widths count each part at its own
    m = roofline.lookup_bytes(shard, bucket, slot, hit, bucket_slots=256,
                              key_bytes=8, value_bytes=4)
    assert (m.keys, m.results, m.rows, m.hit_values) == (48, 24, 6144, 20)
