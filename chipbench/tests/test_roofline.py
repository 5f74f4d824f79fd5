"""The peaks table and the lookup byte count."""
import numpy as np
import pytest

from chipbench import roofline


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


def test_dir_slots_take_the_top_bits_of_the_hash():
    keys = np.array([1, 2, 0xDEADBEEF], np.uint32)
    h = (keys.astype(np.uint64) * 2654435761) & 0xFFFFFFFF
    np.testing.assert_array_equal(roofline.dir_slots(keys, 10), h >> 22)
    np.testing.assert_array_equal(roofline.dir_slots(keys, 0), [0, 0, 0])
