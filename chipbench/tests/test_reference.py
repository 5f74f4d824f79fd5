"""The plain reference map."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import gen
from chipbench.references.kv_map import KVMap

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
# sha256 of the reference's answers to the first 300 requests of each
# 32-bit cell at 2^19 records and seed 2147651003: the answers every
# 32-bit cell has been compared with since the benchmark began
PINNED_ANSWERS = {
    "ycsb-c.uniform":
        "f878b345e99cd5556d901e2320ea61f6a09d43139a938146638c5c7b4f51d5e5",
    "ycsb-b.zipf":
        "290d28c0c0a5120aa9d551ec9e3f00df5cfb8f23ae47bfa0cb978043e69941d9",
}


def test_get_update_and_miss():
    m = KVMap(np.array([5, 3, 9], np.uint32), np.array([50, 30, 90],
                                                      np.uint32))
    np.testing.assert_array_equal(
        m.get(np.array([3, 4, 9, 5], np.uint32)), [30, 2**32 - 1, 90, 50])
    # the later write of a key within one batch wins
    m.update(np.array([9, 3, 9], np.uint32), np.array([1, 2, 3], np.uint32))
    np.testing.assert_array_equal(m.get(np.array([9, 3, 5], np.uint32)),
                                  [3, 2, 50])


def test_update_of_an_unknown_key_raises():
    m = KVMap(np.array([1], np.uint32), np.array([1], np.uint32))
    with pytest.raises(ValueError):
        m.update(np.array([2], np.uint32), np.array([2], np.uint32))


def test_64_bit_keys_that_share_a_low_word_keep_their_own_values():
    low = np.uint64(0x1234ABCD)
    keys = np.array([low, (7 << 32) | low, (9 << 32) | low], np.uint64)
    vals = np.array([2**64 - 2, 5, 2**40], np.uint64)
    m = KVMap(keys, vals)
    np.testing.assert_array_equal(m.get(keys), vals)
    assert m.get(keys).dtype == np.uint64


def test_64_bit_last_write_in_a_batch_wins():
    keys = np.array([3 << 40, 5 << 40], np.uint64)
    m = KVMap(keys, np.array([1, 2], np.uint64))
    m.update(np.array([5 << 40, 3 << 40, 5 << 40], np.uint64),
             np.array([10, 20, 2**63 + 1], np.uint64))
    np.testing.assert_array_equal(m.get(keys), [20, 2**63 + 1])


def test_a_never_stored_64_bit_key_reads_the_all_ones_miss():
    m = KVMap(np.array([(1 << 32) | 6], np.uint64),
              np.array([4], np.uint64))
    got = m.get(np.array([6, 1 << 32, (2 << 32) | 6], np.uint64))
    assert got.dtype == np.uint64 and m.miss == 2**64 - 1
    np.testing.assert_array_equal(got, [2**64 - 1] * 3)
    # a 32-bit map cannot be asked for 64-bit keys: they would wrap
    m32 = KVMap(np.array([6], np.uint32), np.array([4], np.uint32))
    with pytest.raises(TypeError):
        m32.get(np.array([(1 << 32) | 6], np.uint64))


@pytest.mark.parametrize("name", sorted(PINNED_ANSWERS))
def test_32_bit_reference_answers_are_pinned(name):
    n, seed = 1 << 19, 2147651003
    keys = gen.record_keys(n, 32)
    pool = gen.request_pool(
        seed, json.loads((TRAFFIC / f"{name}.json").read_text()), n, 32)
    ref = KVMap(keys, gen.load_values(seed, n, 32))
    h = hashlib.sha256()
    for q in range(300):
        e = pool.entry(q)
        if pool.updates is not None:
            ref.update(keys[pool.updates[e]], pool.update_values(q))
        h.update(ref.get(keys[pool.reads[e]]).tobytes())
    assert h.hexdigest() == PINNED_ANSWERS[name]
