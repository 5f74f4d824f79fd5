"""The plain reference map."""
import numpy as np
import pytest

from chipbench.references.kv_map import MISS, KVMap


def test_get_update_and_miss():
    m = KVMap(np.array([5, 3, 9], np.uint32), np.array([50, 30, 90],
                                                      np.uint32))
    np.testing.assert_array_equal(
        m.get(np.array([3, 4, 9, 5], np.uint32)), [30, MISS, 90, 50])
    # the later write of a key within one batch wins
    m.update(np.array([9, 3, 9], np.uint32), np.array([1, 2, 3], np.uint32))
    np.testing.assert_array_equal(m.get(np.array([9, 3, 5], np.uint32)),
                                  [3, 2, 50])


def test_update_of_an_unknown_key_raises():
    m = KVMap(np.array([1], np.uint32), np.array([1], np.uint32))
    with pytest.raises(ValueError):
        m.update(np.array([2], np.uint32), np.array([2], np.uint32))
