"""The plain reference of a key -> value index: a map kept as two sorted
arrays, with no code of the package under test.

Its guarantee is the one the configurations state: every read returns
the latest acknowledged value of its key, and MISS for a key never
stored.  Within one update batch, a later write of a key wins."""
from __future__ import annotations

import numpy as np

MISS = np.uint32(0xFFFFFFFF)


class KVMap:
    def __init__(self, keys: np.ndarray, values: np.ndarray):
        keys = np.asarray(keys, np.uint32)
        order = np.argsort(keys, kind="stable")
        self.keys = keys[order]
        self.values = np.asarray(values, np.uint32)[order].copy()
        if np.any(self.keys[1:] == self.keys[:-1]):
            raise ValueError("the load holds a key twice")

    def _find(self, q: np.ndarray):
        q = np.asarray(q, np.uint32)
        i = np.minimum(np.searchsorted(self.keys, q), self.keys.size - 1)
        return i, self.keys[i] == q

    def update(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Apply one acknowledged batch of updates to stored keys."""
        keys = np.asarray(keys, np.uint32)
        # the last write of each key in the batch wins
        _, last = np.unique(keys[::-1], return_index=True)
        last = keys.size - 1 - last
        i, found = self._find(keys[last])
        if not found.all():
            raise ValueError("an update names a key that was never loaded")
        self.values[i] = np.asarray(values, np.uint32)[last]

    def get(self, q: np.ndarray) -> np.ndarray:
        i, found = self._find(q)
        return np.where(found, self.values[i], MISS)
