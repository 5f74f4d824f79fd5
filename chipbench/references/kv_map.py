"""The plain reference of a key -> value index: a map kept as two sorted
arrays, with no code of the package under test.

Its guarantee is the one the configurations state: every read returns
the latest acknowledged value of its key, and the miss marker (the
all-ones word of the value width) for a key never stored.  Within one
update batch, a later write of a key wins.  Keys and values keep the
types they are given; keys asked for or updated must have a type that
holds them exactly."""
from __future__ import annotations

import numpy as np


def _exact(a, dtype) -> np.ndarray:
    a = np.asarray(a)
    if not np.can_cast(a.dtype, dtype, "safe"):
        raise TypeError(f"{a.dtype} does not fit the map's {dtype}")
    return a.astype(dtype, copy=False)


class KVMap:
    def __init__(self, keys: np.ndarray, values: np.ndarray):
        keys, values = np.asarray(keys), np.asarray(values)
        order = np.argsort(keys, kind="stable")
        self.keys = keys[order]
        self.values = values[order].copy()
        self.miss = values.dtype.type(np.iinfo(values.dtype).max)
        if np.any(self.keys[1:] == self.keys[:-1]):
            raise ValueError("the load holds a key twice")

    def _find(self, q: np.ndarray):
        q = _exact(q, self.keys.dtype)
        i = np.minimum(np.searchsorted(self.keys, q), self.keys.size - 1)
        return i, self.keys[i] == q

    def update(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Apply one acknowledged batch of updates to stored keys."""
        keys = _exact(keys, self.keys.dtype)
        # the last write of each key in the batch wins
        _, last = np.unique(keys[::-1], return_index=True)
        last = keys.size - 1 - last
        i, found = self._find(keys[last])
        if not found.all():
            raise ValueError("an update names a key that was never loaded")
        self.values[i] = _exact(values, self.values.dtype)[last]

    def get(self, q: np.ndarray) -> np.ndarray:
        i, found = self._find(q)
        return np.where(found, self.values[i], self.miss)
