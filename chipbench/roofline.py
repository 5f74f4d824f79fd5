"""Peaks of the chips and the least bytes a lookup request must move.

Peaks are per chip, from Google Cloud's "TPU v5e" documentation (system
architecture: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s).  A ``device_kind`` that is not in the table is an error.

The lookup kernels do no arithmetic worth counting, so their bound is
HBM bandwidth: the least time of a request is its least bytes over the
chip's HBM peak.  The least bytes are the work itself, whatever
implements it: each key read in, each result written out, one S-slot
key row per distinct bucket the request resolves to, one value per hit,
and, on the traditional path, one directory entry per distinct
directory slot.  Keys and key rows count at the configuration's key
width, results and hit values at its value width, and a directory
entry (a bucket number) at 4 bytes.  Padding is not counted.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

DIR_ENTRY = 4                    # bytes of a directory entry


@dataclass(frozen=True)
class Peaks:
    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    int8_ops_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                         int8_ops_per_s=393e12, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclass(frozen=True)
class LookupBytes:
    """Least bytes of one lookup request, by part."""
    keys: int
    results: int
    rows: int
    hit_values: int
    directory: int

    def total(self, traditional_share: float) -> float:
        """Bytes when ``traditional_share`` of the request resolves on
        the traditional path (the directory entries are its alone)."""
        return (self.keys + self.results + self.rows + self.hit_values
                + traditional_share * self.directory)


def lookup_bytes(shard_of_key: np.ndarray, bucket_of_key: np.ndarray,
                 slot_of_key: np.ndarray, hit: np.ndarray,
                 bucket_slots: int, key_bytes: int = 4,
                 value_bytes: int = 4) -> LookupBytes:
    """Least bytes of one request of ``n`` keys.

    ``shard_of_key``, ``bucket_of_key`` and ``slot_of_key`` give each
    key's shard, bucket and directory slot (buckets and slots are
    numbered per shard); ``hit`` says which keys are stored."""
    n = int(np.asarray(shard_of_key).size)

    def distinct(a):
        pairs = (np.asarray(shard_of_key, np.int64) << 32) \
            | np.asarray(a, np.int64)
        return int(np.unique(pairs).size)

    return LookupBytes(
        keys=key_bytes * n, results=value_bytes * n,
        rows=key_bytes * bucket_slots * distinct(bucket_of_key),
        hit_values=value_bytes * int(np.count_nonzero(hit)),
        directory=DIR_ENTRY * distinct(slot_of_key))
