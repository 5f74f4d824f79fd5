"""Shared test fixtures.

NOTE: no XLA_FLAGS device-count override here — smoke tests and benches
must see the real single CPU device (the 512-device override belongs to
launch/dryrun.py and the dedicated subprocess-based distributed tests).
"""
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def unique_keys(rng, n, lo=1, hi=2**31):
    """Distinct uint32 keys, avoiding 0 and the EMPTY sentinel."""
    return rng.choice(np.arange(lo, hi, dtype=np.uint32), size=n,
                      replace=False)


def wide_keys(rng, n, bits=64):
    """``n`` distinct keys (or values) of ``bits`` bits, every 32-bit
    word in [1, 2**32 - 2]."""
    from repro.core import hashing
    out = np.zeros(0, np.uint64)
    while out.size < n:
        w = rng.integers(1, 2**32 - 1, (bits // 32, 2 * n), dtype=np.uint64)
        k = w[0] if bits == 32 else (w[0] << np.uint64(32)) | w[1]
        out = np.unique(np.concatenate([out, k]))
    return rng.permutation(out)[:n].astype(hashing.word_type(bits))


def miss_probe(rng, stored):
    """Never-stored 64-bit keys, half sharing their low word with a
    stored key, half their high word."""
    low = np.uint64(0xFFFFFFFF)
    pick = rng.choice(stored, 64)
    fresh = wide_keys(rng, 64, 32).astype(np.uint64)
    probe = np.concatenate([(pick[:32] & low) | (fresh[:32] << np.uint64(32)),
                            (pick[32:] & ~low) | fresh[32:]])
    return probe[~np.isin(probe, stored)]
