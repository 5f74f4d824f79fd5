"""Compile rehearsals of the lookup kernels for a TPU v5e, without one.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached: what Mosaic refuses here (unaligned blocks,
unlowerable primitives, too much VMEM or SMEM) it would refuse on the
chip.  Interpret mode, which every other kernel test uses, cannot show
any of that.  Shapes are ``chip_smoke.py``'s: 64-slot buckets,
directories and views of 2^14 rows, 2048-bucket pools, 65,536-key
batches, for the flat index (N=1) and for 16 shards (a 65,536-key batch
pads to 16,384 keys per shard).

At 64-bit keys and values the shapes are the ``eh-flat-4k-k64`` cell's:
256-slot buckets of two-word pairs (512-lane rows), a depth-12
directory and view, 4,096-bucket pools, 65,536 keys as (hi, lo) words.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
the test runner's workers all import this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import eh_lookup as kernels

DEPTH, SLOTS, CAPACITY = 14, 64, 2048
HBM_BYTES = 16 * 2**30                 # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _args(sharding, entry: str, n: int):
    D = V = 1 << DEPTH
    batch = 1 << 16
    keys_per_shard = batch if n == 1 else 1 << 14

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    keys = s((n, keys_per_shard), jnp.uint32)
    trad = (s((n, D), jnp.int32), s((n, CAPACITY, SLOTS), jnp.uint32),
            s((n, CAPACITY, SLOTS), jnp.uint32), s((n,), jnp.int32))
    view = (s((n, V, SLOTS), jnp.uint32), s((n, V, SLOTS), jnp.uint32),
            s((n,), jnp.int32))
    return {
        "sharded_eh_lookup": (keys, *trad),
        "sharded_shortcut_lookup": (keys, *view),
        "sharded_routed_lookup": (keys, *trad, *view, s((n,), jnp.int32)),
    }[entry]


@pytest.mark.parametrize("n", [1, 16])
@pytest.mark.parametrize("entry", ["sharded_eh_lookup",
                                   "sharded_shortcut_lookup",
                                   "sharded_routed_lookup"])
def test_lookup_compiles_for_v5e(one_chip, entry, n):
    fn = functools.partial(getattr(kernels, entry), interpret=False)
    compiled = jax.jit(fn).lower(*_args(one_chip, entry, n)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def _args64(sharding, entry: str):
    D = V = 1 << 12
    slots, capacity = 2 * 256, 4096

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    keys = s((1, 2, 1 << 16), jnp.uint32)
    trad = (s((1, D), jnp.int32), s((1, capacity, slots), jnp.uint32),
            s((1, capacity, slots), jnp.uint32), s((1,), jnp.int32))
    view = (s((1, V, slots), jnp.uint32), s((1, V, slots), jnp.uint32),
            s((1,), jnp.int32))
    return {
        "sharded_eh_lookup": (keys, *trad),
        "sharded_shortcut_lookup": (keys, *view),
        "sharded_routed_lookup": (keys, *trad, *view, s((1,), jnp.int32)),
    }[entry]


@pytest.mark.parametrize("entry", ["sharded_eh_lookup",
                                   "sharded_shortcut_lookup",
                                   "sharded_routed_lookup"])
def test_lookup_compiles_for_v5e_at_64_bits(one_chip, entry):
    fn = functools.partial(getattr(kernels, entry), interpret=False)
    compiled = jax.jit(fn).lower(*_args64(one_chip, entry)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= 2 * 4 * (1 << 16)


def test_insert_many_compiles_for_v5e(one_chip):
    """The index's insert at a bulk load's length (2^19 keys into a
    depth-11 directory of 2,048 buckets of 512 slots) compiles for the
    chip, classifying a tile at a time: its temporaries stay under
    64 MiB."""
    from repro.core import extendible_hashing as eh
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: eh.eh_create(11, 512, 2048)))
    batch = jax.ShapeDtypeStruct((1 << 19,), jnp.uint32, sharding=one_chip)
    mem = eh.eh_insert_many.lower(state, batch, batch).compile() \
        .memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20, mem.temp_size_in_bytes


def test_insert_many_compiles_for_v5e_at_64_bits(one_chip):
    """The 64-bit load (2^19 keys as (hi, lo) words into a depth-12
    directory of 4,096 buckets of 256 two-word slots) compiles for the
    chip with its temporaries under 64 MiB: the fresh keys are compacted
    a word plane at a time (a (2^19, 2) array would pad to 128 lanes,
    256 MiB each)."""
    from repro.core import extendible_hashing as eh
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: eh.eh_create(12, 256, 4096, 64, 64)))
    batch = jax.ShapeDtypeStruct((2, 1 << 19), jnp.uint32, sharding=one_chip)
    mem = eh.eh_insert_many.lower(state, batch, batch).compile() \
        .memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20, mem.temp_size_in_bytes
