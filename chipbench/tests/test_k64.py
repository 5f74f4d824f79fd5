"""The 64-bit cell ``ycsb-b.zipf.flat.k64``: it runs correct at a tiny
size on the CPU with the real adapter, the miss probe catches an index
that keeps one word of a key, the 32-bit adapter still refuses the
widths, its layout is the index's own, its metric readers read a stub
run, and the program's split spans reach a real trace."""
import json
import math
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import gen, harness, roofline
from chipbench import spans as sp
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
CELL = "ycsb-b.zipf.flat.k64"
CONFIG = json.loads((ROOT / "chipbench/configs/eh-flat-4k-k64.json")
                    .read_text())
NEW_METRICS = ("k64_lookup_kernel_ms", "k64_lookup_roofline_pct",
               "k64_insert_device_ms", "k64_maint_ms", "word_split_ms")


def test_the_cell_runs_correct_at_64_bits():
    r = tiny.run(CELL, trace=True)
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["wrong_misses"] == {"value": 0, "limit": 0}
    m = r["metrics"]
    # off the chip there is no device plane: the host's readers read
    assert m["word_split_ms"]["value"] > 0
    assert m["k64_maint_ms"]["value"] > 0
    assert "k64_lookup_kernel_ms" not in m
    assert "lookup_kernel_ms" not in m         # not listed for this cell


def test_low_word_only_is_caught_by_the_miss_probe():
    r = tiny.run(CELL, fault="low_word_only")
    assert r["correct"] is False
    assert r["checks"]["wrong_misses"]["value"] > 0
    assert r["checks"]["wrong_reads"]["value"] == 0


def test_the_32_bit_adapter_still_refuses_the_widths():
    from chipbench.systems.sharded_shortcut_eh import System
    with pytest.raises(ValueError, match="R3"):
        System(CONFIG)


def test_the_cell_is_declared_with_its_metrics():
    spec = harness.cell_spec(CELL)
    assert spec.config["key_bits"] == spec.config["value_bits"] == 64
    assert spec.config["system"] == "sharded_shortcut_eh_k64"
    names = {m["name"] for m in spec.per_layer}
    assert names == set(NEW_METRICS)


@pytest.fixture(scope="module")
def tiny_index():
    """The real adapter at a tiny size, loaded with 64-bit YCSB keys:
    its layout, its directory and the keys."""
    from chipbench.systems.sharded_shortcut_eh_k64 import System
    cfg = dict(CONFIG, records=4096, bucket_slots=64, max_global_depth=10,
               capacity=256)
    keys = gen.record_keys(4096, 64)
    system = System(cfg)
    try:
        system.insert(keys, gen.load_values(tiny.SEED, 4096, 64))
        assert system.wait_in_sync(60)
        st = system.idx.shards[0].state
        return keys, system.layout(), np.asarray(st.directory), \
            system.counters()
    finally:
        system.close()


def test_layout_gives_each_key_the_bucket_its_slot_names(tiny_index):
    keys, (skeys, shard, bucket, slot), directory, _ = tiny_index
    assert skeys.dtype == np.uint64
    np.testing.assert_array_equal(skeys, np.sort(keys))
    assert (shard == 0).all()
    np.testing.assert_array_equal(directory[slot], bucket)


def test_counters_add_the_split_and_the_published_bytes(tiny_index):
    counters = tiny_index[3]
    assert counters["split_seconds"] > 0
    assert counters["publish_bytes"] > 0
    assert {"routed_shortcut", "routed_traditional", "replay_seconds",
            "populate_seconds"} <= set(counters)


def _stub():
    before = {"routed_shortcut": 0, "routed_traditional": 0,
              "replay_seconds": 1.0, "populate_seconds": 0.5,
              "split_seconds": 0.25}
    after = {"routed_shortcut": 0, "routed_traditional": 100,
             "replay_seconds": 2.0, "populate_seconds": 1.0,
             "split_seconds": 0.35}
    weighted = [(1, roofline.lookup_bytes(
        np.zeros(8), np.arange(8), np.arange(8), np.ones(8, bool), 256,
        key_bytes=8, value_bytes=8))]
    return SimpleNamespace(
        trace=SimpleNamespace(by_jit={"jit(sharded_eh_lookup)": 0.2,
                                      "jit(eh_insert_many)": 0.02}),
        requests=100, counters_before=before, counters_after=after,
        traffic={"updates_per_request": 3449}, device_kind="TPU v5 lite",
        lookup_bytes=lambda: weighted)


def test_each_new_reader_reads_a_stub_run():
    got = harness.read_metrics(
        [{"name": n, "unit": "x"} for n in NEW_METRICS], _stub(), ROOT)
    assert set(got) == set(NEW_METRICS)
    v = {n: got[n]["value"] for n in NEW_METRICS}
    assert math.isclose(v["k64_lookup_kernel_ms"], 2.0)
    assert math.isclose(v["k64_insert_device_ms"], 0.2)
    assert math.isclose(v["k64_maint_ms"], 15.0)
    assert math.isclose(v["word_split_ms"], 1.0)
    # 8 keys, results and hit values at 8 bytes, 8 rows of 2 KB, and 8
    # directory entries: 100 requests over 0.2 s of kernels
    least = (3 * 8 * 8 + 8 * 2048 + 8 * 4) * 100 / 819e9
    assert math.isclose(v["k64_lookup_roofline_pct"], 100 * least / 0.2)
    assert 0 < v["k64_lookup_roofline_pct"] < 100


def test_word_split_ms_reads_nothing_without_the_counter():
    ctx = _stub()
    for c in (ctx.counters_before, ctx.counters_after):
        del c["split_seconds"]
    assert harness.read_metrics([{"name": "word_split_ms", "unit": "ms"}],
                                ctx, ROOT) == {}


def test_split_spans_reach_a_real_trace(tmp_path):
    import jax

    from repro.core.sharded_eh import ShardedShortcutEH

    keys = gen.record_keys(512, 64)
    idx = ShardedShortcutEH(8, 64, 64, key_bits=64, value_bits=64)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        idx.insert(keys, keys)
        got = idx.lookup_batched(keys[:256])
    finally:
        jax.profiler.stop_trace()
        idx.close()
    np.testing.assert_array_equal(got, keys[:256])
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {s.name for s in sp.load(str(path))}
    assert {"insert.split", "lookup.split", "insert.scan",
            "lookup.wait"} <= names
