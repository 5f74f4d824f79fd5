"""A configuration, a traffic mix, a per-layer metric and a system are
added as new files that the harness finds by name, with no edit to a
file that is there."""
import json
import os
import shutil
from pathlib import Path

import pytest

from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
# a test-only system: the plain reference in the program's place, exact
# at any key and value width
EXACT_SYSTEM = '''"""The plain reference in the program's place, exact at any width."""
from chipbench import faults
from chipbench.references import kv_map


class System(faults.StandIn):
    def __init__(self, cfg):
        super().__init__(cfg, kv_map)
'''


def _copy(tmp_path: Path):
    """The benchmark's files in ``tmp_path``, with its BENCHMARK.json
    and the flat configuration to start new entries from."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/eh-flat-4k.json")
                     .read_text())
    return bench, cfg


def test_new_files_make_a_new_cell(tmp_path):
    bench, cfg = _copy(tmp_path)
    cfg.update(name="eh-2shard-4k", records=4096, num_shards=2,
               bucket_slots=64, max_global_depth=10, capacity=128)
    (tmp_path / "chipbench/configs/eh-2shard-4k.json").write_text(
        json.dumps(cfg))
    (tmp_path / "chipbench/traffic/tiny-a.json").write_text(json.dumps(
        {"reads_per_request": 512, "updates_per_request": 512,
         "distribution": "uniform", "pool_requests": 4}))
    (tmp_path / "chipbench/metrics/reads_per_request.py").write_text(
        "def read(ctx):\n"
        "    return ctx.traffic['reads_per_request']\n")
    bench["configs"].append({"name": "eh-2shard-4k",
                             "source": "test", "reduced": [], "why": "test",
                             "file": "chipbench/configs/eh-2shard-4k.json"})
    bench["workloads"].append({"name": "tiny-a.2sh", "config": "eh-2shard-4k",
                               "traffic": "tiny-a", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "reads_per_request", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "host front", "moves": "ops_per_s",
                               "workloads": ["tiny-a.2sh"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    r = tiny.run("tiny-a.2sh", root=tmp_path, trace=True, overrides={})
    assert r["correct"] is True, r["checks"]
    assert r["metrics"]["reads_per_request"]["value"] == 512
    assert "shortcut_route_pct" not in r["metrics"]   # not listed there
    r = tiny.run("tiny-a.2sh", root=tmp_path, overrides={})
    assert set(r["metrics"]) == {"ops_per_s", "req_p95_ms", "setup_s"}


def test_a_64_bit_cell_from_new_files(tmp_path):
    bench, cfg = _copy(tmp_path)
    cfg.update(name="kv-exact-k64", system="exact_map", records=4096,
               key_bits=64, value_bits=64)
    (tmp_path / "chipbench/systems/exact_map.py").write_text(EXACT_SYSTEM)
    (tmp_path / "chipbench/configs/kv-exact-k64.json").write_text(
        json.dumps(cfg))
    (tmp_path / "chipbench/traffic/tiny-b.json").write_text(json.dumps(
        {"reads_per_request": 512, "updates_per_request": 27,
         "distribution": "zipfian", "zipf_theta": 0.99,
         "pool_requests": 4}))
    bench["configs"].append({"name": "kv-exact-k64",
                             "source": "test", "reduced": [], "why": "test",
                             "file": "chipbench/configs/kv-exact-k64.json"})
    bench["workloads"].append({"name": "tiny-b.k64", "config": "kv-exact-k64",
                               "traffic": "tiny-b", "chips": 1, "why": "t"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    r = tiny.run("tiny-b.k64", root=tmp_path, overrides={})
    assert r["correct"] is True, r["checks"]
    assert r["checks"]["wrong_misses"] == {"value": 0, "limit": 0}
    assert set(r["metrics"]) == {"ops_per_s", "req_p95_ms", "setup_s"}

    # an index that keeps each key's low word: every read of the traffic
    # hits a stored key and reads right, so only the miss probe sees it
    r = tiny.run("tiny-b.k64", root=tmp_path, overrides={},
                 fault="low_word_only")
    assert r["correct"] is False
    assert r["checks"]["wrong_misses"]["value"] > 0
    assert r["checks"]["wrong_reads"]["value"] == 0

    # the program holds 32-bit words: it refuses the configuration
    # rather than wrap its keys
    from chipbench.systems.sharded_shortcut_eh import System
    with pytest.raises(ValueError, match="R3"):
        System(cfg)
