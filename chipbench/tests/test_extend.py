"""A configuration, a traffic mix and a per-layer metric are added as
new files that the harness finds by name, with no edit to a file that
is there."""
import json
import os
import shutil
from pathlib import Path

from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def test_new_files_make_a_new_cell(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench/configs/eh-flat-4k.json")
                     .read_text())
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
