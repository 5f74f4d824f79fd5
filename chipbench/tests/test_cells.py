"""Every cell of BENCHMARK.json runs at a tiny size on the CPU and
compares correct; the command refuses to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_with_its_end_to_end_metrics(workload):
    r = tiny.run(workload)
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    assert "wrong_misses" not in r["checks"]     # no probe at 32 bits
    want = {m["name"] for m in BENCH["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"


def test_traced_run_reports_the_host_side_layers():
    r = tiny.run("ycsb-b.zipf.flat", trace=True)
    assert r["correct"] is True, r["checks"]
    m = r["metrics"]
    assert {"lookup_call_ms", "shortcut_route_pct", "maint_ms",
            "window_compiles"} <= set(m)
    assert m["window_compiles"]["value"] == 0
    # off the chip there is no device plane, so no device number
    assert "lookup_kernel_ms" not in m and "device_idle_pct" not in m
    assert "busy_s" not in r["device"]


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.cell_spec("no-such-cell")
