"""Tiny sizes at which a cell runs on the CPU, in interpret mode,
through the same ``harness.run_cell`` the command calls."""
import copy
import time

from chipbench import harness

SEED = 2**33 + 17
BASE = {"config": {"records": 4096, "bucket_slots": 64,
                   "max_global_depth": 10, "capacity": 256},
        "traffic": {"reads_per_request": 1024, "pool_requests": 4}}


def overrides(workload: str) -> dict:
    ov = copy.deepcopy(BASE)
    if workload.startswith("ycsb-b"):
        ov["traffic"]["updates_per_request"] = 54
    return ov


def run(workload: str, *, trace: bool = False, fault=None, root=None,
        seconds: float = 0.5, **kw) -> dict:
    extra = {"root": root} if root is not None else {}
    return harness.run_cell(workload, SEED, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            overrides=kw.get("overrides",
                                             overrides(workload)),
                            fault=fault, log=lambda s: None, **extra)
