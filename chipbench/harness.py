"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration file and traffic mix, the configuration names its
system (``systems/<system>.py``) and its reference
(``references/<reference>.py``), the traffic is ``traffic/<name>.json``,
and each metric is read by ``metrics/<name>.py``.

The client is a closed loop with one outstanding request.  Request
``seq`` is entry ``seq % P`` of a pool of ``P`` requests drawn from the
seed during set-up; it sends its updates through ``insert`` (the return
is the acknowledgement), then its reads through ``lookup_batched``, and
is complete when the read results are on the host.

Keys and values are as wide as the configuration's ``key_bits`` and
``value_bits`` say, from generation to the comparison.  Every read of
the traffic hits a stored key, so at 64-bit keys the harness also reads,
after the window, a probe of never-stored keys that each share one
32-bit word with a stored key: each has to read the miss marker
(``wrong_misses``), or the index has dropped part of a key.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Optional

import numpy as np

from chipbench import faults, gen, roofline, trace as tracing

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".jax_cache"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
SYNC_TIMEOUT_S = 600.0       # the first run of a cell compiles in here
WARM_REQUESTS = 3
TRACE_WINDOW_S = 5.0         # a traced run measures at most this long
SAMPLE_EVERY = 8             # about one window request in 8 is compared


class NoChip(RuntimeError):
    pass


# -- finding things by name ---------------------------------------------------

def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell's entry, configuration, traffic and metrics."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    return SimpleNamespace(
        cell=cell, config=load_json(root / conf["file"]),
        traffic=load_json(root / "chipbench" / "traffic"
                          / f"{cell['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        root=root)


def read_metrics(metrics, ctx, root: Path) -> dict:
    """Each metric from its own reader; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = load_module(root / "chipbench" / "metrics"
                            / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- JAX set-up ---------------------------------------------------------------

def configure_jax():
    """The persistent compilation cache at its one fixed path in the
    checkout, holding every program.  Called by the command, not by
    ``run_cell``, so the tests leave JAX's configuration alone."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(jax, chips: int, require_tpu: bool):
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices


class CompileCounter:
    """Executables built (compiled, or read from the persistent cache),
    through ``jax.monitoring``."""

    def __init__(self, jax):
        self.jax, self.count = jax, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.count += 1

    def close(self):
        self.jax.monitoring.unregister_event_duration_listener(self._event)


def _sampled(seed: int, seq: int) -> bool:
    mixed = gen.fmix32(np.asarray([(seed ^ (seq * 0x9E3779B9))
                                   & 0xFFFFFFFF], np.uint32))[0]
    return int(mixed) % SAMPLE_EVERY == 0


# -- the run ------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, require_tpu: bool = True,
             overrides: Optional[dict] = None, fault: Optional[str] = None,
             log: Callable[[str], None] = lambda s: print(
                 s, file=sys.stderr, flush=True)) -> dict:
    """Run one cell and return its result line as a dict.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock;
    set-up is measured from it.  ``overrides`` replaces configuration
    and traffic keys (``{"config": {...}, "traffic": {...}}``), for
    small runs on the CPU; ``fault`` plants one of ``faults.FAULTS``."""
    spec = cell_spec(workload, root)
    cfg = {**spec.config, **(overrides or {}).get("config", {})}
    traffic = {**spec.traffic, **(overrides or {}).get("traffic", {})}
    import jax
    devices = check_devices(jax, int(spec.cell["chips"]), require_tpu)
    device = devices[0]
    counter = CompileCounter(jax)
    try:
        return _run(jax, device, spec, cfg, traffic, workload, seed,
                    seconds, trace, t_start, root, fault, counter, log)
    finally:
        counter.close()


def _run(jax, device, spec, cfg, traffic, workload, seed, seconds, trace,
         t_start, root, fault, counter, log):
    reference = load_module(root / "chipbench" / "references"
                            / f"{cfg['reference']}.py")
    system_mod = load_module(root / "chipbench" / "systems"
                             / f"{cfg['system']}.py")
    n = int(cfg["records"])
    key_bits = int(cfg.get("key_bits", 32))
    value_bits = int(cfg.get("value_bits", 32))
    keys = gen.record_keys(n, key_bits)
    values = gen.load_values(seed, n, value_bits)
    pool = gen.request_pool(seed, traffic, n, value_bits)
    read_keys = keys[pool.reads]
    upd_keys = None if pool.updates is None else keys[pool.updates]
    system = (faults.build(fault, cfg, system_mod.System, reference)
              if fault else system_mod.System(cfg))
    kept = {}                  # seq -> read results on the host
    forced = {}                # (when, route) -> results of a forced route
    reduced = None
    lat, lookup_s = [], []

    def request(seq: int, keep: bool):
        e = pool.entry(seq)
        t0 = time.perf_counter()
        if upd_keys is not None:
            with jax.profiler.TraceAnnotation("insert"):
                system.insert(upd_keys[e], pool.update_values(seq))
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("lookup_batched"):
            res = system.lookup(read_keys[e])
        with jax.profiler.TraceAnnotation("fetch"):
            host = np.asarray(res)
        t2 = time.perf_counter()
        if keep:
            kept[seq] = host
        return t2 - t0, t2 - t1, host

    phases = {"start": time.perf_counter() - t_start}
    try:
        # the load, then the mapper's catch-up
        t = time.perf_counter()
        system.insert(keys, values)
        phases["load"] = time.perf_counter() - t
        t = time.perf_counter()
        in_sync_after_load = system.wait_in_sync(SYNC_TIMEOUT_S)
        entries, dropped = system.entries(), system.dropped()
        phases["sync"] = time.perf_counter() - t
        t = time.perf_counter()
        # warm-up: both read routes, then whole requests with their
        # replays, then every replay chunk shape
        for route in ("traditional", "shortcut"):
            system.force_route(route)
            system.wait_in_sync(SYNC_TIMEOUT_S)
            forced[("warm", route)] = np.asarray(
                system.lookup(read_keys[0]))
        system.force_route(None)
        for seq in range(WARM_REQUESTS):
            request(seq, keep=True)
            system.wait_in_sync(SYNC_TIMEOUT_S)
        system.warm_replay_shapes()
        seq = WARM_REQUESTS
        phases["warm-up"] = time.perf_counter() - t

        # the measured window
        c0, compiles0 = system.counters(), counter.count
        trace_dir = root / "chipbench" / ".trace"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(
                str(trace_dir), profiler_options=_profile_options(jax))
        setup_s = time.perf_counter() - t_start
        log("set-up " + ", ".join(f"{k} {v:.2f} s" for k, v in
                                  phases.items()) + f"; in all {setup_s:.2f} s")
        if trace:
            seconds = min(seconds, TRACE_WINDOW_S)
        first = seq
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            w0 = time.perf_counter()
            while time.perf_counter() - w0 < seconds:
                total, lk, host = request(seq, keep=_sampled(seed, seq))
                lat.append(total)
                lookup_s.append(lk)
                seq += 1
            window_s = time.perf_counter() - w0
        kept[seq - 1] = host          # the last request is always compared
        c1, compiles = system.counters(), counter.count - compiles0
        if trace:
            jax.profiler.stop_trace()
        stats = device.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))

        # after the window: the mapper's replayed view, both routes,
        # and at 64-bit keys the miss probe on each
        probe = (gen.miss_probe(seed, keys, read_keys.shape[1])
                 if key_bits > 32 else None)
        in_sync_after = system.wait_in_sync(SYNC_TIMEOUT_S)
        for route in ("traditional", "shortcut"):
            system.force_route(route)
            system.wait_in_sync(SYNC_TIMEOUT_S)
            forced[("after", route)] = np.asarray(
                system.lookup(read_keys[0]))
            if probe is not None:
                forced[("probe", route)] = np.asarray(system.lookup(probe))
        system.force_route(None)
        final_entries = system.entries()
        layout = system.layout() if trace else None
    finally:
        system.close()
    del system
    gc.collect()
    if trace:
        tr = tracing.load(_xplane(trace_dir))
        if tr.modules or device.platform == "tpu":
            reduced = tracing.reduce(tr)
        # off the chip the trace holds no device plane, and no device
        # number is reported
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the plain reference, after the program's state is freed
    ref = reference.KVMap(keys, values)
    wrong = 0
    compared = 0
    for route in ("traditional", "shortcut"):
        wrong += int(np.count_nonzero(forced[("warm", route)]
                                      != ref.get(read_keys[0])))
    for s in range(seq):
        e = pool.entry(s)
        if upd_keys is not None:
            ref.update(upd_keys[e], pool.update_values(s))
        if s in kept:
            wrong += int(np.count_nonzero(kept[s] != ref.get(read_keys[e])))
            compared += 1
    after = 0
    for route in ("traditional", "shortcut"):
        after += int(np.count_nonzero(forced[("after", route)]
                                      != ref.get(read_keys[0])))
    checks = {
        "wrong_reads": {"value": wrong, "limit": 0},
        "wrong_reads_after_sync": {"value": after, "limit": 0},
        "records_missing": {"value": n - min(entries, final_entries),
                            "limit": 0},
        "inserts_dropped": {"value": dropped, "limit": 0},
        "mapper_out_of_sync": {
            "value": int(not (in_sync_after_load and in_sync_after)),
            "limit": 0},
    }
    if probe is not None:
        checks["wrong_misses"] = {"value": sum(
            int(np.count_nonzero(forced[("probe", route)]
                                 != gen.miss(value_bits)))
            for route in ("traditional", "shortcut")), "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    requests = seq - first
    log(_window_summary(np.asarray(lat), compiles))
    log(f"compared {compared} requests' reads ({compared} x "
        f"{read_keys.shape[1]}) and the forced-route reads with the "
        f"reference" + ("" if probe is None else
                        f", and {probe.size} never-stored keys a route"))

    ctx = SimpleNamespace(
        workload=workload, config=cfg, traffic=traffic, seed=seed,
        device_kind=device.device_kind, requests=requests,
        ops=requests * (read_keys.shape[1]
                        + (0 if upd_keys is None else upd_keys.shape[1])),
        window_s=window_s, latencies_s=np.asarray(lat),
        lookup_s=np.asarray(lookup_s), setup_s=setup_s,
        counters_before=c0, counters_after=c1, window_compiles=compiles,
        trace=reduced,
        lookup_bytes=(lambda: _lookup_bytes(layout, pool, read_keys,
                                            range(first, seq), cfg))
        if layout is not None else None)
    metrics = read_metrics(spec.per_layer if trace else spec.end_to_end,
                           ctx, root)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": requests, "failed": 0,
              "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": tracing.top(reduced.by_jit),
            "idle_gaps": tracing.top(reduced.idle_by_span)}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def _window_summary(lat: np.ndarray, compiles: int) -> str:
    """One line on how the window's request times spread: their
    quantiles, the requests over twice the median (host stalls), and
    the mean of each half (drift within the run)."""
    if lat.size == 0:
        return "window: no request"
    ms = lat * 1e3
    half = ms.size // 2
    q50, q95, q99 = np.percentile(ms, [50, 95, 99])
    return (f"window: {ms.size} requests, mean {ms.mean():.4f} ms, median "
            f"{q50:.4f}, p95 {q95:.4f}, p99 {q99:.4f}, max {ms.max():.4f}; "
            f"{int(np.count_nonzero(ms > 2 * q50))} over twice the median; "
            f"halves' means {ms[:half].mean() if half else ms.mean():.4f} "
            f"and {ms[half:].mean():.4f} ms; {compiles} compiles")


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host spans only, no Python calls
    opts.host_tracer_level = 2
    return opts


def _xplane(trace_dir: Path) -> str:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return str(found[-1])


def _lookup_bytes(layout, pool, read_keys, window_seqs, cfg):
    """Least bytes of the window's lookup requests, each pool entry
    weighted by how often the window issued it: ``[(weight,
    roofline.LookupBytes)]``.  Each key's shard, bucket and directory
    slot are the system's, from ``layout()``; every read of the traffic
    hits a stored key."""
    skeys, sshard, sbucket, sslot = layout
    entries, counts = np.unique(
        np.asarray([pool.entry(s) for s in window_seqs]), return_counts=True)
    out = []
    for e, c in zip(entries, counts):
        q = read_keys[e]
        i = np.minimum(np.searchsorted(skeys, q), skeys.size - 1)
        out.append((int(c), roofline.lookup_bytes(
            sshard[i], sbucket[i], sslot[i], skeys[i] == q,
            int(cfg["bucket_slots"]),
            key_bytes=int(cfg.get("key_bits", 32)) // 8,
            value_bytes=int(cfg.get("value_bits", 32)) // 8)))
    return out

