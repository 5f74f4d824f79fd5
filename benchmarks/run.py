"""Benchmark harness entry point: one function per paper table/figure.

``python -m benchmarks.run [--scale S] [--only table1,fig2,...]
                           [--json PATH] [--compare PREV.json]
                           [--strict]``

Prints ``bench,name,value,unit,extra`` CSV rows; ``--json PATH``
additionally writes the full Row list as structured JSON
(``bench, name, value, unit, extra, wall``) — the machine-readable perf
trajectory CI archives per commit.  ``--compare PREV.json`` diffs the
run against a previous ``--json`` artifact and prints a WARNING for
every row regressed by more than 2x; with ``--strict`` those warnings
become a hard failure (exit code 3) — CI runs strict now that artifact
history exists (ROADMAP perf-trajectory phase 2).  A missing/unreadable
previous artifact never fails, strict or not (first run, expired
artifact).  The roofline table (§Roofline, from the multi-pod dry-run)
is appended when dry-run records exist under results/dryrun_baseline.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback

from benchmarks.common import Row, emit
from repro.compile_cache import enable_compile_cache

ALL = ("table1", "fig2", "fig4", "fig5", "fig7", "fig8", "kv_shortcut",
       "sharded")

# Per-row strict-compare factors, keyed ``(bench, name)``; rows not
# listed use DEFAULT_FACTOR.  Calibrated from 4 repeated
# ``--scale 0.002`` runs on a single-core CI-class host: each bound is
# ~1.7x the observed max/min spread of its row.  Three bands:
#
#   * 1.3x  — spread stayed under ~12% (deterministic footprints, the
#     N>=4 churn/cached rows, the big fig7 insert walls);
#   * 1.5-1.7x — spread 12-35%;
#   * >2x   — rows whose spread already exceeded the old uniform 2.0
#     default (sub-second timings at N<=2, host-scheduling-bound pump
#     paths): a uniform 2.0 was silently flaky for these, so their
#     bounds are *loosened* to match measured reality.
#
# The replay_throughput_shards* rows pay a *deliberate* publish-side
# copy since the zero-copy lookup landed (the slice patch moved from
# the lookup path to the mapper thread) — do NOT tighten those below
# the default regardless of measured spread.
DEFAULT_FACTOR = 2.0
STRICT_FACTORS: dict = {
    # -- tight (1.3x): stable across runs ----------------------------------
    ("fig7a", "HT_total_insert"): 1.3,
    ("fig7a", "HTI_total_insert"): 1.3,
    ("fig7b", "CH_lookup"): 1.3,
    ("sharded", "insert_N1"): 1.3,
    ("sharded", "churn_lookup_N1_k1"): 1.3,
    ("sharded", "churn_lookup_N4_k1"): 1.3,
    ("sharded", "churn_lookup_N4_k4"): 1.3,
    ("sharded", "cached_speedup_N4"): 1.3,
    ("sharded", "operand_mib_N1"): 1.3,
    ("sharded", "operand_mib_N2"): 1.3,
    ("sharded", "operand_mib_N4"): 1.3,
    ("sharded", "operand_mib_N8"): 1.3,
    # -- mid (1.5-1.7x) ----------------------------------------------------
    ("fig7b", "HTI_lookup"): 1.5,
    ("fig7b", "HT_lookup"): 1.5,
    ("fig7b", "ShortcutEH_lookup"): 1.5,
    ("sharded", "batched_lookup_N4"): 1.5,
    ("sharded", "churn_lookup_N8_k8"): 1.5,
    ("sharded", "churn_lookup_N2_k1"): 1.5,
    ("sharded", "restack_lookup_N4"): 1.5,
    ("fig7b", "EH_lookup"): 1.7,
    ("fig7a", "ShortcutEH_total_insert"): 1.7,
    ("fig7a", "CH_total_insert"): 1.7,
    ("fig7a", "EH_total_insert"): 1.7,
    ("kv_shortcut", "compose_view_all_seqs"): 1.7,
    ("sharded", "batched_lookup_N8"): 1.7,
    ("sharded", "cached_speedup_N2"): 1.7,
    ("sharded", "cached_speedup_N8"): 1.7,
    ("sharded", "insert_N8"): 1.7,
    ("sharded", "restack_lookup_N8"): 1.7,
    ("sharded", "routed_lookup_N4"): 1.7,
    # -- looser than the old default (measured spread > ~1.65x) ------------
    ("kv_shortcut", "append_update_request"): 2.8,
    ("kv_shortcut", "paged_gather_context"): 2.8,
    ("kv_shortcut", "shortcut_slice_raw"): 2.8,
    ("sharded", "churn_lookup_N2_k2"): 2.8,
    ("kv_shortcut", "replay_throughput_shards1"): 3.5,
    ("kv_shortcut", "shortcut_slice_context"): 3.5,
    ("sharded", "batched_lookup_N2"): 3.5,
    ("sharded", "churn_lookup_N8_k1"): 3.5,
    ("sharded", "insert_N4"): 3.5,
    ("sharded", "restack_lookup_N1"): 3.5,
    ("sharded", "restack_lookup_N2"): 3.5,
    ("kv_shortcut", "paged_gather_raw"): 4.0,
    ("sharded", "insert_N2"): 4.5,
    ("kv_shortcut", "replay_throughput_shards2"): 6.0,
    ("sharded", "batched_lookup_N1"): 6.0,
    ("sharded", "routed_lookup_N2"): 8.0,
    ("sharded", "cached_speedup_N1"): 10.0,
}


def _strict_factor(bench: str, name: str) -> float:
    return STRICT_FACTORS.get((bench, name), DEFAULT_FACTOR)


def _regression_ratio(row: Row, prev: dict) -> float:
    """How many times worse ``row`` is than ``prev`` (1.0 = unchanged);
    0.0 for rows whose unit encodes no better/worse direction."""
    cur_v, prev_v = float(row.value), float(prev["value"])
    if cur_v <= 0 or prev_v <= 0:
        return 0.0
    base = row.unit.split("/")[0]
    if base in ("s", "ms", "us", "ns"):       # time-like: lower is better
        return cur_v / prev_v
    if base in ("B", "KiB", "MiB", "GiB"):    # footprint: lower is better
        return cur_v / prev_v
    if row.unit.endswith("/s"):               # throughput: higher is better
        return prev_v / cur_v
    if row.unit == "x":                       # speedup ratio: higher is better
        return prev_v / cur_v
    return 0.0


def compare_to_previous(rows: list, prev_path: str,
                        factor: float = None, strict: bool = False) -> int:
    """Print a WARNING per row regressed past its per-row factor
    (``STRICT_FACTORS``, default ``DEFAULT_FACTOR``) vs the previous
    ``--json`` artifact; returns the number of warnings (``main`` turns
    a nonzero count into exit code 3 under ``--strict``).  Passing
    ``factor`` overrides the table for every row (tests use this).  A
    missing or unreadable artifact is a note, not an error (first run,
    expired artifact) — strict mode only fails on *measured*
    regressions."""
    try:
        with open(prev_path) as f:
            prev_rows = json.load(f)
    except (OSError, ValueError) as e:
        print(f"compare: no usable previous artifact at {prev_path} "
              f"({e}); skipping perf diff", file=sys.stderr)
        return 0
    prev = {(r["bench"], r["name"]): r for r in prev_rows}
    warned = 0
    for r in rows:
        if r.name.startswith("_"):            # _bench_wall / _bench_error
            continue
        p = prev.get((r.bench, r.name))
        if p is None or p.get("unit") != r.unit:
            continue
        row_factor = (factor if factor is not None
                      else _strict_factor(r.bench, r.name))
        ratio = _regression_ratio(r, p)
        if ratio > row_factor:
            warned += 1
            print(f"WARNING: perf regression {r.bench},{r.name}: "
                  f"{p['value']:.6g} -> {r.value:.6g} {r.unit} "
                  f"({ratio:.2f}x worse, bound {row_factor:.2f}x)",
                  file=sys.stderr)
    if warned:
        print(f"compare: {warned} row(s) regressed past their bound vs "
              f"{prev_path} "
              f"({'FAILING (--strict)' if strict else 'warning only'})",
              file=sys.stderr)
    else:
        print(f"compare: no regressions past per-row bounds vs "
              f"{prev_path}", file=sys.stderr)
    return warned


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0 / 100,
                    help="fraction of paper-size workloads (1.0 = paper)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(ALL))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write all rows as structured JSON to PATH")
    ap.add_argument("--compare", default=None, metavar="PREV.json",
                    help="diff against a previous --json artifact and "
                         "warn on >2x regressions")
    ap.add_argument("--strict", action="store_true",
                    help="with --compare: exit 3 when any row regressed "
                         ">2x (a missing previous artifact still passes)")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()
    wanted = [b for b in args.only.split(",") if b] or list(ALL)

    rows: list = []
    failures = 0
    for name in wanted:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        try:
            bench_rows = mod.run(scale=args.scale)
            wall = time.time() - t0
            for r in bench_rows:
                r.wall = wall
            rows += bench_rows
            rows.append(Row(name, "_bench_wall", wall, "s", wall=wall))
        except Exception as e:
            failures += 1
            rows.append(Row(name, "_bench_error", 0.0, "-",
                            f"{type(e).__name__}: {e}",
                            wall=time.time() - t0))
            traceback.print_exc(file=sys.stderr)
    emit(rows)

    if args.json:
        with open(args.json, "w") as f:
            json.dump([dataclasses.asdict(r) for r in rows], f, indent=2)
            f.write("\n")
        print(f"wrote {len(rows)} rows to {args.json}", file=sys.stderr)

    regressions = 0
    if args.compare:
        regressions = compare_to_previous(rows, args.compare,
                                          strict=args.strict)

    if not args.skip_roofline:
        import os
        for d in ("results/dryrun_final", "results/dryrun_baseline"):
            if os.path.isdir(d):
                from benchmarks import roofline
                print(f"\n== Roofline (from multi-pod dry-run: {d}) ==")
                roofline.main(["--dir", d])
                break
    if failures:
        return 1
    if args.strict and regressions:
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
