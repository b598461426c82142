"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one line per row). Default sizes are
CPU-bounded; REPRO_BENCH_FULL=1 runs paper-scale versions. Select subsets
with ``python -m benchmarks.run --tables mnist_ae,savings_ratio``.

``--json DIR`` additionally persists one ``BENCH_<table>.json`` artifact per
table — the benchmark-trajectory format (schema below) that
``benchmarks/check_regression.py`` diffs against the committed baselines in
``benchmarks/baselines/`` to gate perf regressions in CI (DESIGN.md §11.3).
The JSON rows are exactly the CSV rows (asserted row-for-row in
tests/test_bench_artifacts.py): one measurement, two sinks.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import List, Optional, Tuple

SCHEMA_VERSION = 1


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, check=True,
        ).stdout.strip()
    except Exception:                                  # noqa: BLE001
        return "unknown"


def _backend() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:                                  # noqa: BLE001
        return "unknown"


def write_artifact(json_dir: str, name: str,
                   rows: List[Tuple[str, float, str]],
                   error: Optional[str] = None) -> str:
    """Persist one table's measurements as ``BENCH_<name>.json``. Rows keep
    full float precision here (the CSV prints one decimal); ``roofline`` is
    attached for tables registered in ``tables.ROOFLINES`` — the analytic
    placement of each decode→aggregate variant against the memory roof
    (repro.roofline.analysis, DESIGN.md §11.3)."""
    doc = {
        "schema": SCHEMA_VERSION,
        "name": name,
        "git_rev": _git_rev(),
        "backend": _backend(),
        "rows": [
            {"name": rname, "us_per_call": us, "derived": derived}
            for rname, us, derived in rows
        ],
    }
    if error is not None:
        doc["error"] = error
    try:
        from benchmarks.tables import ROOFLINES
        roof_fn = ROOFLINES.get(name)
    except Exception:                                  # noqa: BLE001
        roof_fn = None
    if roof_fn is not None and error is None:
        try:
            doc["roofline"] = roof_fn()
        except Exception as e:                         # noqa: BLE001
            doc["roofline"] = {"error": repr(e)}
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def merge_min_rows(
        all_rows: List[List[Tuple[str, float, str]]],
) -> List[Tuple[str, float, str]]:
    """Fold repeated table runs into one row set: rows are matched by name and
    the fastest ``us_per_call`` (with its derived string) wins. Min-of-many
    converges to the machine's true floor, which is what the regression gate
    needs — single-shot timings on a shared host jitter well past the 20%
    threshold (DESIGN.md §11.3). Non-timed rows (us<=0) keep their first
    occurrence; row order follows the first repeat."""
    merged: dict = {}
    order: List[str] = []
    for rows in all_rows:
        for rname, us, derived in rows:
            if rname not in merged:
                merged[rname] = (rname, us, derived)
                order.append(rname)
            else:
                _, best, _ = merged[rname]
                if us > 0 and (best <= 0 or us < best):
                    merged[rname] = (rname, us, derived)
    return [merged[n] for n in order]


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tables", default="all",
                    help="comma-separated table names (or 'all')")
    ap.add_argument("--list", action="store_true",
                    help="print available table names and exit")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="also write BENCH_<table>.json artifacts to DIR")
    ap.add_argument("--repeats", type=int, default=1, metavar="N",
                    help="run each table N times and keep the per-row minimum"
                         " (use >=3 when generating baselines or gating)")
    args = ap.parse_args(argv)

    from benchmarks.tables import ALL_TABLES
    if args.list:
        for name, _ in ALL_TABLES:
            print(name)
        return
    selected = {t.strip() for t in args.tables.split(",")}
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in ALL_TABLES:
        if args.tables != "all" and name not in selected:
            continue
        t0 = time.perf_counter()
        try:
            # materialize INSIDE the try: a generator table that raises
            # mid-iteration must produce the ERROR row, not leak a partial
            # CSV prefix that parses as a clean (shorter) table
            rows_runs = [[tuple(r) for r in fn()]
                         for _ in range(max(1, args.repeats))]
            rows = (merge_min_rows(rows_runs) if len(rows_runs) > 1
                    else rows_runs[0])
        except Exception as e:                        # noqa: BLE001
            print(f"{name},0,ERROR: {e!r}")
            failures += 1
            if args.json:
                write_artifact(args.json, name, [], error=repr(e))
            continue
        for rname, us, derived in rows:
            print(f"{rname},{us:.1f},{derived}")
        if args.json:
            write_artifact(args.json, name, rows)
        print(f"# table {name} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
