"""Record a traced serve window on the chip, reduce it by the step's
scopes, and split the device's idle time into its gaps between programs
and inside them.

    python3 bench/tests/record_scopes.py --workload serve_chunkae_k1024 \
        --population 4096 --buffer-k 16 --seconds 0.15 \
        --out bench/tests/data/scoped_chunkae_q8_k16

Runs one serve cell, at the given population and buffer where they are
given (the rest as the cell states it), and prints one JSON line: the
rounds and ``updates_per_s`` of the window, device seconds and event
counts by scope (``bench/scopes.py``) with the longest operations of
each, and the device's idle gaps, those between two programs (``XLA
Modules`` line) apart from those between the ops of one, with the rounds
the host had dispatched and the device not yet started (runs of the
driver's ``PROGRAMS``) when each gap between programs began. Such a gap
with rounds queued is the device's turnaround between programs; one with
none is the host starving it.
With ``--out`` it also writes ``<out>.xplane.pb.gz``, the trace, and
``<out>.hlo.txt.gz``, the step's text, for ``test_scopes.py``. Needs the
chip; sets up JAX as ``bench/run.py`` does.
"""
import argparse
import bisect
import gzip
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def by_scope(summary, scope_map, top=5):
    """Device seconds, event counts and longest operations per scope;
    ``scope_map`` is keyed by program and instruction."""
    seconds, counts, ops = {}, {}, {}
    for dev in summary.ops:
        for e in dev:
            s = scope_map.get((e.program, e.name),
                              "not in the driver's programs")
            seconds[s] = seconds.get(s, 0.0) + e.dur_ns * 1e-9
            counts[s] = counts.get(s, 0) + 1
            per = ops.setdefault(s, {})
            per[e.name] = per.get(e.name, 0.0) + e.dur_ns * 1e-9
    return {s: {"seconds": seconds[s], "events": counts[s],
                "top": sorted(ops[s].items(), key=lambda kv: -kv[1])[:top]}
            for s in seconds}


def _spread(us):
    if not us:
        return {"count": 0}
    q = statistics.quantiles(us, n=100) if len(us) > 1 else us * 99
    return {"count": len(us), "sum_s": sum(us) * 1e-6,
            "median_us": statistics.median(us), "p99_us": q[98],
            "max_us": max(us)}


def idle_gaps(path, programs):
    """The device's idle gaps in the window, split into those between two
    programs (``XLA Modules`` line) and those between the ops of one, and
    for each gap between programs the rounds queued when it began: the
    ``bench.round`` dispatches ended on the host by then, less the runs of
    ``programs`` the device had started."""
    from bench import trace
    tr = trace.load(path)
    lo, hi = tr.window()
    mods = [(a, b, name in programs) for a, b, name in tr.runs[0]]
    rounds_done = sorted(s.end_ns for s in tr.spans
                         if s.name == "bench.round")
    starts = [a for a, _, _ in mods]
    step_starts = [a for a, _, step in mods if step]
    between, inside, queued = [], [], []
    for a, b in trace.gaps(trace.clip(tr.ops[0], lo, hi), lo, hi):
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and mods[i][1] >= b:
            inside.append((b - a) * 1e-3)
        else:
            between.append((b - a) * 1e-3)
            queued.append(bisect.bisect_right(rounds_done, a)
                          - bisect.bisect_right(step_starts, a))
    out = {"window_s": (hi - lo) * 1e-9,
           "between_programs": _spread(between),
           "inside_programs": _spread(inside)}
    if queued:
        out["between_programs"].update(
            with_no_round_queued=sum(1 for n in queued if n <= 0),
            queued_median=statistics.median(queued))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--population", type=int)
    ap.add_argument("--buffer-k", type=int)
    ap.add_argument("--seconds", type=float, default=0.15)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    from bench import harness, scopes
    from bench.drivers import serve
    from bench.run import _setup_jax
    _setup_jax()        # run.py's queue of dispatches and compile cache
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    bench = harness.load_json(os.pardir, "BENCHMARK.json")
    w = next(c for c in bench["workloads"] if c["name"] == args.workload)
    cell = harness.make_cell(w, args.seed, args.seconds, True,
                             time.perf_counter())
    if args.population:
        cell.traffic["population"] = args.population
    if args.buffer_k:
        cell.traffic["buffer_k"] = args.buffer_k
    tmp = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        raw = os.path.join(tmp, "trace.xplane.pb")
        # a short window waits for the device after every few rounds
        out = harness.run_cell(cell, keep_trace=raw, sync_seconds=min(
            serve.SYNC_SECONDS, args.seconds))
        s = out.summary
        driver = harness.driver_of(cell)
        gaps = idle_gaps(raw, driver.PROGRAMS)
        if args.out:
            with open(raw, "rb") as src, \
                    gzip.open(args.out + ".xplane.pb.gz", "wb") as dst:
                shutil.copyfileobj(src, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    texts = scopes.program_texts(cell)
    if args.out:
        with gzip.open(args.out + ".hlo.txt.gz", "wt") as f:
            f.write(texts[driver.PROGRAMS[0]])
    prefix = scopes.family(driver.SCOPES)
    scope_map = {(p, n): sc for p, text in texts.items()
                 for n, sc in scopes.parse(text, prefix).items()}
    rounds = out.counters["rounds"]
    print(json.dumps({
        "workload": args.workload, "traffic": cell.traffic,
        "rounds": rounds, "window_s": s.window_s, "busy_s": s.busy_s,
        "updates_per_s": cell.traffic["buffer_k"] * rounds / s.window_s,
        "scopes": by_scope(s, scope_map), "idle_gaps": gaps,
        "checks": {c.name: c.value for c in out.checks}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
