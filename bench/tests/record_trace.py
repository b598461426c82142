"""Record a short traced serve window on the chip, for the trace
reduction's test and for reading how the device names its operations.

    python3 bench/tests/record_trace.py --workload serve_chunkae_k1024 \
        --population 4096 --buffer-k 16 --seconds 0.2 \
        --out bench/tests/data/serve_small.xplane.pb --names ops.txt

Runs one serve cell at the given population and buffer (the rest as the
cell states it), copies the trace's ``.xplane.pb`` to ``--out``, and with
``--names`` writes every distinct device operation name with one event's
statistics. Needs the chip; prints the reduced summary.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--population", type=int)
    ap.add_argument("--buffer-k", type=int)
    ap.add_argument("--seconds", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--names")
    args = ap.parse_args()

    import jax
    from bench import harness
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
    # a short window: wait for the device after every few rounds
    from bench.drivers import serve
    out = harness.run_cell(cell, keep_trace=args.out,
                           sync_seconds=min(serve.SYNC_SECONDS, args.seconds))
    s = out.summary
    print(json.dumps({"rounds": out.counters["rounds"],
                      "window_s": s.window_s, "busy_s": s.busy_s,
                      "breakdown": s.breakdown,
                      "checks": {c.name: c.value for c in out.checks}}))
    if args.names:
        from jax.profiler import ProfileData
        seen = {}
        for plane in ProfileData.from_file(args.out).planes:
            for line in plane.lines:
                for e in line.events:
                    key = (plane.name, line.name, e.name)
                    device = not plane.name.startswith("/host:")
                    if key not in seen and (device
                                            or e.name.startswith("bench.")):
                        seen[key] = {k: str(v) for k, v in e.stats}
        with open(args.names, "w") as f:
            for (p, l, n), st in seen.items():
                f.write(f"{p} | {l} | {n} | {json.dumps(st)}\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
