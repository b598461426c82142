"""Readings that a cell's limits are set from, on the chip at the cell's
own size: the compared numbers of the program on a dozen seeds or more and
of the control (the plain reference in bfloat16 in the program's place) on
three or more, all in one process so that set-up is paid once per compile.

    python3 bench/tests/readings.py --workload serve_chunkae_k1024 \
        --seeds 12 --control-seeds 3 --seconds 2

Prints one JSON line per run (``kind``, ``seed``, the worst reading of each
compared number over the run's checked rounds), then a summary line with
the program's largest and the control's smallest reading of each number.
Seeds are drawn above 2**32, where a 32-bit seed would overflow.
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=2 ** 32 + 1000)
    args = ap.parse_args()

    import jax
    from bench import harness
    from bench.drivers import serve
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    bench = harness.load_json(os.pardir, "BENCHMARK.json")
    w = next(c for c in bench["workloads"] if c["name"] == args.workload)
    runs = ([("program", None, args.first_seed + i)
             for i in range(args.seeds)]
            + [("control", serve.control_step, args.first_seed + 500 + i)
               for i in range(args.control_seeds)])
    worst = {"program": {}, "control": {}}
    for kind, step, seed in runs:
        cell = harness.make_cell(w, seed, args.seconds, False,
                                 time.perf_counter())
        try:
            out = harness.run_cell(cell, make_step=step)
        except Exception as e:                          # noqa: BLE001
            # a control that crashes has failed and sets no upper reading
            print(json.dumps({"kind": kind, "seed": seed,
                              "error": repr(e)[:500]}), flush=True)
            continue
        vals = {c.name: c.value for c in out.checks}
        print(json.dumps({"kind": kind, "seed": seed, "rounds": out.attempted,
                          "checked": out.counters["checked_rounds"],
                          "updates_per_s": out.end_to_end["updates_per_s"],
                          **vals}), flush=True)
        agg = max if kind == "program" else min
        for k, v in vals.items():
            worst[kind][k] = agg(worst[kind].get(k, v), v)
    print(json.dumps({"summary": args.workload,
                      "program_max": worst["program"],
                      "control_min": worst["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
