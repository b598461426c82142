"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout;
its configuration, traffic mix, generator, limits and per-layer metric
readers are files under ``bench/`` found by name (``bench/harness.py``).
The run makes its inputs from ``--seed``, warms up, measures for
``--seconds``, checks a sample of what the timed path produced against a
plain reference, and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` ``breakdown``), then ``checks``, each compared
number beside its limit, which are also the last lines of standard error.
With ``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, it exits non-zero before the cell starts. JAX's persistent
compilation cache lives in ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def _fail(msg: str, code: int) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


# Dispatches the TPU runtime lets a process keep queued on the device. The
# serve loop keeps a second of rounds queued (bench/drivers/serve.py), so
# that the chip stays busy while the host stands still; the runtime's own
# limit is lower and would drain the queue within tens of milliseconds.
MAX_INFLIGHT = 4096


def _setup_jax() -> None:
    import jax
    jax.config.update("jax_pjrt_client_create_options",
                      {"max_inflight_computations": MAX_INFLIGHT})
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # every program, however quick to compile, so that later runs compile
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        import repro  # noqa: F401  the program under test
    except (OSError, ImportError, ValueError) as e:
        return _fail(f"cannot load the benchmark or the program: {e}", 2)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return _fail(f"no workload {args.workload!r} in BENCHMARK.json", 2)
    workload = cells[args.workload]

    import jax
    _setup_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"needs a TPU, JAX found {devices[0].platform!r}", 3)
    if len(devices) < workload["chips"]:
        return _fail(f"the cell asks for {workload['chips']} chips, JAX "
                     f"found {len(devices)}", 3)

    from bench import harness
    cell = harness.make_cell(workload, args.seed, args.seconds,
                             bool(args.trace), T_START)
    outcome = harness.run_cell(cell)
    kind = devices[0].device_kind

    if args.trace:
        ctx = harness.MetricContext(cell, outcome, kind)
        metrics = {}
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            value = harness.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": outcome.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if args.workload in m.get("workloads", [args.workload])}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = outcome.summary.busy_s
        device["window_s"] = outcome.summary.window_s
        result["breakdown"] = outcome.summary.breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    for c in outcome.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
