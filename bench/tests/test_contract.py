"""The driver contract (``bench/harness.py``) on the CPU, with a cell of a
driver other than serve: a copy of the ``bench/`` layout gains a driver
``stub`` with checks of its own, a model of another size and two programs
that share instruction names under scopes of their own, with its
configuration, traffic, limits and a scoped metric's reader. Everything it
needs is new files and new entries; no file of the repo's ``bench/`` is
written."""
import copy
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, scopes, trace  # noqa: E402

REPO_BENCH = harness.BENCH

STUB_DRIVER = '''\
"""A driver of its own kind: two programs of one shape, each under a
scope of its own, checked against each other."""
import jax
import jax.numpy as jnp

from bench.harness import Check, Outcome

CHECKS = ("loss_err",)
PROGRAMS = ("jit_a", "jit_b")
SCOPES = ("stub.a", "stub.b")


def a(x):
    with jax.named_scope("stub.a"):
        return jnp.sin(x) * 2.0 + jnp.cos(x)


def b(x):
    with jax.named_scope("stub.b"):
        return jnp.sin(x) * 2.0 + jnp.cos(x)


def update_size(config):
    m = config["model"]
    return m["layers"] * m["width"] ** 2


def lower(cell):
    x = jax.ShapeDtypeStruct((cell.traffic["n"],), jnp.float32)
    return {"jit_a": jax.jit(a).lower(x), "jit_b": jax.jit(b).lower(x)}


def run(cell):
    x = jax.random.normal(jax.random.PRNGKey(cell.seed % 2 ** 32),
                          (cell.traffic["n"],))
    err = float(jnp.max(jnp.abs(jax.jit(a)(x) - jax.jit(b)(x))))
    return Outcome(attempted=1, failed=0,
                   checks=[Check("loss_err", err, cell.limits["loss_err"])],
                   end_to_end={"updates_per_s": 1.0, "setup_s": 0.0},
                   counters={"rounds": 1}, memory_peak_bytes=0)
'''

STUB_READER = '''\
"""Device milliseconds per round under the stub's ``stub.a`` scope."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "stub.a")
'''

STUB_FILES = {
    "drivers/stub.py": STUB_DRIVER,
    "metrics/scope_ms.stub_a.py": STUB_READER,
    "configs/stub_model.json": json.dumps({
        "name": "stub_model", "source": "none: a test's own model",
        "model": {"layers": 3, "width": 64, "update_size": 3 * 64 ** 2}}),
    "traffic/stub_mix.json": json.dumps({"driver": "stub", "n": 64}),
    "limits/stub_cell.json": json.dumps({"loss_err": 0.0}),
}
STUB_ENTRIES = {
    "configs": {"name": "stub_model", "source": "none",
                "file": "bench/configs/stub_model.json", "reduced": [],
                "why": "another model"},
    "workloads": {"name": "stub_cell", "config": "stub_model",
                  "traffic": "stub_mix", "chips": 1, "why": "another driver"},
    "per_layer": {"name": "scope_ms.stub_a", "unit": "ms", "better": "lower",
                  "source": "device_trace", "layer": "stub scope stub.a",
                  "moves": "updates_per_s", "workloads": ["stub_cell"]},
}


def _repo_files():
    out = {}
    for d, dirs, files in os.walk(REPO_BENCH):
        dirs[:] = [x for x in dirs if x not in ("__pycache__",
                                                ".pytest_cache")]
        for f in files:
            st = os.stat(os.path.join(d, f))
            out[os.path.join(d, f)] = (st.st_size, st.st_mtime_ns)
    return out


@pytest.fixture
def stub(tmp_path, monkeypatch):
    """A copy of the benchmark's layout with the stub's files added, as
    ``harness.BENCH``, and ``BENCHMARK.json`` with the stub's entries."""
    before = _repo_files()
    layout = tmp_path / "bench"
    shutil.copytree(REPO_BENCH, layout,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for rel, text in STUB_FILES.items():
        (layout / rel).write_text(text)
    bench = harness.load_json(os.pardir, "BENCHMARK.json")
    for key, entry in STUB_ENTRIES.items():
        bench[key].append(copy.deepcopy(entry))
    monkeypatch.setattr(harness, "BENCH", str(layout))
    yield {"bench": bench, "dir": layout}
    assert _repo_files() == before


def _cell(bench):
    w = next(w for w in bench["workloads"] if w["name"] == "stub_cell")
    return harness.make_cell(w, 2 ** 33 + 5, 1.0, True, time.perf_counter())


def test_a_cell_of_another_driver_keeps_the_contract(stub):
    """The stub's cell, with its own checks and a model of another size,
    passes the contract beside the serve cells, resolves its files and
    runs."""
    bench = stub["bench"]
    assert harness.contract_problems(bench) == []
    cell = _cell(bench)
    driver = harness.driver_of(cell)
    assert driver is not harness.driver_of(bench["workloads"][0])
    assert driver.update_size(cell.config) != 550_586
    out = harness.run_cell(cell)
    assert out.correct and [c.name for c in out.checks] == ["loss_err"]
    reader = harness.load_module("metrics", "scope_ms.stub_a")
    assert callable(reader.read)


def test_a_shared_instruction_name_keeps_its_program_scope(stub):
    """The two programs' texts, compiled on the CPU, hold the same
    instruction names under different scopes; the map by program sends
    each to its own program's scope, and the stub's reader, on a window of
    synthetic ops, reads only ``jit_a``'s. A harness copy (a program
    outside ``PROGRAMS``) does not count against the coverage: counted, it
    would put the map under 99%."""
    cell = _cell(stub["bench"])
    m = scopes.scope_map(cell)
    shared = ({n for p, n in m if p == "jit_a"}
              & {n for p, n in m if p == "jit_b"})
    both = sorted(n for n in shared if m["jit_a", n] == "stub.a"
                  and m["jit_b", n] == "stub.b")
    assert both, m
    name = both[0]
    ev = trace.Event
    tr = trace.Trace(
        ops=[[ev(name, 0, 40, "jit_a"), ev(name, 50, 20, "jit_b"),
              ev("copy.1", 80, 5, "jit__lambda")]],
        spans=[ev("bench.window", 0, 100)], runs=[[]])
    summary = trace.summarize(tr)
    assert [n for n, _ in summary.breakdown["device_ops"]] == [
        f"jit_a:{name}", f"jit_b:{name}", "copy.1"]
    out = harness.Outcome(attempted=4, failed=0, checks=[], end_to_end={},
                          counters={"rounds": 4}, memory_peak_bytes=0,
                          summary=summary)
    ctx = harness.MetricContext(cell, out, "cpu")
    assert scopes.coverage(summary, m, ("jit_a", "jit_b")) == 1.0
    assert scopes.coverage(summary, m, ("jit_a", "jit_b", "jit__lambda")) \
        < scopes.MIN_COVERAGE
    reader = harness.load_module("metrics", "scope_ms.stub_a")
    assert reader.read(ctx) == pytest.approx(1e3 * 40e-9 / 4)
    assert scopes.ms_per_round(ctx, "stub.b") == pytest.approx(1e3 * 20e-9
                                                               / 4)


SERVE_LIMITS = {"state_mismatch": 0, "times_err": 1e-6, "agg_err": 0.1}


@pytest.mark.parametrize("fault", ["serve_limits", "update_size",
                                   "unknown_cell", "no_family",
                                   "missing_export"])
def test_a_cell_that_breaks_the_contract_is_reported(stub, fault):
    """Each break of the contract by the stub's cell is one problem, and
    names the cell or the metric."""
    bench, layout = stub["bench"], stub["dir"]
    driver = layout / "drivers" / "stub.py"
    if fault == "serve_limits":
        (layout / "limits" / "stub_cell.json").write_text(
            json.dumps(SERVE_LIMITS))
    elif fault == "update_size":
        config = json.loads((layout / "configs" / "stub_model.json")
                            .read_text())
        config["model"]["update_size"] = 550_586
        (layout / "configs" / "stub_model.json").write_text(json.dumps(config))
    elif fault == "unknown_cell":
        bench["per_layer"][-1]["workloads"].append("no_such_cell")
    elif fault == "no_family":
        driver.write_text(STUB_DRIVER.replace(
            'SCOPES = ("stub.a", "stub.b")', 'SCOPES = ("a", "b")'))
    else:
        driver.write_text(STUB_DRIVER.replace("def update_size",
                                              "def _update_size"))
    problems = harness.contract_problems(bench)
    assert len(problems) == 1, problems
    want = "scope_ms.stub_a" if fault == "unknown_cell" else "stub_cell"
    assert problems[0].startswith(want + ":"), problems
