"""The benchmark's shared pieces: one cell's inputs as the generators get
them, what a driver hands back, and the lookup of a cell's files by name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration is ``bench/configs/<config>.json``, the traffic mix
``bench/traffic/<traffic>.json``, whose ``driver`` key names the generator
``bench/drivers/<driver>.py``, and the limits of the comparison that
decides ``correct`` are ``bench/limits/<workload>.json``. Each per-layer
metric is read by ``bench/metrics/<metric>.py``.

Whatever belongs to one kind of traffic lives in its driver, which
exports:

* ``run(cell) -> Outcome``: one run of the cell. Its ``end_to_end`` holds
  every end-to-end metric of ``BENCHMARK.json`` that lists the cell, or
  that lists no cells;
* ``CHECKS``: the names of the limits ``run`` compares, which are the keys
  of the cell's limits file;
* ``update_size(config) -> int``: the parameter count of the model the
  program builds from ``config["model"]``, counted by the driver; the
  configuration states it as ``model.update_size``;
* ``PROGRAMS``: the names of the driver's jitted programs as the trace's
  ``XLA Modules`` line gives them (``jit_step``);
* ``lower(cell) -> {program: Lowered}``: each of those programs as the
  driver builds and calls it, with abstract arguments;
* ``SCOPES``: the names the program gives its layers with
  ``jax.named_scope``, one family with a common dotted prefix
  (``serve.pop``, ``serve.aggregate``, ...); empty where it names none.

:func:`contract_problems` holds every cell to this.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Union

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def codec_of(config: Dict) -> Dict:
    """The configuration's codec section with the update length copied in."""
    return dict(config["codec"], size=config["model"]["update_size"])


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Cell:
    """What a driver runs: one workload, one seed, one window."""
    workload: str
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    seed: int
    seconds: float
    trace: bool
    t_start: float                  # process start, host clock

    @property
    def codec(self) -> Dict:
        return codec_of(self.config)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""
    attempted: int
    failed: int
    checks: List[Check]
    end_to_end: Dict[str, float]
    counters: Dict[str, Any]
    memory_peak_bytes: int
    summary: Any = None             # trace.Summary of a traced run

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def make_cell(workload: Dict, seed: int, seconds: float, trace: bool,
              t_start: float) -> Cell:
    return Cell(workload=workload["name"],
                config=load_json("configs", workload["config"] + ".json"),
                traffic=load_json("traffic", workload["traffic"] + ".json"),
                limits=load_json("limits", workload["name"] + ".json"),
                seed=seed, seconds=seconds, trace=trace, t_start=t_start)


def driver_of(cell_or_workload: Union[Cell, Dict]):
    """The driver module of a :class:`Cell`, or of a ``BENCHMARK.json``
    workload entry."""
    if isinstance(cell_or_workload, Cell):
        traffic = cell_or_workload.traffic
    else:
        traffic = load_json("traffic", cell_or_workload["traffic"] + ".json")
    return load_module("drivers", traffic["driver"])


def run_cell(cell: Cell, **overrides) -> Outcome:
    return driver_of(cell).run(cell, **overrides)


DRIVER_EXPORTS = ("run", "CHECKS", "update_size", "PROGRAMS", "lower",
                  "SCOPES")


def contract_problems(bench: Dict) -> List[str]:
    """What keeps ``bench`` (``BENCHMARK.json`` as loaded) from the driver
    contract: a driver without one of its exports, a limits file whose
    names are not its driver's ``CHECKS``, a configuration whose
    ``model.update_size`` is not its driver's count, a driver's ``SCOPES``
    without a common dotted prefix, a per-layer metric that lists a cell
    the benchmark lacks. Nothing for a sound benchmark."""
    from bench import scopes
    problems = []
    for w in bench["workloads"]:
        cell, driver = w["name"], driver_of(w)
        missing = [x for x in DRIVER_EXPORTS if not hasattr(driver, x)]
        if missing:
            problems.append(f"{cell}: the driver lacks {missing}")
            continue
        limits = sorted(load_json("limits", cell + ".json"))
        if limits != sorted(driver.CHECKS):
            problems.append(f"{cell}: limits {limits}, the driver checks "
                            f"{sorted(driver.CHECKS)}")
        config = load_json("configs", w["config"] + ".json")
        stated, counted = (config["model"]["update_size"],
                           driver.update_size(config))
        if stated != counted:
            problems.append(f"{cell}: {w['config']} states update_size "
                            f"{stated}, the driver counts {counted}")
        if driver.SCOPES and not scopes.family(driver.SCOPES):
            problems.append(f"{cell}: the driver's SCOPES "
                            f"{list(driver.SCOPES)} share no dotted prefix")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        unknown = sorted(set(m.get("workloads", ())) - cells)
        if unknown:
            problems.append(f"{m['name']}: lists no such cells {unknown}")
    return problems


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader gets from a traced run."""
    cell: Cell
    outcome: Outcome
    device_kind: str

    @property
    def summary(self):
        return self.outcome.summary

    @property
    def counters(self) -> Dict[str, Any]:
        return self.outcome.counters
