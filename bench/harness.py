"""The benchmark's shared pieces: one cell's inputs as the generators get
them, what a driver hands back, and the lookup of a cell's files by name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration is ``bench/configs/<config>.json``, the traffic mix
``bench/traffic/<traffic>.json``, whose ``driver`` key names the generator
``bench/drivers/<driver>.py``, and the limits of the comparison that
decides ``correct`` are ``bench/limits/<workload>.json``. Each per-layer
metric is read by ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

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


def run_cell(cell: Cell, **overrides) -> Outcome:
    driver = load_module("drivers", cell.traffic["driver"])
    return driver.run(cell, **overrides)


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
