"""The scope readers (``bench/scopes.py``) on the CPU: the parse of a
step's text into scopes, the checks a map has to pass before any metric
reads it, and every scope metric on the two serve windows recorded on a
TPU v5e with the compiled text of their step (``conftest.py``), against
the rule by instruction name alone that the map by program replaced."""
import collections
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, scopes  # noqa: E402
from bench.drivers import serve  # noqa: E402
from conftest import (BUFFER_K, POPULATION, RECORDINGS,  # noqa: E402
                      recorded_text)
from repro.core.serve import SCOPES  # noqa: E402

PREFIX = scopes.family(SCOPES)

HAND_WRITTEN = """\
HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/jit(step)/serve.aggregate/add"}
}

ENTRY %main.9 (state.1: f32[4]) -> (f32[4], s32[4]) {
  %state.1 = f32[4]{0} parameter(0), metadata={op_name="state"}
  %copy-start = (f32[4]{0:S(1)}, f32[4]{0}, u32[]{:S(2)}) copy-start(%state.1)
  %copy-done = f32[4]{0:S(1)} copy-done(%copy-start)
  %sort = (f32[4]{0}, s32[4]{0}) sort(%copy-done, %iota), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/jit(step)/serve.pop/sort"}
  %fusion.7 = f32[4]{0} fusion(%copy-done), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jit(step)/serve.aggregate/jit(decode_and_aggregate)/dot_general"}
  %copy-start.1 = (f32[4]{0}, f32[4]{0:S(1)}, u32[]{:S(2)}) copy-start(%fusion.7)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %negate = f32[4]{0} negate(%copy-done.1), metadata={op_name="jit(step)/jit(step)/outside"}
  ROOT %tuple = (f32[4]{0}, s32[4]{0}) tuple(%negate, %sort)
}
"""


def test_parse_of_a_hand_written_module():
    """Each instruction takes the first ``serve.*`` component of its
    ``op_name``; one without metadata takes its source's scope, else its
    first consumer's; the program's own ops outside every scope, and the
    parameters, stay unscoped."""
    assert PREFIX == "serve."
    m = scopes.parse(HAND_WRITTEN, PREFIX)
    assert m == {
        "param_0": "serve.aggregate",   # its consumer's (inside a fusion)
        "add.1": "serve.aggregate",
        "state.1": scopes.UNSCOPED,
        "copy-start": "serve.pop",      # no source scope: first consumer
        "copy-done": "serve.pop",
        "sort": "serve.pop",
        "fusion.7": "serve.aggregate",
        "copy-start.1": "serve.aggregate",   # its source's
        "copy-done.1": "serve.aggregate",
        "negate": scopes.UNSCOPED,
        "tuple": "serve.pop",           # its first scoped source's
    }
    assert scopes.scope_of("jit(step)/serve.pop/jit(f)/serve.x/sort",
                           PREFIX) == "serve.pop"


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.pardir, "BENCHMARK.json")


def test_recorded_text_names_every_scope(recording):
    m = scopes.parse(recording["text"], PREFIX)
    assert set(SCOPES) <= set(m.values())
    assert m["sort"] == "serve.pop"
    if recording["config"] == "cifar_chunkae_q8":
        kernels = [n for n in m if n.split(".")[0] in
                   ("fused_dense", "fused_decode_agg",
                    "dequantize_blocks_2d")]
        assert kernels and all(m[n] == "serve.aggregate" for n in kernels)


def _step_ops(recording, lo=float("-inf"), hi=float("inf")):
    """``(name, seconds)`` of every device op of the recording's trace that
    ran inside a run of the driver's programs and started inside
    ``[lo, hi]``."""
    return [(e.name, e.dur_ns * 1e-9) for dev in recording["trace"].ops
            for e in dev
            if e.program in serve.PROGRAMS and lo <= e.start_ns <= hi]


def test_scopes_cover_the_step(recording):
    """At least 99% of the device time of the step's programs maps to one
    of the four scopes."""
    m = scopes.parse(recording["text"], PREFIX)
    ops = _step_ops(recording)
    total = sum(s for _, s in ops)
    mapped = sum(s for n, s in ops if m.get(n) in SCOPES)
    assert total > 0.0
    assert mapped >= 0.99 * total, (mapped, total)


def _context(bench, recording):
    tr = recording["trace"]
    lo, hi = tr.window()
    rounds = sum(1 for s in tr.spans
                 if s.name == "bench.round" and lo <= s.start_ns <= hi)
    w = next(c for c in bench["workloads"]
             if c["config"] == recording["config"])
    cell = harness.make_cell(w, 1, 1.0, True, 0.0)
    cell.traffic.update(population=POPULATION, buffer_k=BUFFER_K)
    outcome = harness.Outcome(
        attempted=rounds, failed=0, checks=[], end_to_end={},
        counters={"rounds": rounds, "buffer_k": BUFFER_K,
                  "population": POPULATION},
        memory_peak_bytes=0, summary=recording["summary"])
    return harness.MetricContext(cell, outcome, "TPU v5 lite")


def test_metric_readers_on_scoped_recordings(bench, recording):
    """Every metric listed for a cell of the recording's configuration
    reads above 0, no share above 100%; the pop's scope holds the sort,
    the scoped roofline counts more time than the kernels' roofline, and
    the four scopes add up to the step's device time per round."""
    ctx = _context(bench, recording)
    cells = {w["name"] for w in bench["workloads"]
             if w["config"] == recording["config"]}
    read = {}
    for m in bench["per_layer"]:
        if not cells & set(m["workloads"]):
            continue
        value = harness.load_module("metrics", m["name"]).read(ctx)
        assert value is not None and value > 0.0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, (m["name"], value)
        read[m["name"]] = value
    assert read["scope_ms.pop"] >= read["pop_ms"]
    if "decode_agg_roofline" in read:
        assert read["aggregate_roofline"] <= read["decode_agg_roofline"]
    rounds = ctx.counters["rounds"]
    lo, hi = recording["trace"].window()
    step_ms = 1e3 * sum(s for _, s in _step_ops(recording, lo, hi)) / rounds
    scoped_ms = sum(scopes.ms_per_round(ctx, s) or 0.0 for s in SCOPES)
    assert scoped_ms == pytest.approx(step_ms, rel=0.02)


def _scope_metrics(bench):
    return [m["name"] for m in bench["per_layer"]
            if m["name"].startswith("scope_ms.")
            or m["name"] == "aggregate_roofline"]


@pytest.mark.parametrize("case", ["other_program", "missing_scope"])
def test_a_map_that_fails_its_checks_gives_no_metric(bench, recording,
                                                     monkeypatch, case):
    """The scope metrics read nothing, and do not raise, where the
    compiled text is another program's (the other recording's step, whose
    names cover under 99% of this window's device time) or lacks a scope
    of the program's ``SCOPES``."""
    if case == "other_program":
        text = recorded_text(next(c for c in RECORDINGS
                                  if c != recording["config"]))
    else:
        text = recording["text"].replace("serve.redispatch/",
                                         "serve.elsewhere/")
    monkeypatch.setattr(scopes, "program_texts",
                        lambda cell: {serve.PROGRAMS[0]: text})
    ctx = _context(bench, recording)
    if case == "other_program":
        m = {(serve.PROGRAMS[0], n): s
             for n, s in scopes.parse(text, PREFIX).items()}
        assert scopes.coverage(ctx.summary, m, serve.PROGRAMS) \
            < scopes.MIN_COVERAGE
    for name in _scope_metrics(bench):
        assert harness.load_module("metrics", name).read(ctx) is None, name



# The rule the map by program replaced, as it stood: each instruction of
# the step's text takes the first ``serve.*`` component of its ``op_name``
# (else the scope of what it copies from, else into), keyed by instruction
# name alone, so that it also placed another program's op of that name.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? [a-z][\w\-]*\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _by_name(text):
    def first_serve(op_name):
        return next((p for p in op_name.split("/")
                     if p.startswith("serve.")), scopes.UNSCOPED)

    def operands(line, start):
        depth = 0
        for i in range(start, len(line)):
            depth += {"(": 1, ")": -1}.get(line[i], 0)
            if depth == 0:
                return _OPERAND.findall(line, start, i)
        return _OPERAND.findall(line, start)

    own, srcs, users = {}, {}, {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, op = m.group(1), _OP_NAME.search(line)
        own[name] = first_serve(op.group(1)) if op else None
        srcs[name] = operands(line, m.end() - 1)
        for o in srcs[name]:
            users.setdefault(o, []).append(name)

    def inherit(name, edges):
        seen, todo = {name}, collections.deque(edges.get(name, ()))
        while todo:
            n = todo.popleft()
            if n in seen or n not in own:
                continue
            seen.add(n)
            if own[n] is None:
                todo.extend(edges.get(n, ()))
            elif own[n] != scopes.UNSCOPED:
                return own[n]
        return None

    return {n: s if s is not None
            else inherit(n, srcs) or inherit(n, users) or scopes.UNSCOPED
            for n, s in own.items()}


def test_map_by_program_agrees_with_the_rule_by_name(bench, recording,
                                                      monkeypatch):
    """On the step's program the map by program is the rule by name, entry
    for entry. Every scoped metric reads, bit for bit, what the rule by
    name reads over the step's own ops; the rule by name also counted the
    harness's copies (``_copy``, a program of its own) whose instruction
    names the step shares, and its ``scope_ms.*`` readings exceed these by
    exactly their time. The coverage is no lower than the rule by name's,
    which counted those copies in its total."""
    ctx = _context(bench, recording)
    new = scopes.scope_map(ctx.cell)
    old = _by_name(recording["text"])
    assert {p for p, _ in new} == set(serve.PROGRAMS)
    assert {n: s for (p, n), s in new.items()} == old
    s, rounds = ctx.summary, ctx.counters["rounds"]
    copies = {sc: s.program_op_seconds(
        lambda p, n: p not in serve.PROGRAMS and old.get(n) == sc)
        for sc in SCOPES}
    assert any(copies.values())     # the recordings do share names

    def rule_by_name(step_only):
        def scope_seconds(ctx, scope):
            return ctx.summary.program_op_seconds(
                lambda p, n: (p in serve.PROGRAMS or not step_only)
                and old.get(n) == scope)
        return scope_seconds

    cells = {w["name"] for w in bench["workloads"]
             if w["config"] == recording["config"]}
    current = scopes.scope_seconds
    for name in _scope_metrics(bench):
        if not cells & set(next(m for m in bench["per_layer"]
                                if m["name"] == name)["workloads"]):
            continue
        reader = harness.load_module("metrics", name)
        value = reader.read(ctx)
        monkeypatch.setattr(scopes, "scope_seconds", rule_by_name(True))
        assert reader.read(ctx) == value, name
        monkeypatch.setattr(scopes, "scope_seconds", rule_by_name(False))
        by_name = reader.read(ctx)
        monkeypatch.setattr(scopes, "scope_seconds", current)
        if name.startswith("scope_ms."):
            extra = 1e3 * copies["serve." + name.split(".", 1)[1]] / rounds
            assert by_name - value == pytest.approx(extra, rel=1e-9,
                                                    abs=1e-15), name
    old_share = (s.op_seconds(lambda n: old.get(n, scopes.UNSCOPED)
                              != scopes.UNSCOPED)
                 / s.op_seconds(lambda n: True))
    assert scopes.coverage(s, new, serve.PROGRAMS) >= old_share
