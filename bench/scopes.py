"""Device time by the serve step's own layers.

The program names the four layers of its serve step with
``jax.named_scope`` (``repro.core.serve.SCOPES``: ``serve.pop``,
``serve.payloads``, ``serve.aggregate``, ``serve.redispatch``), and XLA
carries that name stack into each HLO instruction's ``op_name`` metadata.
A profiler trace names a device operation by its instruction alone, so
the map from instruction to scope is read from the compiled step's text:
the cell's step is built and lowered as the driver builds and calls it
(``bench/program.py``), with abstract arguments, and compiled, which on
the chip finds the driver's executable in the persistent cache; then
every instruction of every computation (names are unique in a module) is
mapped to the first ``serve.*`` component of its ``op_name``
(:func:`parse`). This runs after the window of a traced run, once per
cell in a process.

Nothing at run time says that this compile returned the executable the
window ran, so the map is held to two checks before any metric reads it:
the text names every scope of ``SCOPES``, and the operations it maps to a
scope make up at least :data:`MIN_COVERAGE` of the window's device time.
A map that fails either (a program without the scopes, one that predates
them, among them) gives no metric, and the readers of the ``scope_ms.*``
and ``aggregate_roofline`` metrics then return nothing.
"""
from __future__ import annotations

import bisect
import collections
import json
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

SCOPE_PREFIX = "serve."
UNSCOPED = "unscoped"
# the least share of the window's device time the map has to place in a
# scope; the harness's own copies take under 0.01% of a 10 s window
MIN_COVERAGE = 0.99
# the trace's line of programs, one event per run of a compiled module
MODULES_LINE = "XLA Modules"
STEP_MODULE = "jit_step"

# ``  ROOT %name = <type> opcode(operands), attributes`` in
# ``Compiled.as_text()``; the non-greedy type stops at the first
# `` opcode(``, which tuple types never hold
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? [a-z][\w\-]*\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

_MAPS: Dict[tuple, Optional[Dict[str, str]]] = {}
# [summary, map, the map or nothing] of the last window checked
_CHECKED: list = []


def scope_of(op_name: str) -> str:
    """The first ``serve.*`` component of a name stack, or UNSCOPED."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def _operands(line: str, start: int) -> List[str]:
    """Instruction names inside the parentheses that open at ``start``."""
    depth = 0
    for i in range(start, len(line)):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        if depth == 0:
            return _OPERAND.findall(line, start, i)
    return _OPERAND.findall(line, start)


def parse(text: str) -> Dict[str, str]:
    """Instruction name → scope, for every instruction of an HLO module's
    text.

    An instruction the compiler added carries no ``op_name`` at all: the
    copies and slices that move a buffer between memory spaces, and those
    of copy insertion. It takes the scope of the program's instruction it
    moves data out of, found through other such instructions, else of the
    first (in program order) that it moves data into; else it is
    UNSCOPED. An instruction of the program outside every scope (the
    step's parameters among them) stays UNSCOPED, and passes no scope on.
    """
    own: Dict[str, Optional[str]] = {}
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else None
        operands[name] = _operands(line, m.end() - 1)
        for o in operands[name]:
            users.setdefault(o, []).append(name)

    def inherit(name: str, edges: Dict[str, List[str]]) -> Optional[str]:
        seen, todo = {name}, collections.deque(edges.get(name, ()))
        while todo:
            n = todo.popleft()
            if n in seen or n not in own:
                continue
            seen.add(n)
            if own[n] is None:
                todo.extend(edges.get(n, ()))
            elif own[n] != UNSCOPED:
                return own[n]
        return None

    return {name: (scope if scope is not None
                   else inherit(name, operands) or inherit(name, users)
                   or UNSCOPED)
            for name, scope in own.items()}


def program_scopes() -> Tuple[str, ...]:
    """The program's ``SCOPES``; none for a program that has no scopes."""
    from repro.core import serve
    return tuple(getattr(serve, "SCOPES", ()))


def _lower(cell):
    """The cell's step lowered as the driver calls it: the arguments left
    on the default device, as the driver's are, so the lowering is the
    driver's and its compile finds the driver's executable in the
    persistent cache."""
    import jax
    import jax.numpy as jnp
    from bench import program
    from bench.configs import reference

    spec = program.codec_spec(cell.codec)
    cfg = program.serve_config(cell.traffic, spec)
    state = jax.eval_shape(lambda: program.serve_init_state(cfg))
    dec = jax.eval_shape(
        lambda k: reference.make_decoder(cell.codec, cell.config["weights"],
                                         k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return program.serve_step(cell.codec, cfg).lower(state, dec)


def step_text(cell) -> str:
    """The compiled text of the cell's step."""
    return _lower(cell).compile().as_text()


def scope_map(cell) -> Optional[Dict[str, str]]:
    """:func:`parse` of :func:`step_text`, once per cell in a process;
    nothing where the text lacks a scope of the program's ``SCOPES``."""
    key = (cell.workload, json.dumps(cell.traffic, sort_keys=True))
    if key not in _MAPS:
        want, m = set(program_scopes()), None
        if not want:
            print("bench: the program names no scopes; no scope is read",
                  file=sys.stderr)
        else:
            t = time.perf_counter()
            m = parse(step_text(cell))
            print(f"bench: scopes of {cell.workload}'s step read in "
                  f"{time.perf_counter() - t:.2f} s", file=sys.stderr)
            missing = sorted(want - set(m.values()))
            if missing:
                print(f"bench: the compiled step lacks the scopes {missing};"
                      f" no scope is read", file=sys.stderr)
                m = None
        _MAPS[key] = m
    return _MAPS[key]


def coverage(summary, m: Dict[str, str]) -> float:
    """The share of the window's device time whose operations ``m`` maps
    to a scope."""
    total = summary.op_seconds(lambda name: True)
    mapped = summary.op_seconds(lambda name: m.get(name, UNSCOPED)
                                != UNSCOPED)
    return mapped / total if total > 0.0 else 0.0


def checked_map(ctx) -> Optional[Dict[str, str]]:
    """The cell's :func:`scope_map` where it covers at least
    :data:`MIN_COVERAGE` of the window's device time, else nothing; checked
    once per window and map."""
    m = scope_map(ctx.cell)
    if m is None:
        return None
    if not (_CHECKED and _CHECKED[0] is ctx.summary and _CHECKED[1] is m):
        share = coverage(ctx.summary, m)
        ok = share >= MIN_COVERAGE
        print(f"bench: the step's scopes cover {100 * share:.4f}% of the "
              f"window's device time"
              + ("" if ok else f", under {100 * MIN_COVERAGE:g}%; no scope "
                 f"is read"), file=sys.stderr)
        _CHECKED[:] = [ctx.summary, m, m if ok else None]
    return _CHECKED[2]


def scope_seconds(ctx, scope: str) -> Optional[float]:
    """Device seconds in the traced window of the step's operations under
    ``scope``; nothing where the map fails its checks."""
    m = checked_map(ctx)
    if m is None:
        return None
    return ctx.summary.op_seconds(lambda name: m.get(name) == scope)


def ms_per_round(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per round under ``scope``; nothing where no
    operation of the window maps to it."""
    sec = scope_seconds(ctx, scope)
    if not sec:
        return None
    return 1e3 * sec / ctx.counters["rounds"]


def _runs(events) -> List[Tuple[float, float, str]]:
    """``(start_ns, end_ns, module)`` of an ``XLA Modules`` line's events,
    in order."""
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in events)


def programs(path: str) -> List[List[Tuple[float, float, str]]]:
    """Per device, the ``(start_ns, end_ns, module)`` of every program run
    in a trace file, in order, from its ``XLA Modules`` line."""
    from jax.profiler import ProfileData
    return [_runs(e for line in plane.lines if line.name == MODULES_LINE
                  for e in line.events)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/device:TPU:")]


def step_ops(path: str, lo: float = float("-inf"),
             hi: float = float("inf")) -> List[Tuple[str, float]]:
    """``(name, seconds)`` of every device op of a trace file that ran
    inside a run of the step's program (``jit_step`` on the ``XLA
    Modules`` line) and started inside ``[lo, hi]``."""
    from jax.profiler import ProfileData
    from bench import trace
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = [(a, b) for a, b, name in _runs(lines[MODULES_LINE])
                if name.startswith(STEP_MODULE)]
        starts = [a for a, _ in mods]
        for e in lines[trace.OPS_LINE]:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if (i >= 0 and e.start_ns + e.duration_ns <= mods[i][1]
                    and lo <= e.start_ns <= hi):
                out.append((trace.op_name(e.name), e.duration_ns * 1e-9))
    return out
