"""Device time by the layers a program names itself.

A program names its layers with ``jax.named_scope``, and XLA carries that
name stack into each HLO instruction's ``op_name`` metadata. The cell's
driver (``bench/harness.py``) says which layers these are (``SCOPES``, all
of one family such as ``serve.pop``, ``serve.payloads``, ...), which
programs it runs (``PROGRAMS``, as the trace's ``XLA Modules`` line names
them), and how it builds them (``lower``). A profiler trace names a device
operation by its instruction and its program (``bench/trace.py``), so the
map from ``(program, instruction)`` to scope is read from the compiled
texts: each of the driver's programs is lowered as the driver calls it,
with abstract arguments, and compiled, which on the chip finds the
driver's executable in the persistent cache; then every instruction of
every computation (names are unique in a module, not across modules) is
mapped to the first component of its ``op_name`` in the family of
``SCOPES`` (:func:`parse`). This runs after the window of a traced run,
once per cell in a process.

Nothing at run time says that these compiles returned the executables the
window ran, so the map is held to two checks before any metric reads it:
the texts together name every scope of ``SCOPES``, and the operations it
maps to a scope make up at least :data:`MIN_COVERAGE` of the device time
of the driver's programs in the window (the harness's own copies are other
programs, and do not count). A map that fails either (a program without
the scopes, one that predates them, among them) gives no metric, and the
readers of the scoped metrics then return nothing.
"""
from __future__ import annotations

import collections
import json
import os
import re
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from bench import harness

UNSCOPED = "unscoped"
# the least share of the device time of the driver's programs in the
# window that the map has to place in a scope
MIN_COVERAGE = 0.99

# ``  ROOT %name = <type> opcode(operands), attributes`` in
# ``Compiled.as_text()``; the non-greedy type stops at the first
# `` opcode(``, which tuple types never hold
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? [a-z][\w\-]*\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

# (program, instruction) -> scope
ScopeMap = Dict[Tuple[str, str], str]

_MAPS: Dict[tuple, Optional[ScopeMap]] = {}
# [summary, map, the map or nothing] of the last window checked
_CHECKED: list = []


def family(scopes: Sequence[str]) -> str:
    """The prefix that names a family of scopes: their common prefix up to
    its last dot (``serve.`` for ``serve.pop``, ``serve.payloads``, ...);
    "" where they share no dotted prefix."""
    prefix = os.path.commonprefix(list(scopes))
    return prefix[:prefix.rfind(".") + 1]


def scope_of(op_name: str, prefix: str) -> str:
    """The first component of a name stack in the family ``prefix``, or
    UNSCOPED."""
    for part in op_name.split("/"):
        if part.startswith(prefix):
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


def parse(text: str, prefix: str) -> Dict[str, str]:
    """Instruction name → scope of the family ``prefix``, for every
    instruction of an HLO module's text.

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
        own[name] = scope_of(op.group(1), prefix) if op else None
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


def program_texts(cell) -> Dict[str, str]:
    """The compiled text of each program of the cell's driver."""
    lowered = harness.driver_of(cell).lower(cell)
    return {program: low.compile().as_text()
            for program, low in lowered.items()}


def scope_map(cell) -> Optional[ScopeMap]:
    """:func:`parse` of every text of :func:`program_texts`, keyed by
    program and instruction, once per cell in a process; nothing where the
    texts lack a scope of the driver's ``SCOPES``."""
    key = (cell.workload, json.dumps(cell.traffic, sort_keys=True))
    if key not in _MAPS:
        want, m = set(harness.driver_of(cell).SCOPES), None
        if not family(want):
            print("bench: the driver names no family of scopes; no scope "
                  "is read", file=sys.stderr)
        else:
            t = time.perf_counter()
            m = {(program, name): scope
                 for program, text in program_texts(cell).items()
                 for name, scope in parse(text, family(want)).items()}
            print(f"bench: scopes of {cell.workload}'s programs read in "
                  f"{time.perf_counter() - t:.2f} s", file=sys.stderr)
            missing = sorted(want - set(m.values()))
            if missing:
                print(f"bench: the compiled programs lack the scopes "
                      f"{missing}; no scope is read", file=sys.stderr)
                m = None
        _MAPS[key] = m
    return _MAPS[key]


def coverage(summary, m: ScopeMap, programs: Sequence[str]) -> float:
    """The share of the device time of ``programs`` in the window whose
    operations ``m`` maps to a scope."""
    total = summary.program_op_seconds(lambda p, name: p in programs)
    mapped = summary.program_op_seconds(
        lambda p, name: p in programs
        and m.get((p, name), UNSCOPED) != UNSCOPED)
    return mapped / total if total > 0.0 else 0.0


def checked_map(ctx) -> Optional[ScopeMap]:
    """The cell's :func:`scope_map` where it covers at least
    :data:`MIN_COVERAGE` of the device time of the driver's programs in
    the window, else nothing; checked once per window and map."""
    m = scope_map(ctx.cell)
    if m is None:
        return None
    if not (_CHECKED and _CHECKED[0] is ctx.summary and _CHECKED[1] is m):
        share = coverage(ctx.summary, m, harness.driver_of(ctx.cell).PROGRAMS)
        ok = share >= MIN_COVERAGE
        print(f"bench: the scopes cover {100 * share:.4f}% of the device "
              f"time of the cell's programs in the window"
              + ("" if ok else f", under {100 * MIN_COVERAGE:g}%; no scope "
                 f"is read"), file=sys.stderr)
        _CHECKED[:] = [ctx.summary, m, m if ok else None]
    return _CHECKED[2]


def scope_seconds(ctx, scope: str) -> Optional[float]:
    """Device seconds in the traced window of the operations under
    ``scope``; nothing where the map fails its checks."""
    m = checked_map(ctx)
    if m is None:
        return None
    return ctx.summary.program_op_seconds(
        lambda program, name: m.get((program, name)) == scope)


def ms_per_round(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per round under ``scope``; nothing where no
    operation of the window maps to it."""
    sec = scope_seconds(ctx, scope)
    if not sec:
        return None
    return 1e3 * sec / ctx.counters["rounds"]
