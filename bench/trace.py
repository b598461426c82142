"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device operations inside the benchmark's window, the
device's busy time (the union of their intervals), and the host spans the
harness opened (``bench.*``), which label the device's idle gaps.

Read with ``jax.profiler.ProfileData`` alone. Device planes are named
``/device:TPU:<n>``; their operations are the events of the ``XLA Ops``
line. An operation's
event carries the HLO instruction's whole text (``%fused_dense.1 =
f32[...] custom-call(...)``); it is named here by the instruction alone
(``fused_dense.1``), since the operand list names other instructions.

Instruction names are unique within one compiled program, not across
programs: XLA names a fusion ``fusion.3`` in each. So every operation also
keeps its program, the run on the plane's ``XLA Modules`` line that
contains it (``jit_step(1235368921123069479)`` names the program
``jit_step``), and a breakdown puts the program in front of a name only
where two programs of the window share it (``jit_step:fusion.3``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
# one event per run of a compiled program
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    program: str = ""               # a device op's program; "" for a span

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: List[List[Event]]          # per device, operations
    spans: List[Event]              # host spans named bench.*
    runs: List[List[Tuple[float, float, str]]]   # per device, program runs

    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                             f"{len(w)}")
        return w[0].start_ns, w[0].end_ns


def op_name(text: str) -> str:
    """``%fused_dense.1 = f32[...] custom-call(...)`` → ``fused_dense.1``."""
    return text.split(" = ", 1)[0].lstrip("%")


def program_of(module: str) -> str:
    """``jit_step(1235368921123069479)`` → ``jit_step``."""
    return module.split("(", 1)[0]


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, found "
                         f"{len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, runs = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            runs.append(sorted(
                (e.start_ns, e.start_ns + e.duration_ns, program_of(e.name))
                for e in lines.get(MODULES_LINE, ())))
            ops.append([Event(op_name(e.name), e.start_ns, e.duration_ns,
                              program_in(runs[-1], e.start_ns,
                                         e.start_ns + e.duration_ns))
                        for e in lines.get(OPS_LINE, ())])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.duration_ns))
    return Trace(ops=ops, spans=spans, runs=runs)


def program_in(runs: Sequence[Tuple[float, float, str]], start: float,
               end: float) -> str:
    """The program of the run in ``runs`` (in order, none overlapping)
    that contains ``[start, end]``; "" where none does."""
    i = bisect.bisect_right(runs, (start, float("inf"))) - 1
    return runs[i][2] if i >= 0 and end <= runs[i][1] else ""


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """The parts of ``events`` inside ``[lo, hi]``."""
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(dataclasses.replace(e, start_ns=s, dur_ns=t - s))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals covered by ``events``."""
    merged: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end_ns)
        else:
            merged.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union(events))


def sums_by_op(events: Iterable[Event]) -> Dict[Tuple[str, str], float]:
    """Summed durations by ``(program, name)``."""
    out: Dict[Tuple[str, str], float] = {}
    for e in events:
        key = (e.program, e.name)
        out[key] = out.get(key, 0.0) + e.dur_ns
    return out


def labels(keys: Iterable[Tuple[str, str]]) -> Dict[Tuple[str, str], str]:
    """A breakdown's name of each ``(program, name)``: the name alone where
    no other program of ``keys`` has it, else ``program:name``."""
    keys = list(keys)
    count = collections.Counter(name for _, name in keys)
    return {(p, n): n if count[n] == 1 else f"{p}:{n}" for p, n in keys}


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle ``(start, end)`` intervals of the device inside ``[lo, hi]``."""
    out, t = [], lo
    for a, b in union(events):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans: Sequence[Event], t: float) -> Optional[str]:
    """The innermost harness span open at ``t`` (the window span only when
    nothing else is)."""
    open_ = [s for s in spans if s.start_ns <= t <= s.end_ns]
    if not open_:
        return None
    return min(open_, key=lambda s: s.dur_ns).name


@dataclasses.dataclass
class Summary:
    """One traced window, reduced. Times in seconds, averaged over the
    devices where a per-device number is asked for."""
    window_s: float
    busy_s: float
    ops: List[List[Event]]          # per device, clipped to the window
    breakdown: Dict[str, list]

    def op_seconds(self, match) -> float:
        """Device seconds, averaged over devices, of the operations whose
        instruction name ``match`` accepts."""
        return self.program_op_seconds(lambda program, name: match(name))

    def program_op_seconds(self, match) -> float:
        """Device seconds, averaged over devices, of the operations whose
        program and instruction name ``match(program, name)`` accepts."""
        n = max(len(self.ops), 1)
        return sum(e.dur_ns for dev in self.ops for e in dev
                   if match(e.program, e.name)) / n * 1e-9


def summarize(trace: Trace, top: int = 10) -> Summary:
    lo, hi = trace.window()
    ops = [clip(dev, lo, hi) for dev in trace.ops]
    if not any(ops):
        raise ValueError("no device operation inside the traced window")
    n = len(ops)
    busy = sum(busy_ns(dev) for dev in ops) / n
    totals: Dict[Tuple[str, str], float] = {}
    for dev in ops:
        for k, v in sums_by_op(dev).items():
            totals[k] = totals.get(k, 0.0) + v / n
    label = labels(totals)
    device_ops = [[label[k], v * 1e-9] for k, v in
                  sorted(totals.items(), key=lambda kv: -kv[1])[:top]]
    longest = sorted(((b - a, (a + b) / 2) for dev in ops
                      for a, b in gaps(dev, lo, hi)), reverse=True)[:top]
    idle = [[span_at(trace.spans, mid) or "none", d * 1e-9]
            for d, mid in longest]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9, ops=ops,
                   breakdown={"device_ops": device_ops, "idle_gaps": idle})
