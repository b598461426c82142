"""Generator for serve traffic: the program's streaming ingest loop
(``repro.core.serve``), one donated step per round — pop the first K
arrivals of an N-client population, decode and aggregate their encoded
payloads with staleness weights into the global model, re-dispatch them.

A traffic file gives the population N, the buffer K, the staleness power,
the latency ``base_latency * U[1 - jitter, 1 + jitter]`` and the scale of
the opening global model; serve traffic has no stragglers. How the loop is
run is the same in every cell (the constants below): the loop is closed
and paced by the device. Rounds are dispatched in chunks of
``SYNC_SECONDS`` (turned into a round count from timed calibration
rounds), and after each chunk the host waits for the end of the chunk
sent ``AHEAD_SECONDS`` earlier, so that about that much work stays queued
on the device and a host that stands still for less does not leave the
chip idle (``bench/run.py`` lets the runtime hold that many dispatches).
When ``--seconds`` are up the host sends nothing more, waits for all that
was sent and reads the clock. ``updates_per_s`` is K × rounds completed
÷ the window, from the first timed dispatch to the end of that last
wait: all the work sent, over all the time it took.

Every input comes from the seed: the decoder (drawn on the device in one
jitted call), the initial arrival times, the global model, and an offset
of the dispatch sequence numbers, which key each round's payloads and
latencies inside the step. The step itself is the same program for every
seed, so only a checkout's first run compiles.

Correctness: a sample of the window's rounds, drawn from the seed, is
copied on the device before and after its step (the device's peak memory
is read before the window, so these copies are not counted in it). Once
the window has closed
and the program's state is freed, the plain reference
(``bench/configs/reference.py``) recomputes each sampled round from its
copy, and three numbers are compared:

* ``state_mismatch``: entries of the queue state (sequence numbers,
  versions, clock, version, next sequence number) that differ — exact,
  and it catches a wrong pop, a skipped round and a wrong re-dispatch;
* ``times_err``: the largest gap in the re-dispatched arrival times, over
  the largest arrival time;
* ``agg_err``: the largest gap in the new global model, over the largest
  entry of the reference's mean update — the decode→aggregate with its
  staleness weights.

Each is the worst over the sampled rounds; a fourth, ``unchecked_rounds``
(limit 0), counts sampled rounds the window did not reach.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import gc
import shutil
import tempfile
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import program, trace as trace_lib
from bench.configs import reference
from bench.harness import Cell, Check, Outcome

CHECKS = ("state_mismatch", "times_err", "agg_err")
# the step as the trace's ``XLA Modules`` line names it: ``program.serve_step``
# jits a function named ``step``
PROGRAMS = ("jit_step",)
SCOPES = program.SCOPES
STATE_KEYS = ("seqs", "versions", "clock", "version", "next_seq")

# How the loop is run, in every cell; tests shorten them through ``run``.
WARMUP_ROUNDS = 3          # compile (or load) the step, fill the pipeline
CALIBRATION_ROUNDS = 20    # timed, for the chunk size and the sample
SYNC_SECONDS = 0.25        # of rounds in a chunk; the host waits once a chunk
AHEAD_SECONDS = 1.0        # of rounds queued beyond the chunk waited for
CHECKED_ROUNDS = 8         # rounds of the window compared with the reference


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    return jax.random.PRNGKey(np.uint32(seed % 2 ** 32))


def initial_state(cell: Cell, template: Dict, key: jax.Array) -> Dict:
    """The opening queue drawn from the seed, in the program's layout:
    every client in flight with an arrival time of ``base * U[1-j, 1+j]``,
    sequence numbers shifted by a seed-drawn offset, a random global
    model."""
    tr = cell.traffic
    n = tr["population"]

    def draw(key):
        k_t, k_g, k_o = jax.random.split(key, 3)
        u = jax.random.uniform(k_t, (n,), dtype=jnp.float32)
        offset = jax.random.randint(k_o, (), 0, 2 ** 20) * 1024
        state = dict(template)
        state.update({
            "times": tr["base_latency"] * (1.0 + tr["jitter"]
                                           * (2.0 * u - 1.0)),
            "seqs": jnp.arange(n, dtype=jnp.int32) + offset,
            "next_seq": jnp.int32(n) + offset,
            "global_flat": tr["global_init_scale"] * jax.random.normal(
                k_g, template["global_flat"].shape, jnp.float32),
        })
        return state

    return jax.jit(draw)(key)


def update_size(config: Dict) -> int:
    """The parameter count of the configuration's classifier."""
    return program.classifier_size(config["model"])


def lower(cell: Cell) -> Dict[str, jax.stages.Lowered]:
    """The cell's step lowered as :func:`run` calls it: the arguments left
    on the default device, as the driver's are, so the lowering is the
    driver's and its compile finds the driver's executable in the
    persistent cache."""
    spec = program.codec_spec(cell.codec)
    cfg = program.serve_config(cell.traffic, spec)
    state = jax.eval_shape(lambda: program.serve_init_state(cfg))
    dec = jax.eval_shape(
        lambda k: reference.make_decoder(cell.codec, cell.config["weights"],
                                         k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return {PROGRAMS[0]: program.serve_step(cell.codec, cfg).lower(state,
                                                                    dec)}


def program_step(cell: Cell, spec) -> Callable:
    """The timed path: the program's serve step, ``(state, dec) -> state``."""
    return program.serve_step(cell.codec, program.serve_config(cell.traffic,
                                                               spec))


def reference_step(cell: Cell, dtype, precision) -> Callable:
    return jax.jit(functools.partial(
        reference.serve_round, codec=cell.codec, traffic=cell.traffic,
        program_seed=program.PROGRAM_SEED, server_lr=program.SERVER_LR,
        dtype=dtype,
        precision=precision))


def control_step(cell: Cell, spec) -> Callable:
    """The control: the reference in bfloat16 in the program's place."""
    ref = reference_step(cell, jnp.bfloat16, None)
    return jax.jit(lambda s, d: ref(s, d)[0], donate_argnums=0)


_copy = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))
# a handle on the end of a round that the next round's donation leaves alive
_mark = jax.jit(lambda state: jnp.copy(state["version"]))


def compare(cell: Cell, pre: Dict, post: Dict, dec: Dict, ref) -> Dict:
    want, mean = ref(pre, dec)
    got = jax.device_get({k: post[k] for k in want})
    want, mean = jax.device_get((want, mean))
    mismatch = sum(int(np.sum(np.asarray(got[k]) != np.asarray(want[k])))
                   for k in STATE_KEYS)
    t_got = np.asarray(got["times"], np.float64)
    t_want = np.asarray(want["times"], np.float64)
    g_got = np.asarray(got["global_flat"], np.float64)
    g_want = np.asarray(want["global_flat"], np.float64)
    scale = float(np.max(np.abs(np.asarray(mean, np.float64))))
    return {
        "state_mismatch": float(mismatch),
        "times_err": float(np.max(np.abs(t_got - t_want))
                           / max(float(np.max(np.abs(t_want))), 1e-30)),
        "agg_err": float(np.max(np.abs(g_got - g_want)) / max(scale, 1e-30)),
    }


def run(cell: Cell, make_step: Optional[Callable] = None,
        keep_trace: Optional[str] = None, *,
        warmup_rounds: int = WARMUP_ROUNDS,
        calibration_rounds: int = CALIBRATION_ROUNDS,
        sync_seconds: float = SYNC_SECONDS,
        ahead_seconds: float = AHEAD_SECONDS,
        checked_rounds: int = CHECKED_ROUNDS) -> Outcome:
    """One run of a serve cell. ``make_step(cell, spec)`` builds the timed
    path; the default is the program's step. Tests pass broken steps and
    shorter loops. ``keep_trace`` copies a traced run's ``.xplane.pb`` to
    that path."""
    tr = cell.traffic
    k, n = tr["buffer_k"], tr["population"]
    spec = program.codec_spec(cell.codec)
    cfg = program.serve_config(tr, spec)
    key = seed_key(cell.seed)
    k_dec, k_state = jax.random.split(key)
    dec = reference.make_decoder(cell.codec, cell.config["weights"], k_dec)
    state = initial_state(cell, program.serve_init_state(cfg), k_state)
    step = (make_step or program_step)(cell, spec)

    # warm-up, then calibration rounds timed for the chunk size and the
    # sample's range; both compile nothing after the first step
    for _ in range(warmup_rounds):
        state = step(state, dec)
    jax.block_until_ready((_copy(state), _mark(state)))
    t = time.perf_counter()
    for _ in range(calibration_rounds):
        state = step(state, dec)
    jax.block_until_ready(state["global_flat"])
    per_round = (time.perf_counter() - t) / calibration_rounds
    chunk = max(1, int(sync_seconds / per_round))
    # chunks queued beyond the one waited for: AHEAD_SECONDS of rounds, and
    # at most half the window
    depth = int(min(ahead_seconds, cell.seconds / 2) / sync_seconds)
    expected = max(1, int(cell.seconds / per_round))
    rng = np.random.default_rng(cell.seed)
    sampled = set(rng.choice(max(expected // 2, checked_rounds),
                             size=checked_rounds, replace=False).tolist())
    # the program's peak: set-up and rounds in flight, before the window's
    # copies for the check
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    tmp = tempfile.mkdtemp(prefix="bench_trace_") if cell.trace else None
    profiler = (jax.profiler.trace(tmp) if cell.trace
                else contextlib.nullcontext())
    snaps = []
    marks = collections.deque()
    rounds = 0
    # no collector pause inside the window; the loop allocates little
    gc.collect()
    gc.disable()
    try:
        with profiler:
            if cell.trace:
                # the first dispatch under the profiler is slow; keep it out
                state = step(state, dec)
                jax.block_until_ready(state["global_flat"])
            t0 = time.perf_counter()
            setup_s = t0 - cell.t_start
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
                while True:
                    for _ in range(chunk):
                        if rounds in sampled:
                            with jax.profiler.TraceAnnotation("bench.copy"):
                                pre = _copy(state)
                        with jax.profiler.TraceAnnotation("bench.round"):
                            state = step(state, dec)
                        if rounds in sampled:
                            with jax.profiler.TraceAnnotation("bench.copy"):
                                snaps.append((pre, _copy(state)))
                        rounds += 1
                    marks.append(_mark(state))
                    if len(marks) > depth:
                        with jax.profiler.TraceAnnotation("bench.sync"):
                            jax.block_until_ready(marks.popleft())
                    if time.perf_counter() - t0 >= cell.seconds:
                        break
                # send nothing more; wait for all that was sent
                with jax.profiler.TraceAnnotation("bench.sync"):
                    jax.block_until_ready(state["global_flat"])
            window = time.perf_counter() - t0
    finally:
        gc.enable()

    summary = None
    if cell.trace:
        try:
            path = trace_lib.find_xplane(tmp)
            if keep_trace:
                shutil.copyfile(path, keep_trace)
            summary = trace_lib.summarize(trace_lib.load(path))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    del state, step, marks

    ref = reference_step(cell, jnp.float32, "highest")
    worst = {c: 0.0 for c in CHECKS}
    failed = 0
    for pre, post in snaps:
        got = compare(cell, pre, post, dec, ref)
        if any(got[c] > cell.limits[c] for c in CHECKS):
            failed += 1
        for c in CHECKS:
            worst[c] = max(worst[c], got[c])
    # every sampled round has to have been reached and checked
    checks = [Check("unchecked_rounds", len(sampled) - len(snaps), 0)]
    checks += [Check(c, worst[c], cell.limits[c]) for c in CHECKS]
    return Outcome(
        attempted=rounds, failed=failed, checks=checks,
        end_to_end={"updates_per_s": k * rounds / window,
                    "setup_s": setup_s},
        counters={"rounds": rounds, "buffer_k": k, "population": n,
                  "checked_rounds": len(snaps)},
        memory_peak_bytes=peak, summary=summary)
