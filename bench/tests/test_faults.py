"""The comparison that decides ``correct``, driven end to end on the CPU
at a small size: the serve driver runs its window and its check as a chip
run does (no look for a chip), with the timed path sound, replaced by the
control, or broken underneath. Only the sound path may come out correct.

Faults a serve cell can have: a step that returns its state unchanged,
half of the cohort left out with the mean taken over the rest, and the
answer (the new global model) altered where it is produced. The exchange
between chips does not exist on one chip. The control is the plain
reference in bfloat16 in the program's place. The limits are the cells'
own (``bench/limits``).
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, program  # noqa: E402
from bench.drivers import serve  # noqa: E402

SMALL = {
    "chunked_ae": ("cifar_chunkae_q8", "serve_chunkae_k1024",
                   {"chunk_size": 256, "hidden": [32]}),
    "fc_ae": ("cifar_fcae", "serve_fcae_k256",
              {"input_dim": 1000, "latent_dim": 16}),
}


def small_cell(kind: str, seed: int = 2 ** 33 + 7) -> harness.Cell:
    config_name, workload, codec = SMALL[kind]
    config = harness.load_json("configs", config_name + ".json")
    config["codec"].update(codec)
    config["model"]["update_size"] = 1000
    bench = harness.load_json(os.pardir, "BENCHMARK.json")
    w = next(c for c in bench["workloads"] if c["name"] == workload)
    traffic = harness.load_json("traffic", w["traffic"] + ".json")
    traffic.update(population=64, buffer_k=16)
    return harness.Cell(workload, config, traffic,
                        harness.load_json("limits", workload + ".json"),
                        seed=seed, seconds=0.5, trace=False,
                        t_start=time.perf_counter())


def unchanged_step(cell, spec):
    return jax.jit(lambda state, dec: state, donate_argnums=0)


def half_cohort_step(cell, spec):
    """The program's round with only the first half of the popped cohort
    aggregated, its weights renormalized over that half."""
    from repro.core import codec as c
    from repro.core.arrival import pop_k_device
    from repro.core.serve import _latency, synthetic_payloads
    cfg = program.serve_config(cell.traffic, spec)
    k = cfg.buffer_k

    def step(state, dec):
        params = program.codec_params(cell.codec, dec)
        popped_t, idx = pop_k_device(state["times"], state["seqs"], k)
        clock = jnp.maximum(state["clock"], popped_t[-1])
        stale = (state["version"] - state["versions"][idx]).astype(
            jnp.float32)
        w = (1.0 + stale) ** (-cfg.staleness_power)
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                 state["next_seq"])
        k_pay, k_lat = jax.random.split(key)
        stacked = synthetic_payloads(cfg.spec, params, k, k_pay)
        half = jax.tree_util.tree_map(lambda a: a[:k // 2], stacked)
        wh = w[:k // 2] / jnp.sum(w[:k // 2])
        mean = c.decode_and_aggregate(cfg.spec, params, half, wh)
        lat = _latency(cfg, k_lat, idx)
        new = dict(state)
        new.update({
            "times": state["times"].at[idx].set(clock + lat),
            "seqs": state["seqs"].at[idx].set(
                state["next_seq"] + jnp.arange(k, dtype=jnp.int32)),
            "versions": state["versions"].at[idx].set(state["version"] + 1),
            "global_flat": state["global_flat"] + cfg.server_lr * mean,
            "clock": clock, "version": state["version"] + 1,
            "next_seq": state["next_seq"] + jnp.int32(k)})
        return new

    return jax.jit(step, donate_argnums=0)


def altered_answer_step(cell, spec):
    """The program's step, with the first 64 entries of the new global
    model left where they were: one slice of the answer altered."""
    inner = serve.program_step(cell, spec)

    def step(state, dec):
        before = state["global_flat"][:64]
        out = inner(state, dec)
        out["global_flat"] = out["global_flat"].at[:64].set(before)
        return out

    return jax.jit(step, donate_argnums=0)


@pytest.fixture(autouse=True)
def kernel_path(monkeypatch):
    """The chip's path: the Pallas kernels (interpreted on the CPU)."""
    monkeypatch.setenv("REPRO_USE_KERNEL", "1")


@pytest.mark.parametrize("kind", sorted(SMALL))
def test_program_is_correct(kind):
    out = serve.run(small_cell(kind), calibration_rounds=3)
    assert out.correct, [(c.name, c.value, c.limit) for c in out.checks]
    assert out.counters["checked_rounds"] == serve.CHECKED_ROUNDS


@pytest.mark.parametrize("kind", sorted(SMALL))
@pytest.mark.parametrize("broken", [serve.control_step, unchanged_step,
                                    half_cohort_step, altered_answer_step],
                         ids=["control", "unchanged", "half_cohort",
                              "altered_answer"])
def test_broken_path_is_not_correct(kind, broken):
    out = serve.run(small_cell(kind), make_step=broken, calibration_rounds=3)
    assert not out.correct, [(c.name, c.value, c.limit) for c in out.checks]
