"""Streaming FL ingest: the sustained-throughput serving pipeline
(DESIGN.md §12.3).

``AsyncBuffered`` answers "is buffered-async *correct*" — lazy local
training, exact byte accounting, heap-oracle event order. This module
answers "how fast can the *server* ingest": a continuous-arrival loop
where encoded payloads stream in from an N-client population, the first-K
buffer fires a fused decode→aggregate (the PR 6 grouped/kernel path for
kernel-spec AEs), the global model updates, and exactly those K clients
are re-dispatched — all staged as **one donated jitted step**:

* event queue, client versions, and the flat global model are stacked
  device arrays (the §12.1 SoA layout with nothing host-side at all);
  the first-K pop is :func:`repro.core.arrival.pop_k_device`
  (``lax.sort`` on the ``(time, seq)`` key pair);
* synthetic encoded payloads are generated *in encoded space* on device
  (PRNG keyed on the dispatch sequence), so the step prices exactly the
  server's work — decode + staleness-weighted aggregate + re-dispatch —
  with zero host payload traffic;
* ``jax.jit(step, donate_argnums=0)`` donates the whole state pytree:
  XLA writes round r+1's state into round r's buffers, so the
  steady-state footprint is **two** generations of state (the classic
  double-buffer), not one per round. The invariant donation imposes: the
  caller must treat the passed-in state as consumed — :func:`run_serve`
  holds only the returned reference, never the donated one;
* per-round *host* work is O(1) — one dispatch of a cached executable —
  beating the O(cohort) the FedBuff regime requires (ISSUE 7); the
  benchmark asserts populations of 10^5+ at cohorts 256/4096/65536;
* ``shard=True`` ``shard_map``s the cohort axis of the decode→aggregate
  across a 1-D ``clients`` device mesh (same layout as
  ``codec.decode_and_aggregate_sharded``, here inlined into the donated
  step so the pop/re-dispatch stays fused around it).

Simulation caveats vs the exact scheduler: times are device ``float32``
(the heap oracle's float64 lexicographic exactness is not needed — ties
still break deterministically on ``seq``), latency is an in-jit uniform
jitter + straggler-tail model rather than ``LatencyModel``'s host
SeedSequence streams, and no local training happens (payloads are
synthetic). Throughput numbers are reported by ``benchmarks/tables.py``
``fl_serve`` (rounds/sec and ingested bytes/sec).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core.arrival import pop_k_device

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static shape of the serving simulation (hashable — the jitted step
    specializes on it). ``spec`` is any codec spec; its ``size`` fixes the
    flat model width the aggregate updates."""

    n_clients: int
    buffer_k: int
    spec: codec.CodecSpec
    staleness_power: float = 0.5
    server_lr: float = 1.0
    base_latency: float = 1.0
    jitter: float = 0.5                # latency ~ base * U[1-j, 1+j]
    straggler_frac: float = 0.0        # first ceil(frac*N) clients slow
    straggler_mult: float = 10.0
    seed: int = 0
    shard: bool = False                # shard_map the cohort axis

    def __post_init__(self):
        assert 0 < self.buffer_k <= self.n_clients


def _latency(cfg: ServeConfig, key: jax.Array, cis: jax.Array) -> jax.Array:
    """Per-dispatch simulated round-trip latency for clients ``cis`` —
    the in-jit counterpart of ``LatencyModel.sample`` (same shape: base ×
    uniform jitter × straggler tail), PRNG-keyed per call."""
    u = jax.random.uniform(key, cis.shape, dtype=jnp.float32)
    lat = cfg.base_latency * (1.0 + cfg.jitter * (2.0 * u - 1.0))
    n_slow = int(np.ceil(cfg.straggler_frac * cfg.n_clients))
    if n_slow:
        lat = jnp.where(cis < n_slow, lat * cfg.straggler_mult, lat)
    return lat


def synthetic_payloads(spec: codec.CodecSpec, params: Optional[Pytree],
                       k: int, key: jax.Array) -> codec.Payload:
    """A stacked cohort of ``k`` synthetic encoded payloads with exactly
    the structure/shapes/dtypes ``codec.encode`` would ship for ``spec``
    (structure from ``jax.eval_shape`` — nothing is actually encoded).
    Floats draw standard normals, integer entries (quantized values,
    top-k indices) draw uniformly in range — the *decode* cost is what
    the serve loop prices, and decode cost is payload-value-independent
    for every codec in the union."""
    shapes = jax.eval_shape(
        lambda f: codec.encode(spec, params, f),
        jax.ShapeDtypeStruct((spec.size,), jnp.float32))
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = []
    for kk, s in zip(keys, leaves):
        shape = (k, *s.shape)
        if jnp.issubdtype(s.dtype, jnp.floating):
            out.append(jax.random.normal(kk, shape).astype(s.dtype))
        elif jnp.issubdtype(s.dtype, jnp.integer):
            lo, hi = ((-127, 128) if s.dtype == jnp.int8
                      else (0, max(int(spec.size), 2)))
            out.append(jax.random.randint(kk, shape, lo, hi,
                                          dtype=jnp.int32).astype(s.dtype))
        else:
            out.append(jnp.zeros(shape, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def init_state(cfg: ServeConfig, codec_params: Optional[Pytree] = None,
               global_flat: Optional[jax.Array] = None) -> Dict[str, Any]:
    """The device-resident serve state (one dict pytree, all arrays):
    every client dispatched at t=0 with the v0 model — the same opening
    position as ``AsyncBuffered._reset``."""
    n = cfg.n_clients
    key = jax.random.PRNGKey(cfg.seed)
    cis = jnp.arange(n, dtype=jnp.int32)
    if global_flat is None:
        global_flat = jnp.zeros((int(cfg.spec.size),), jnp.float32)
    state = {
        "times": _latency(cfg, key, cis),            # (N,) next arrival
        "seqs": cis,                                 # (N,) dispatch seq
        "versions": jnp.zeros(n, jnp.int32),         # (N,) model at dispatch
        "global_flat": jnp.asarray(global_flat, jnp.float32),
        "clock": jnp.float32(0.0),
        "version": jnp.int32(0),
        "next_seq": jnp.int32(n),
    }
    if cfg.shard:
        # replicated on the clients mesh, as the sharded step returns it:
        # round 2 then reuses round 1's executable instead of recompiling
        state = jax.device_put(state, jax.sharding.NamedSharding(
            _clients_mesh(), jax.sharding.PartitionSpec()))
    return state


def _clients_mesh() -> jax.sharding.Mesh:
    """The 1-D ``clients`` mesh over every device (``shard=True``)."""
    return jax.sharding.Mesh(np.array(jax.devices()), ("clients",))


def _decode_aggregate(cfg: ServeConfig, params: Optional[Pytree],
                      stacked: codec.Payload, w: jax.Array) -> jax.Array:
    if not cfg.shard:
        return codec.decode_and_aggregate(cfg.spec, params, stacked, w)
    # cohort axis over a 1-D device mesh, inlined into the donated step:
    # each device reduces its shard's weighted sum (weights are globally
    # normalized), one psum makes the mean — codec.py §7.2 layout
    from jax.sharding import PartitionSpec as P
    mesh = _clients_mesh()
    assert cfg.buffer_k % mesh.devices.size == 0, (
        f"buffer_k={cfg.buffer_k} must divide over {mesh.devices.size} "
        "devices")

    def shard_fn(p, stacked_shard, w_shard):
        rows = codec.decode_batched(cfg.spec, p, stacked_shard)
        return jax.lax.psum(
            jnp.einsum("c,cp->p", w_shard.astype(jnp.float32),
                       rows.astype(jnp.float32)), "clients")

    # check_vma=False: pallas_call (the kernels inside decode_batched) has
    # no varying-manual-axes rule
    return jax.shard_map(shard_fn, mesh=mesh,
                         in_specs=(P(), P("clients"), P("clients")),
                         out_specs=P(), check_vma=False)(params, stacked, w)


def make_step(cfg: ServeConfig, codec_params: Optional[Pytree] = None):
    """Build the donated jitted serve step: state → state, one ingest
    round. Everything — pop, payload synthesis, fused decode→aggregate,
    model update, re-dispatch — is one XLA computation; the state pytree
    is donated (``donate_argnums=0``), so each round's output overwrites
    the previous round's buffers (double-buffered steady state)."""
    k = cfg.buffer_k

    def step(state: Dict[str, Any]) -> Dict[str, Any]:
        times, seqs = state["times"], state["seqs"]
        popped_t, idx = pop_k_device(times, seqs, k)
        clock = jnp.maximum(state["clock"], popped_t[-1])

        # staleness-discounted FedBuff weights, normalized on device
        stale = (state["version"] - state["versions"][idx]).astype(
            jnp.float32)
        w = (1.0 + stale) ** (-cfg.staleness_power)
        w = w / jnp.sum(w)

        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                 state["next_seq"])
        k_pay, k_lat = jax.random.split(key)
        stacked = synthetic_payloads(cfg.spec, codec_params, k, k_pay)
        mean = _decode_aggregate(cfg, codec_params, stacked, w)
        global_flat = state["global_flat"] + cfg.server_lr * mean

        # re-dispatch exactly the drained cohort with the new model
        lat = _latency(cfg, k_lat, idx)
        new_seqs = state["next_seq"] + jnp.arange(k, dtype=jnp.int32)
        return {
            "times": times.at[idx].set(clock + lat),
            "seqs": seqs.at[idx].set(new_seqs),
            "versions": state["versions"].at[idx].set(
                state["version"] + 1),
            "global_flat": global_flat,
            "clock": clock,
            "version": state["version"] + 1,
            "next_seq": state["next_seq"] + jnp.int32(k),
        }

    return jax.jit(step, donate_argnums=0)


def round_bytes(cfg: ServeConfig,
                codec_params: Optional[Pytree] = None) -> int:
    """Uplink bytes one ingest round consumes: K encoded payloads at the
    spec's static wire price (``codec.wire_bytes`` — the same pricing the
    rate controllers plan with)."""
    return cfg.buffer_k * codec.wire_bytes(cfg.spec, codec_params)


def run_serve(cfg: ServeConfig, n_rounds: int,
              codec_params: Optional[Pytree] = None,
              warmup: int = 1,
              global_flat: Optional[jax.Array] = None
              ) -> Tuple[Dict[str, Any], Dict[str, float]]:
    """Drive the serve loop for ``n_rounds`` timed rounds (after
    ``warmup`` untimed ones that absorb compilation) and report sustained
    throughput. Returns ``(final_state, report)`` with ``rounds_per_sec``,
    ``bytes_per_sec`` (ingested uplink), and ``us_per_round``.

    Donation discipline: ``state`` is rebound to the step's return value
    every round — the donated argument is dead the moment the call is
    issued, and XLA recycles its buffers for the next generation."""
    step = make_step(cfg, codec_params)
    state = init_state(cfg, codec_params, global_flat=global_flat)
    for _ in range(max(warmup, 1)):
        state = step(state)
    jax.block_until_ready(state["global_flat"])
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        state = step(state)
    jax.block_until_ready(state["global_flat"])
    dt = time.perf_counter() - t0
    per_round = round_bytes(cfg, codec_params)
    report = {
        "rounds_per_sec": n_rounds / dt,
        "bytes_per_sec": n_rounds * per_round / dt,
        "us_per_round": dt / n_rounds * 1e6,
        "round_bytes": float(per_round),
        "sim_time": float(state["clock"]),
    }
    return state, report
