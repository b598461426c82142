"""Run the federated round on one TPU chip through the entry points a user
calls, and check what comes out.

    python chip_smoke.py                 # one chip: phases A, B, C
    python chip_smoke.py --four-chips    # four chips: the sharded servers

Phases (all seeded; weights are random or trained for a few epochs):

A  The paper's pipeline at paper widths: ``run_prepass`` on the CIFAR CNN
   (550,586 parameters) with its one-bottleneck FC AE (352.9M parameters),
   then ``FederatedRun`` under ``SyncFedAvg``, 4 clients, 2 rounds.
B  The chunked-AE kernel path: the default ``ChunkedAEConfig`` feeding a q8
   stage, ``SampledSync`` cohorts of 16 of 32 clients for 2 rounds, on the
   per-bucket server path and on the grouped one over two AE rungs that
   share one (hidden, chunk) signature. One stacked cohort is also reduced
   by both server steps and compared with the jnp reference.
C  Serving: ``run_serve`` at a population of 10^5 with q8 at cohorts 256
   and 4096, and with the kernel-path chunked AE.

``--four-chips`` runs only the multi-device servers on a 4-device
``clients`` mesh — ``codec.decode_and_aggregate_sharded`` and
``ServeConfig(shard=True)`` — each against its one-device result.

Each phase prints one line: its name, wall seconds (compilation included)
and the checks it made. These lines are informational, not metrics. Any
failed check ends the run with a non-zero exit. The last line of stdout is
the JSON verdict with the device as JAX reports it. There is no CPU
fallback: without a TPU the script exits non-zero before any phase.
Everything runs in this one process; it starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Callable, Dict, List, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# A kernel path against the jnp reference at "highest" matmul precision.
# The Pallas kernels and XLA may each run an f32 matmul as one bf16 MXU pass
# (operands rounded to an 8-bit mantissa, ~4e-3 relative per product).
# Through the decoder's hidden and output matmuls that stays well under 2%
# of the output's largest magnitude; a wrong weight, index or tile is O(1).
KERNEL_RTOL = 2e-2
# Reductions that differ only in summation order (a psum across shards
# against one einsum), relative to the output's largest magnitude.
REORDER_RTOL = 1e-5
# What a compiled program contains when a Pallas kernel runs in it.
KERNEL_MARK = "tpu_custom_call"


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs at; :func:`_full_sizes` is the chip run."""

    classifier: object                  # configs.paper.ClassifierConfig
    a_clients: int = 4
    a_samples: int = 256                # per client
    a_prepass_epochs: int = 4
    a_ae_epochs: int = 4
    b_clients: int = 32
    b_cohort: int = 16
    b_samples: int = 128
    b_chunked: object = None            # core.autoencoder.ChunkedAEConfig
    c_population: int = 100_000
    c_cohorts: Tuple[int, ...] = (256, 4096)
    c_q8_model: int = 4096
    c_ae_cohort: int = 256
    rounds: int = 2
    serve_rounds: int = 5


def _full_sizes() -> Sizes:
    from repro.configs.paper import CIFAR_CLASSIFIER
    from repro.core import ChunkedAEConfig
    return Sizes(classifier=CIFAR_CLASSIFIER, b_chunked=ChunkedAEConfig())


# ------------------------------------------------------------------ helpers
def _rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _finite(x) -> bool:
    import numpy as np
    return bool(np.all(np.isfinite(np.asarray(x))))


def _compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def _split(sz: Sizes, seed: int, n_clients: int, per_client: int):
    from repro.data.pipeline import (synthetic_classification,
                                     train_eval_split, uniform_partition)
    clf = sz.classifier
    data = synthetic_classification(seed, (n_clients + 1) * per_client,
                                    clf.input_shape, clf.n_classes,
                                    sep=8.0, noise=0.7)
    train, ev = train_eval_split(data, per_client)
    return uniform_partition(seed, train, n_clients), ev


def _losses(hist) -> List[float]:
    return [float(r.global_metrics["loss"]) for r in hist]


# ------------------------------------------------------------------ phases
def phase_a(sz: Sizes, seed: int) -> str:
    """Pre-pass → FC AE → AE-compressed SyncFedAvg, at paper widths."""
    import jax
    from jax.flatten_util import ravel_pytree
    from repro.configs.paper import cifar_ae_for
    from repro.core import (FCAECompressor, FLConfig, FederatedRun,
                            ae_param_count, run_prepass)
    from repro.core.task import ClassifierTask

    task = ClassifierTask(sz.classifier)
    datasets, ev = _split(sz, seed, sz.a_clients, sz.a_samples)
    init = task.init_params(jax.random.PRNGKey(seed))
    n = int(ravel_pytree(init)[0].size)
    ae_cfg = cifar_ae_for(n)
    out = run_prepass(jax.random.PRNGKey(seed), sz.classifier, ae_cfg,
                      datasets[0], prepass_epochs=sz.a_prepass_epochs,
                      ae_epochs=sz.a_ae_epochs, init_params=init)
    ae_loss = out["ae_history"]["loss"]
    _check(all(map(_finite, ae_loss)), f"AE training loss not finite: "
           f"{ae_loss}")
    comps = [FCAECompressor(out["ae_params"], ae_cfg)
             for _ in range(sz.a_clients)]
    run = FederatedRun(task, datasets,
                       FLConfig(n_rounds=sz.rounds, local_epochs=1,
                                seed=seed),
                       compressors=comps, eval_data=ev)
    hist = run.run()
    losses = _losses(hist)
    _check(all(map(_finite, losses)), f"global loss not finite: {losses}")
    want = n / ae_cfg.latent_dim
    ratio = hist[-1].compression_ratio
    _check(abs(ratio - want) <= 1e-3 * want,
           f"compression ratio {ratio} != {n}/{ae_cfg.latent_dim}")
    return (f"{n:,}-param model, {ae_param_count(out['ae_params']):,}-param "
            f"FC AE (loss {float(ae_loss[0]):.4g} -> "
            f"{float(ae_loss[-1]):.4g}); SyncFedAvg x{sz.a_clients} loss "
            + " -> ".join(f"{x:.4f}" for x in losses)
            + f" finite; compression {ratio:.1f}x = {n}/"
            f"{ae_cfg.latent_dim}")


def _rung_compressors(sz: Sizes, seed: int, task, datasets, init):
    """Two chunked-AE rungs with one (hidden, chunk) signature — latents
    c and 2c — each feeding a q8 stage. The decoders are random, their
    normalizers fitted to one real local update so decoded updates have
    its scale. Clients of one rung share one compressor (one decoder)."""
    import dataclasses as dc
    import jax
    from jax.flatten_util import ravel_pytree
    from repro.core import (ChainCompressor, ChunkedAECompressor, FLConfig,
                            QuantizeCompressor, init_chunked_ae)
    from repro.core.autoencoder import chunk_vector, fit_normalizer

    local, _ = task.local_update(init, datasets[0],
                                 FLConfig(local_epochs=1, seed=seed),
                                 seed=seed)
    delta = ravel_pytree(local)[0] - ravel_pytree(init)[0]
    comps = []
    for r, latent in enumerate((sz.b_chunked.latent_chunk,
                                2 * sz.b_chunked.latent_chunk)):
        cfg = dc.replace(sz.b_chunked, latent_chunk=latent)
        prm = init_chunked_ae(jax.random.PRNGKey(seed + 1 + r), cfg)
        prm = fit_normalizer(prm, chunk_vector(delta, cfg.chunk_size)[0])
        comps.append(ChainCompressor([ChunkedAECompressor(prm, cfg),
                                      QuantizeCompressor(bits=8, block=64)]))
    return comps


def _cohort_check(sz: Sizes, seed: int, comps, n: int) -> Dict[str, float]:
    """Reduce one stacked mixed-rung cohort of seeded update-sized vectors
    with the per-bucket server step and the grouped one; compare each with
    the jnp reference (the same specs with ``use_kernel=False`` at
    "highest" precision)."""
    import dataclasses as dc
    import jax
    import jax.numpy as jnp
    from repro.core import codec, normalize_weights, partition
    from repro.core.scheduler import EncodedUpdate

    half = sz.b_cohort // 2
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), 2)
    flats = 1e-3 * jax.random.normal(keys[0], (sz.b_cohort, n))
    raw = jax.random.uniform(keys[1], (sz.b_cohort,)) + 0.5
    norm = normalize_weights([float(x) for x in raw])
    rung = [0] * half + [1] * (sz.b_cohort - half)
    specs = [c.spec(n) for c in comps]
    prms = [c.codec_params() for c in comps]
    for s in specs:
        _check(codec.kernel_terminal_ae(s) is not None,
               f"{s} does not take the kernel path")
    pays = [codec.encode(specs[r], prms[r], flats[i])
            for i, r in enumerate(rung)]
    buckets = []
    for r in (0, 1):
        idx = [i for i in range(sz.b_cohort) if rung[i] == r]
        s_g = sum(norm[i] for i in idx)
        buckets.append((codec.stack_payloads([pays[i] for i in idx]),
                        jnp.asarray([norm[i] / s_g for i in idx],
                                    jnp.float32), s_g))

    def per_bucket(spec_of):
        total = None
        for r, (stk, w, s_g) in enumerate(buckets):
            part = jnp.float32(s_g) * codec.decode_and_aggregate(
                spec_of[r], prms[r], stk, w)
            total = part if total is None else total + part
        return total

    def grouped(pays_, prm0, prm1):
        enc = [EncodedUpdate(payload=p, spec=specs[r],
                             params=(prm0, prm1)[r], weight=1.0, stats={},
                             metrics={})
               for p, r in zip(pays_, rung)]
        return partition.grouped_flat_server_aggregate(enc, norm, None)

    stk0, w0, _ = buckets[0]
    _check(KERNEL_MARK in _compiled_text(codec.decode_and_aggregate,
                                         specs[0], prms[0], stk0, w0),
           "per-bucket server step has no Pallas kernel")
    grouped_jit = jax.jit(grouped)
    _check(KERNEL_MARK in _compiled_text(grouped_jit, pays, *prms),
           "grouped server step has no Pallas kernel")
    got_pb = per_bucket(specs)
    got_gr = grouped_jit(pays, *prms)
    ref_specs = [codec.ChainSpec((dc.replace(s.stages[0], use_kernel=False),)
                                 + s.stages[1:]) for s in specs]
    with jax.default_matmul_precision("highest"):
        ref = per_bucket(ref_specs)
    errs = {"per-bucket": _rel_err(got_pb, ref),
            "grouped": _rel_err(got_gr, ref)}
    for name, e in errs.items():
        _check(e <= KERNEL_RTOL, f"{name} kernel server step differs from "
               f"the jnp reference by {e:.3g} of max (> {KERNEL_RTOL})")
    return errs


def phase_b(sz: Sizes, seed: int) -> str:
    """Chunked-AE kernel path through SampledSync, per-bucket and grouped."""
    import jax
    from jax.flatten_util import ravel_pytree
    from repro.core import FLConfig, FederatedRun
    from repro.core.scheduler import SampledSync
    from repro.core.task import ClassifierTask

    task = ClassifierTask(sz.classifier)
    datasets, ev = _split(sz, seed, sz.b_clients, sz.b_samples)
    init = task.init_params(jax.random.PRNGKey(seed))
    n = int(ravel_pytree(init)[0].size)
    comps = _rung_compressors(sz, seed, task, datasets, init)
    half = sz.b_clients // 2
    per_client = [comps[0]] * half + [comps[1]] * (sz.b_clients - half)
    out = []
    for grouped in (False, True):
        run = FederatedRun(
            task, datasets,
            FLConfig(n_rounds=sz.rounds, local_epochs=1, payload="update",
                     error_feedback=True, use_grouped_kernel=grouped,
                     seed=seed),
            compressors=per_client, eval_data=ev,
            scheduler=SampledSync(cohort=sz.b_cohort, sample_seed=seed))
        hist = run.run()
        losses = _losses(hist)
        _check(all(map(_finite, losses)),
               f"grouped={grouped}: global loss not finite: {losses}")
        for r in hist:
            rungs = {ci >= half for ci in r.participants}
            _check(len(r.participants) == sz.b_cohort
                   and rungs == {False, True},
                   f"round {r.round} cohort {r.participants} does not mix "
                   "both rungs")
        out.append(("grouped" if grouped else "per-bucket") + " loss "
                   + " -> ".join(f"{x:.4f}" for x in losses))
    errs = _cohort_check(sz, seed, comps, n)
    return ("; ".join(out) + f"; {KERNEL_MARK} in both server steps; "
            "kernel vs jnp reference: "
            + ", ".join(f"{k} {v:.3g}" for k, v in errs.items())
            + f" of max (tol {KERNEL_RTOL})")


def _serve_once(cfg, params, rounds: int) -> Tuple[Dict, Dict]:
    import numpy as np
    from repro.core.serve import init_state, make_step, run_serve
    _check(KERNEL_MARK in _compiled_text(make_step(cfg, params),
                                         init_state(cfg, params)),
           f"serve step for {type(cfg.spec).__name__} has no Pallas kernel")
    state, rep = run_serve(cfg, rounds, codec_params=params)
    g = state["global_flat"]
    _check(g.shape == (cfg.spec.size,) and _finite(g)
           and bool(np.any(np.asarray(g) != 0.0)),
           f"serve K={cfg.buffer_k}: global model not finite/non-zero")
    _check(int(state["version"]) == rounds + 1,
           f"serve K={cfg.buffer_k}: version {int(state['version'])} after "
           f"{rounds}+1 rounds")
    return state, rep


def phase_c(sz: Sizes, seed: int) -> str:
    """Streaming serve loop: q8 at two cohorts, kernel-path chunked AE."""
    import jax
    from jax.flatten_util import ravel_pytree
    from repro.core import ChunkedAESpec, QuantizeSpec, init_chunked_ae
    from repro.core.serve import ServeConfig
    from repro.core.task import ClassifierTask
    from repro.kernels.ops import use_kernel_default

    out = []
    for k in sz.c_cohorts:
        cfg = ServeConfig(n_clients=sz.c_population, buffer_k=k,
                          spec=QuantizeSpec(size=sz.c_q8_model, bits=8),
                          seed=seed)
        _, rep = _serve_once(cfg, None, sz.serve_rounds)
        out.append(f"q8 K={k} {rep['rounds_per_sec']:.1f} r/s")
    task = ClassifierTask(sz.classifier)
    n = int(ravel_pytree(task.init_params(jax.random.PRNGKey(seed)))[0].size)
    spec = ChunkedAESpec(size=n, cfg=sz.b_chunked,
                         use_kernel=use_kernel_default())
    params = init_chunked_ae(jax.random.PRNGKey(seed + 3), sz.b_chunked)
    cfg = ServeConfig(n_clients=sz.c_population, buffer_k=sz.c_ae_cohort,
                      spec=spec, seed=seed)
    _, rep = _serve_once(cfg, params, sz.serve_rounds)
    out.append(f"chunked-AE ({n:,} params) K={sz.c_ae_cohort} "
               f"{rep['rounds_per_sec']:.1f} r/s")
    return (f"N={sz.c_population:,}: " + "; ".join(out)
            + f"; {sz.serve_rounds}+1 rounds each, models finite, "
            f"{KERNEL_MARK} in every step")


def phase_four(sz: Sizes, seed: int) -> str:
    """The sharded servers on a 4-device ``clients`` mesh against their
    one-device results on the same seed and payloads."""
    import dataclasses as dc
    import jax
    import numpy as np
    from jax.flatten_util import ravel_pytree
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import (ChunkedAESpec, QuantizeSpec, codec,
                            init_chunked_ae)
    from repro.core.serve import ServeConfig, run_serve, synthetic_payloads
    from repro.core.task import ClassifierTask
    from repro.kernels.ops import use_kernel_default

    devices = jax.devices()
    _check(len(devices) == 4, f"--four-chips needs 4 devices, found "
           f"{len(devices)}")
    mesh = Mesh(np.array(devices), ("clients",))
    task = ClassifierTask(sz.classifier)
    n = int(ravel_pytree(task.init_params(jax.random.PRNGKey(seed)))[0].size)
    cases = [
        ("q8", QuantizeSpec(size=sz.c_q8_model, bits=8), None,
         max(sz.c_cohorts), REORDER_RTOL),
        ("chunked-AE", ChunkedAESpec(size=n, cfg=sz.b_chunked,
                                     use_kernel=use_kernel_default()),
         init_chunked_ae(jax.random.PRNGKey(seed + 3), sz.b_chunked),
         sz.c_ae_cohort, KERNEL_RTOL),
    ]
    out = []
    for name, spec, params, cohort, tol in cases:
        k_pay, k_w = jax.random.split(jax.random.PRNGKey(seed + 11))
        stacked = synthetic_payloads(spec, params, cohort, k_pay)
        w = jax.random.uniform(k_w, (cohort,)) + 0.5
        w = w / w.sum()
        one = codec.decode_and_aggregate(
            spec, params, jax.device_put(stacked, devices[0]),
            jax.device_put(w, devices[0]))
        sharded_in = jax.device_put(stacked, NamedSharding(mesh, P("clients")))
        for leaf in jax.tree_util.tree_leaves(sharded_in):
            holders = {s.device for s in leaf.addressable_shards}
            _check(len(holders) == 4 and all(
                s.data.shape[0] == cohort // 4
                for s in leaf.addressable_shards),
                f"{name}: cohort not split over the 4 devices")
        got = codec.decode_and_aggregate_sharded(spec, params, sharded_in, w,
                                                 mesh=mesh)
        _check(len(got.sharding.device_set) == 4,
               f"{name}: sharded reduction ran on "
               f"{len(got.sharding.device_set)} device(s)")
        e = _rel_err(got, one)
        _check(e <= tol, f"{name}: sharded decode_and_aggregate differs from "
               f"one device by {e:.3g} of max (> {tol})")
        out.append(f"decode_and_aggregate_sharded {name} C={cohort} "
                   f"err {e:.3g}")

    k = max(sz.c_cohorts)
    cfg = ServeConfig(n_clients=sz.c_population, buffer_k=k,
                      spec=QuantizeSpec(size=sz.c_q8_model, bits=8),
                      seed=seed)
    one_state, _ = run_serve(cfg, sz.serve_rounds)
    sh_state, rep = run_serve(dc.replace(cfg, shard=True), sz.serve_rounds)
    g1, g4 = one_state["global_flat"], sh_state["global_flat"]
    _check(len(g4.sharding.device_set) == 4,
           f"shard=True serve state on {len(g4.sharding.device_set)} "
           "device(s)")
    _check(_finite(g4), "shard=True serve model not finite")
    e = _rel_err(g4, g1)
    _check(e <= REORDER_RTOL, f"shard=True serve differs from one device by "
           f"{e:.3g} of max (> {REORDER_RTOL})")
    out.append(f"ServeConfig(shard=True) q8 K={k} err {e:.3g}, "
               f"{rep['rounds_per_sec']:.1f} r/s")
    return "; ".join(out) + " — every shard on its own device"


PHASES: Dict[str, Callable[[Sizes, int], str]] = {
    "A paper pipeline": phase_a,
    "B chunked-AE kernel path": phase_b,
    "C serve": phase_c,
}


# ------------------------------------------------------------------ main
def _count_cache_events(counts: Dict[str, int]) -> None:
    from jax import monitoring

    def listen(event: str, **_) -> None:
        if event.startswith("/jax/compilation_cache/"):
            key = event.rsplit("/", 1)[-1]
            counts[key] = counts.get(key, 0) + 1

    monitoring.register_event_listener(listen)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded servers on 4 devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    from repro.kernels import ops
    _check(not ops.interpret_default(), "Pallas would run in interpret mode")
    _check(ops.use_kernel_default(),
           "kernel path is off (REPRO_USE_KERNEL set to a false value?)")
    cache: Dict[str, int] = {}
    _count_cache_events(cache)

    sizes = _full_sizes()
    phases = ({"four-chip servers": phase_four} if args.four_chips
              else PHASES)
    for name, fn in phases.items():
        t0 = time.perf_counter()
        check = fn(sizes, args.seed)
        print(f"{name}: {time.perf_counter() - t0:.1f} s — {check}",
              flush=True)
    print(f"compile cache {cache_dir}: {cache.get('cache_hits', 0)} hits, "
          f"{cache.get('cache_misses', 0)} entries written", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
