"""What the benchmark takes from the program: its codec specs and serve
step, built from a configuration's codec section, the serve step's scope
names, and the classifier a configuration's model section describes.
Nothing else here is the program's; weights, traffic and the reference
are the benchmark's own.
"""
from __future__ import annotations

import math
import os
import sys
from typing import Dict

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core import serve as _serve  # noqa: E402

# The serve step's layers, as the program names them with
# ``jax.named_scope``; none for a program that names none.
SCOPES = tuple(getattr(_serve, "SCOPES", ()))

# The serve step's own PRNG seed. It is a constant of the compiled step, so
# it stays fixed and every run finds the step in the compilation cache; a
# run's seed varies the initial queue, the sequence numbers (which key each
# round's payloads and latencies), the global model and the weights.
PROGRAM_SEED = 0

# The server's step size: 1, plain buffered averaging, where the global
# model gains the round's staleness-weighted mean update. Serve traffic has
# no stragglers (the reference models none).
SERVER_LR = 1.0


def codec_spec(codec: Dict, use_kernel=None):
    """The program's codec spec for a configuration's codec section."""
    from repro.configs.paper import AEConfig
    from repro.core import codec as c
    from repro.core.autoencoder import ChunkedAEConfig
    from repro.kernels.ops import use_kernel_default

    if codec["kind"] == "chunked_ae":
        if codec["final_activation"] != "linear":
            raise ValueError("the chunked AE's last decoder layer is linear")
        ae = c.ChunkedAESpec(
            size=codec["size"],
            cfg=ChunkedAEConfig(chunk_size=codec["chunk_size"],
                                hidden=tuple(codec["hidden"]),
                                latent_chunk=codec["latent_chunk"],
                                activation=codec["activation"]),
            use_kernel=use_kernel_default(use_kernel))
        q = codec.get("quantize")
        if q is None:
            return ae
        return c.ChainSpec((ae, c.QuantizeSpec(
            size=ae.n_chunks * codec["latent_chunk"], bits=q["bits"],
            block=q["block"])))
    if codec["kind"] == "fc_ae":
        if codec.get("quantize") is not None:
            raise ValueError("fc_ae with a quantize stage is not wired")
        return c.FCAESpec(size=codec["size"], cfg=AEConfig(
            input_dim=codec["input_dim"],
            encoder_hidden=tuple(codec["encoder_hidden"]),
            latent_dim=codec["latent_dim"], activation=codec["activation"],
            final_activation=codec["final_activation"]))
    raise ValueError(f"unknown codec kind {codec['kind']!r}")


def codec_params(codec: Dict, dec: Dict):
    """The program's codec params around the server's decoder ``dec``.

    The program's param tree also holds an encoder, which the serve step
    reads only for its shapes (``jax.eval_shape`` of the encode). Called
    inside the step's trace, the zeros below are never computed: the
    server holds only the decoder, as a deployed one would."""
    if codec["kind"] == "chunked_ae":
        dims = [codec["chunk_size"], *codec["hidden"], codec["latent_chunk"]]
    else:
        dims = [codec["input_dim"], *codec["encoder_hidden"],
                codec["latent_dim"]]
    enc = [{"w": jnp.zeros((a, b), jnp.float32),
            "b": jnp.zeros((b,), jnp.float32)}
           for a, b in zip(dims[:-1], dims[1:])]
    ae = {"enc": enc, "dec": dec["dec"], "norm": dec["norm"]}
    if codec["kind"] == "chunked_ae" and codec.get("quantize") is not None:
        return (ae, None)
    return ae


def serve_config(traffic: Dict, spec):
    from repro.core.serve import ServeConfig
    return ServeConfig(
        n_clients=traffic["population"], buffer_k=traffic["buffer_k"],
        spec=spec, staleness_power=traffic["staleness_power"],
        server_lr=SERVER_LR,
        base_latency=traffic["base_latency"], jitter=traffic["jitter"],
        straggler_frac=0.0, seed=PROGRAM_SEED)


def serve_step(codec: Dict, cfg):
    """The program's serve step with the decoder as an argument:
    ``(state, dec) -> state``, state donated. ``make_step`` closes over
    its codec params, which would bake the decoder into the compiled
    program as a constant (707 MB for the FC AE, and a new program for
    every seed); tracing it inside this jit passes them in instead."""
    from repro.core.serve import make_step

    def step(state, dec):
        return make_step(cfg, codec_params(codec, dec))(state)

    return jax.jit(step, donate_argnums=0)


def serve_init_state(cfg):
    from repro.core.serve import init_state
    return init_state(cfg)


def classifier_size(model: Dict) -> int:
    """The parameter count of the classifier the program builds from a
    configuration's model section (``repro.configs.paper.ClassifierConfig``
    and ``init_classifier``), counted from shapes alone."""
    from repro.configs.paper import ClassifierConfig
    from repro.models.classifiers import init_classifier
    cfg = ClassifierConfig(name="bench", **{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in model.items() if k != "update_size"})
    shapes = jax.eval_shape(lambda k: init_classifier(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))
