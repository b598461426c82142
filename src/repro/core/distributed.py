"""The paper's technique as a first-class distributed training feature.

``build_fl_round_step`` assembles a federated round over the production mesh:
the ``pod`` mesh axis is the collaborator axis (DESIGN.md §3.1). Each pod
computes its local gradients (conventional data/model parallelism *inside*
the pod — handled by GSPMD auto axes under a partial-manual ``shard_map``),
then — instead of all-reducing full gradients across pods — each pod:

  1. chunk-encodes every update leaf with the shared chunked AE
     (collaborator-side encoder, Eq. 1),
  2. ``pmean``s only the LATENTS across the ``pod`` axis — the sole
     cross-pod traffic, smaller by the compression ratio,
  3. decodes (aggregator-side decoder, Eq. 2) and applies the optimizer.

The roofline §collective term of this step vs. the baseline train step
quantifies the paper's bandwidth claim at datacenter scale.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.autoencoder import (ChunkedAEConfig, fc_decode, fc_encode,
                                    init_chunked_ae)
from repro.models import model as model_lib
from repro.optim.optimizers import make_optimizer

Pytree = Any

# default production codec: 4096-element chunks → 8 latents = 512x
DEFAULT_AE = ChunkedAEConfig(chunk_size=4096, hidden=(512,), latent_chunk=8)


def leaf_encode(ae_params: Pytree, ae_cfg: ChunkedAEConfig,
                leaf: jax.Array) -> jax.Array:
    """Flatten a param leaf into chunks and encode: (n_chunks, latent)."""
    flat = leaf.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % ae_cfg.chunk_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(-1, ae_cfg.chunk_size)
    return fc_encode(ae_params, ae_cfg.as_fc(), chunks)


def leaf_decode(ae_params: Pytree, ae_cfg: ChunkedAEConfig,
                latents: jax.Array, like: jax.Array) -> jax.Array:
    chunks = fc_decode(ae_params, ae_cfg.as_fc(), latents)
    flat = chunks.reshape(-1)[:like.size]
    return flat.reshape(like.shape).astype(like.dtype)


def encode_tree(ae_params: Pytree, ae_cfg: ChunkedAEConfig,
                tree: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda leaf: leaf_encode(ae_params, ae_cfg, leaf), tree)


def decode_tree(ae_params: Pytree, ae_cfg: ChunkedAEConfig,
                latents: Pytree, like: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda z, l: leaf_decode(ae_params, ae_cfg, z, l), latents, like)


def compressed_fraction(tree: Pytree, ae_cfg: ChunkedAEConfig) -> float:
    """Latent bytes / original bytes for a param tree (exactly what crosses
    the pod axis vs. what a full all-reduce would move)."""
    orig = comp = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        n = leaf.size
        chunks = -(-n // ae_cfg.chunk_size)
        orig += n * 4
        comp += chunks * ae_cfg.latent_chunk * 4
    return comp / max(orig, 1)


def _spec_shards(spec, mesh: Mesh) -> int:
    total = 1
    for axis in spec:
        if axis is None:
            continue
        for a in (axis if isinstance(axis, tuple) else (axis,)):
            total *= mesh.shape[a]
    return total


def build_fl_round_step(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                        ae_cfg: ChunkedAEConfig = DEFAULT_AE,
                        aligned: bool = True):
    """StepBundle for one federated round on the (pod, data, model) mesh.

    ``aligned=True`` (§Perf iteration 1 for the FL step): the codec runs in a
    fully-manual nested shard_map over (data, model) — every device encodes
    its LOCAL gradient shard, so the chunking can never force GSPMD to
    all-gather model-sharded leaves (the naive flatten-then-chunk baseline
    measured 8–12 TB/device of resharding all-reduce). Only the latent
    ``pmean`` crosses the pod axis, exactly as DESIGN.md §3 specifies.
    """
    from jax.sharding import PartitionSpec
    from repro.launch.steps import (StepBundle, _opt_specs, batch_shapes,
                                    param_shapes)
    from repro.models import sharding as shard_lib

    assert "pod" in mesh.shape, "FL round step needs the multi-pod mesh"
    opt = make_optimizer(cfg.optimizer, cfg.learning_rate,
                         weight_decay=cfg.weight_decay,
                         grad_clip=cfg.grad_clip)

    p_shapes_early = param_shapes(cfg)
    grad_specs = shard_lib.param_specs(p_shapes_early, mesh)

    def _codec_local(grads_local, ae_p):
        """Runs per-device on raw local shards (inner manual region)."""
        latents = jax.tree_util.tree_map(
            lambda leaf: leaf_encode(ae_p, ae_cfg, leaf), grads_local)
        # the ONLY cross-pod communication: compressed latents
        latents = jax.lax.pmean(latents, "pod")
        return jax.tree_util.tree_map(
            lambda z, g: leaf_decode(ae_p, ae_cfg, z, g),
            latents, grads_local)

    def per_pod(params, opt_state, ae_params, batch):
        # local gradients: data/model parallelism inside the pod (auto axes)
        if cfg.grad_reduce_dtype == "bfloat16":
            cast_p = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.bfloat16)
                if p.dtype == jnp.float32 else p, params)
            (_, metrics), grads = jax.value_and_grad(
                model_lib.train_loss, has_aux=True)(cast_p, cfg, batch)
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(jnp.float32), grads, params)
        else:
            (_, metrics), grads = jax.value_and_grad(
                model_lib.train_loss, has_aux=True)(params, cfg, batch)
        grads = dict(grads)
        if aligned:
            # pin gradient sharding to the param layout, then run the codec
            # on raw local shards (zero collectives by construction)
            grads = jax.lax.with_sharding_constraint(grads, grad_specs)
            ae_rep = jax.tree_util.tree_map(lambda _: PartitionSpec(),
                                            ae_params)
            # nested manual region: the mesh is inferred from context
            # (the outer pod-manual shard_map has already marked `pod`
            # Manual; passing the concrete mesh would re-introduce it)
            decoded = jax.shard_map(
                _codec_local, axis_names={"data", "model"},
                in_specs=(grad_specs, ae_rep), out_specs=grad_specs,
                check_vma=False)(grads, ae_params)
        else:
            # naive baseline: flatten+chunk whole leaves (GSPMD reshards)
            latents = encode_tree(ae_params, ae_cfg, grads)
            latents = jax.lax.pmean(latents, "pod")
            decoded = decode_tree(ae_params, ae_cfg, latents, grads)
        params, opt_state = opt.update(params, decoded, opt_state)
        loss = jax.lax.pmean(metrics["loss"], "pod")
        acc = jax.lax.pmean(metrics["accuracy"], "pod")
        return params, opt_state, {"loss": loss, "accuracy": acc}

    p_shapes = param_shapes(cfg)
    o_shapes = jax.eval_shape(opt.init, p_shapes)
    b_shapes = batch_shapes(cfg, shape)
    ae_shapes = jax.eval_shape(
        functools.partial(init_chunked_ae, cfg=ae_cfg),
        jax.random.PRNGKey(0))

    p_specs = shard_lib.param_specs(p_shapes, mesh)
    # XLA workaround: a vocab-sharded embedding gather inside a
    # partial-manual shard_map trips a CHECK in the SPMD partitioner
    # (PartitionGather + manual pod subgroups). Shard the embedding on the
    # feature dim instead for the FL step — the gather dim stays whole and
    # partitions trivially; the tied/untied head keeps its own spec.
    if "embed" in p_specs and cfg.d_model % mesh.shape["model"] == 0:
        p_specs = dict(p_specs, embed=P(None, "model"))
    o_specs = _opt_specs(cfg, mesh, p_specs, p_shapes, o_shapes)
    b_specs = shard_lib.batch_specs(b_shapes, mesh)
    ae_specs = jax.tree_util.tree_map(lambda _: P(), ae_shapes)
    metric_specs = {"loss": P(), "accuracy": P()}

    # shard_map manual only over 'pod'; batch dim0 carries pod + data
    inner_batch_shapes = dict(
        b_shapes,
        h0=jax.ShapeDtypeStruct(
            (shape.global_batch, shape.seq_len, cfg.d_model),
            jnp.dtype(cfg.compute_dtype)))
    sm_batch_in = jax.tree_util.tree_map(
        lambda s: P("pod") if s.ndim >= 1 else P(), inner_batch_shapes)
    rep = jax.tree_util.tree_map(lambda _: P(), p_shapes)
    rep_o = jax.tree_util.tree_map(lambda _: P(), o_shapes)
    rep_ae = jax.tree_util.tree_map(lambda _: P(), ae_shapes)

    sm = jax.shard_map(
        per_pod, mesh=mesh, axis_names={"pod"},
        in_specs=(rep, rep_o, rep_ae, sm_batch_in),
        out_specs=(rep, rep_o, {"loss": P(), "accuracy": P()}),
        check_vma=False)

    def step(params, opt_state, ae_params, batch):
        # token-embedding gather OUTSIDE the manual region: the SPMD
        # partitioner CHECK-fails on gathers under manual pod subgroups
        # (input-embedding path is stop-gradiented — frozen in FL mode;
        # tied/untied head gradients still flow through the logits matmul)
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        frozen = dict(params,
                      embed=jax.lax.stop_gradient(params["embed"]))
        h0 = model_lib._embed_inputs(frozen, cfg, batch, positions,
                                     train=True)
        return sm(params, opt_state, ae_params, dict(batch, h0=h0))

    return StepBundle(
        name=f"fl_round:{cfg.name}:{shape.name}",
        fn=step,
        args=(p_shapes, o_shapes, ae_shapes, b_shapes),
        in_shardings=(p_specs, o_specs, ae_specs, b_specs),
        out_shardings=(p_specs, o_specs, metric_specs),
        donate_argnums=(0, 1),
    )
