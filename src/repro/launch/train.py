"""End-to-end training driver.

Two modes:
* ``--mode train`` — conventional data+model-parallel training of any
  ``--arch`` (reduced or full) on synthetic LM data.
* ``--mode fl`` — federated rounds with chunked-AE-compressed update
  exchange (the paper's technique): on real hardware the pod axis carries
  only latents; on CPU the same step runs on a degenerate (1,1,1) mesh.

Examples (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 50 --batch 4 --seq 64
  PYTHONPATH=src python -m repro.launch.train --preset lm100m --steps 300
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --mode fl --steps 20
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpoint import save_pytree
from repro.configs import get_config
from repro.configs.base import ArchConfig, ShapeConfig
from repro.data.pipeline import synthetic_lm_batch
from repro.launch.compile_cache import setup_compile_cache
from repro.models import init_params, param_count
from repro.models import sharding as shard_lib
from repro.optim.optimizers import make_optimizer

# ~100M-parameter preset for the end-to-end example driver
LM100M = ArchConfig(
    name="lm100m", family="dense", n_layers=12, d_model=768, n_heads=12,
    n_kv_heads=4, head_dim=64, d_ff=3072, vocab_size=16384,
    tie_embeddings=True, rope_theta=10000.0, activation="swiglu",
    remat=False, zero1=False, param_dtype="float32",
    compute_dtype="float32")

LM25M = dataclasses.replace(LM100M, name="lm25m", n_layers=8, d_model=384,
                            n_heads=6, n_kv_heads=2, d_ff=1536,
                            vocab_size=8192)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--preset", default=None, choices=["lm100m", "lm25m"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="train", choices=["train", "fl"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()
    setup_compile_cache()

    if args.preset:
        cfg = LM100M if args.preset == "lm100m" else LM25M
    else:
        cfg = get_config(args.arch or "llama3-8b")
        if args.reduced:
            cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, learning_rate=args.lr)

    params = init_params(jax.random.PRNGKey(0), cfg)
    print(f"arch={cfg.name} params={param_count(params):,} "
          f"mode={args.mode}", flush=True)

    if args.mode == "fl":
        from repro.core.autoencoder import ChunkedAEConfig, init_chunked_ae
        from repro.core.distributed import build_fl_round_step
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((1, 1, len(jax.devices())),
                         ("pod", "data", "model"))
        shape = ShapeConfig("cli", args.seq, args.batch, "train")
        ae_cfg = ChunkedAEConfig(chunk_size=512, hidden=(128,),
                                 latent_chunk=16)
        bundle = build_fl_round_step(cfg, shape, mesh, ae_cfg)
        ae_params = init_chunked_ae(jax.random.PRNGKey(1), ae_cfg)
        opt = make_optimizer(cfg.optimizer, cfg.learning_rate,
                             weight_decay=cfg.weight_decay,
                             grad_clip=cfg.grad_clip)
        opt_state = opt.init(params)
        with mesh:
            step_fn = jax.jit(
                bundle.fn,
                in_shardings=shard_lib.named(mesh, bundle.in_shardings),
                out_shardings=shard_lib.named(mesh, bundle.out_shardings))
            t0 = time.time()
            for i in range(args.steps):
                batch = synthetic_lm_batch(i, cfg.vocab_size, args.batch,
                                           args.seq)
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     ae_params, batch)
                if i % args.log_every == 0 or i == args.steps - 1:
                    print(f"fl round {i:4d} loss={float(metrics['loss']):.4f} "
                          f"acc={float(metrics['accuracy']):.3f} "
                          f"({(time.time() - t0) / (i + 1):.2f}s/round)",
                          flush=True)
    else:
        from repro.models import train_loss
        opt = make_optimizer(cfg.optimizer, cfg.learning_rate,
                             weight_decay=cfg.weight_decay,
                             grad_clip=cfg.grad_clip)
        opt_state = opt.init(params)

        @jax.jit
        def step_fn(p, s, b):
            (_, metrics), grads = jax.value_and_grad(
                train_loss, has_aux=True)(p, cfg, b)
            p, s = opt.update(p, grads, s)
            return p, s, metrics

        t0 = time.time()
        for i in range(args.steps):
            batch = synthetic_lm_batch(i, cfg.vocab_size, args.batch,
                                       args.seq)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss={float(metrics['loss']):.4f} "
                      f"acc={float(metrics['accuracy']):.3f} "
                      f"({(time.time() - t0) / (i + 1):.2f}s/step)",
                      flush=True)

    if args.checkpoint:
        save_pytree(args.checkpoint, params,
                    metadata={"arch": cfg.name, "steps": args.steps})
        print(f"saved checkpoint to {args.checkpoint}")


if __name__ == "__main__":
    main()
