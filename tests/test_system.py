"""End-to-end system tests: the paper's full pipeline on CPU-sized configs.

1. Pre-pass → AE training → FC-AE-compressed FL (the paper's architecture,
   Figs. 2-3) reaching working accuracy.
2. The distributed FL round step (chunked-AE over the pod axis) executes on a
   degenerate (1,1,1) mesh and produces finite updated params.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES
from repro.configs.paper import MNIST_CLASSIFIER, AEConfig
from repro.core import (FCAECompressor, FLConfig, FederatedRun, run_prepass,
                        validation_model_curve, fc_reconstruct)
from repro.data.pipeline import dirichlet_partition, mnist_like
from repro.launch.mesh import auto_mesh


@pytest.fixture(scope="module")
def prepass_result():
    ae_cfg = AEConfig(input_dim=15_910, encoder_hidden=(64,), latent_dim=32)
    data = mnist_like(0, 512)
    return run_prepass(jax.random.PRNGKey(0), MNIST_CLASSIFIER, ae_cfg,
                       data, prepass_epochs=12, ae_epochs=200), ae_cfg, data


def test_prepass_produces_weights_dataset(prepass_result):
    out, ae_cfg, _ = prepass_result
    assert out["weights_dataset"].shape == (12, 15_910)
    assert out["ae_history"]["loss"][-1] < out["ae_history"]["loss"][0]
    assert out["decoder_params"] > 0


def test_validation_model_tracks_original(prepass_result):
    """Paper §5.1 validation model: AE-predicted weights give a similar
    accuracy curve to the original weights (Figs. 5/7)."""
    out, ae_cfg, data = prepass_result
    curve = validation_model_curve(
        MNIST_CLASSIFIER, out["weights_dataset"],
        lambda w: fc_reconstruct(out["ae_params"], ae_cfg, w), data)
    orig = np.array(curve["original_acc"])
    pred = np.array(curve["predicted_acc"])
    # the final-epoch reconstruction must stay within 15 acc points
    assert abs(orig[-1] - pred[-1]) < 0.15
    assert pred[-1] > 0.5


def test_fl_with_ae_compression_end_to_end(prepass_result):
    """The paper's full FL setup: AE-compressed updates, 2 collaborators."""
    out, ae_cfg, _ = prepass_result
    # AE trained on raw weights also codes updates reasonably only if
    # trained on deltas; for the system test we train on weights and
    # compress weights-style payloads (paper's §5.2 protocol).
    from repro.data.pipeline import train_eval_split
    train, eval_data = train_eval_split(mnist_like(1, 768), 256)
    data = dirichlet_partition(0, train, 2, alpha=1.0)
    comp = [FCAECompressor(out["ae_params"], ae_cfg) for _ in range(2)]
    run = FederatedRun(MNIST_CLASSIFIER, data,
                       FLConfig(n_rounds=2, local_epochs=1,
                                error_feedback=True),
                       compressors=comp, eval_data=eval_data)
    hist = run.run()
    assert hist[-1].compression_ratio > 300      # ~497x nominal
    assert np.isfinite(hist[-1].global_metrics["loss"])
    totals = run.total_bytes()
    assert totals["effective_ratio"] > 300


def test_distributed_fl_round_degenerate_mesh():
    """The chunked-AE pod-axis round step lowers AND executes on a (1,1,1)
    mesh — same code path the 512-chip dry-run compiles."""
    from repro.configs import get_config
    from repro.core.distributed import build_fl_round_step
    from repro.core.autoencoder import ChunkedAEConfig, init_chunked_ae
    from repro.models import init_params
    from repro.models import sharding as shard_lib
    from repro.optim.optimizers import make_optimizer
    import dataclasses

    mesh = auto_mesh((1, 1, 1), ("pod", "data", "model"))
    cfg = get_config("llama3-8b").reduced()
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=16,
                                global_batch=2)
    ae_cfg = ChunkedAEConfig(chunk_size=128, hidden=(32,), latent_chunk=4)
    bundle = build_fl_round_step(cfg, shape, mesh, ae_cfg)

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(cfg.optimizer, cfg.learning_rate,
                         weight_decay=cfg.weight_decay,
                         grad_clip=cfg.grad_clip)
    opt_state = opt.init(params)
    ae_params = init_chunked_ae(jax.random.PRNGKey(1), ae_cfg)
    k = jax.random.PRNGKey(2)
    batch = {"tokens": jax.random.randint(k, (2, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(k, (2, 16), 0, cfg.vocab_size)}
    with mesh:
        jitted = jax.jit(
            bundle.fn,
            in_shardings=shard_lib.named(mesh, bundle.in_shardings),
            out_shardings=shard_lib.named(mesh, bundle.out_shardings))
        new_params, new_opt, metrics = jitted(params, opt_state, ae_params,
                                              batch)
    assert jnp.isfinite(metrics["loss"])
    l0 = jax.tree_util.tree_leaves(params)[0]
    l1 = jax.tree_util.tree_leaves(new_params)[0]
    assert l0.shape == l1.shape
    assert not jnp.allclose(l0, l1)


def test_input_specs_cover_all_shapes():
    """input_specs exist for every (arch × shape) — the dry-run contract."""
    from repro.configs import get_config
    from repro.launch.steps import batch_shapes, cache_shapes
    cfg = get_config("llama3-8b")
    for name, shape in SHAPES.items():
        b = batch_shapes(cfg, shape)
        assert b["tokens"].shape == (shape.global_batch, shape.seq_len)
        if shape.mode == "decode":
            c = cache_shapes(cfg, shape)
            assert c["index"].shape == ()
