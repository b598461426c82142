"""Tests for the §Perf framework features: activation-sharding context,
2D inference sharding, decomposed-score attention, roofline model-FLOPs,
and chunk-size invariance of the SSD scan."""
try:
    import hypothesis
    import hypothesis.strategies as st
except ModuleNotFoundError:       # dev extra absent: property tests skip
    from _hypothesis_stub import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch.mesh import auto_mesh
from repro.models.attention import flash_attention
from repro.models.partition_ctx import activation_sharding, \
    constrain_activations
from repro.models.ssm import ssd_scan

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=20,
    suppress_health_check=list(hypothesis.HealthCheck))
hypothesis.settings.load_profile("ci")


def test_constrain_activations_noop_without_context():
    x = jnp.ones((2, 4, 8))
    y = constrain_activations(x)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_activations_applies_in_context():
    """Under a 1-device mesh the constraint must be a semantic no-op."""
    mesh = auto_mesh((1, 1), ("data", "model"))
    x = jnp.arange(24, dtype=jnp.float32).reshape(2, 4, 3)
    with mesh:
        with activation_sharding(("data",), "model"):
            y = jax.jit(constrain_activations)(x)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fully_shard_adds_data_axis_to_big_leaves():
    from repro.launch.steps import param_shapes
    from repro.models import sharding as shard_lib
    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("llama3-8b")
    shapes = param_shapes(cfg)
    specs = shard_lib.param_specs(shapes, mesh)
    specs2 = shard_lib.fully_shard(specs, shapes, mesh)
    flat1 = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    flat2 = jax.tree_util.tree_leaves(
        specs2, is_leaf=lambda x: isinstance(x, P))
    more = sum(1 for a, b in zip(flat1, flat2)
               if a != b and "data" in str(b))
    assert more > 0
    # all still divisibility-valid
    def check(shp, spec):
        for dim, axis in zip(shp.shape, tuple(spec) + (None,) * 8):
            if axis is not None:
                axes = axis if isinstance(axis, tuple) else (axis,)
                tot = 1
                for a in axes:
                    tot *= mesh.shape[a]
                assert dim % tot == 0
    jax.tree_util.tree_map(check, shapes, specs2)


def test_flash_attention_extra_qk_matches_concat():
    """Decomposed scores == concatenated q/k (the MLA formulation)."""
    k = jax.random.PRNGKey(0)
    B, S, H, D, P2 = 2, 33, 4, 16, 8
    q1 = jax.random.normal(k, (B, S, H, D))
    k1 = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    q2 = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, P2))
    k2 = jax.random.normal(jax.random.PRNGKey(4), (B, S, P2))

    scale = (D + P2) ** -0.5
    got = flash_attention(q1, k1, v, extra_qk=(q2, k2), scale=scale,
                          q_chunk=16, kv_chunk=16)
    q_cat = jnp.concatenate([q1, q2], axis=-1)
    k_cat = jnp.concatenate(
        [k1, jnp.broadcast_to(k2[:, :, None, :], (B, S, H, P2))], axis=-1)
    want = flash_attention(q_cat, k_cat, v, q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-3)


@hypothesis.given(st.sampled_from([2, 3, 5, 7, 16, 23]))
def test_ssd_scan_chunk_invariance(chunk):
    """SSD output must not depend on the chunk size (dual-form identity)."""
    k = jax.random.PRNGKey(0)
    b, s, h, p, g, n = 1, 24, 2, 4, 1, 3
    x = jax.random.normal(k, (b, s, h, p)) * 0.5
    dA = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                            (b, s, h)))
    B = jax.random.normal(jax.random.PRNGKey(2), (b, s, g, n)) * 0.5
    C = jax.random.normal(jax.random.PRNGKey(3), (b, s, g, n)) * 0.5
    y_ref, st_ref = ssd_scan(x, dA, B, C, chunk=s)
    y, stt = ssd_scan(x, dA, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(stt), np.asarray(st_ref),
                               atol=1e-4, rtol=1e-3)


def test_model_flops_estimates_positive_and_ordered():
    from repro.roofline.analysis import attention_flops, model_flops
    cfg = get_config("llama3-8b")
    f_train = model_flops(cfg, SHAPES["train_4k"])
    f_prefill = model_flops(cfg, SHAPES["prefill_32k"])
    f_decode = model_flops(cfg, SHAPES["decode_32k"])
    assert f_train > f_prefill > f_decode > 0
    # at 32k, attention is a large fraction of prefill (≥35% for llama3;
    # higher for wider-headed archs like deepseek)
    assert attention_flops(cfg, SHAPES["prefill_32k"]) > \
        0.25 * f_prefill
    # ssm arch: no attention flops
    assert attention_flops(get_config("mamba2-2.7b"),
                           SHAPES["prefill_32k"]) == 0.0


def test_compressed_fraction_matches_config():
    from repro.core.autoencoder import ChunkedAEConfig
    from repro.core.distributed import compressed_fraction
    ae = ChunkedAEConfig(chunk_size=512, hidden=(64,), latent_chunk=16)
    tree = {"w": jnp.zeros((1024, 512))}         # divides evenly
    frac = compressed_fraction(tree, ae)
    assert frac == pytest.approx(16 / 512, rel=1e-6)
