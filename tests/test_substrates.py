"""Substrate tests: optimizers, data pipeline, checkpointing, sharding rules,
HLO static analyzer."""
import os

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

from repro.checkpoint.checkpoint import load_pytree, save_pytree
from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import (batches, color_imbalance_split,
                                 dirichlet_partition, mnist_like,
                                 synthetic_lm_batch)
from repro.models import sharding as shard_lib
from repro.optim.optimizers import (clip_by_global_norm, global_norm,
                                    make_optimizer)

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=25,
    suppress_health_check=list(hypothesis.HealthCheck))
hypothesis.settings.load_profile("ci")


# ------------------------------------------------------------------ optim
@pytest.mark.parametrize("name", ["sgd", "sgdm", "sgdm_bf16", "adam",
                                  "adamw"])
def test_optimizer_reduces_quadratic(name):
    opt = make_optimizer(name, lr=0.1)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)  # noqa: E731
    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state = opt.update(params, g, state)
    assert float(loss(params)) < 0.05


def test_grad_clip():
    g = {"a": jnp.full((4,), 10.0)}
    clipped = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    small = {"a": jnp.full((4,), 0.01)}
    same = clip_by_global_norm(small, 1.0)
    np.testing.assert_allclose(np.asarray(same["a"]), 0.01, rtol=1e-6)


# ------------------------------------------------------------------ data
def test_dirichlet_partition_covers_all():
    data = mnist_like(0, 500)
    parts = dirichlet_partition(0, data, 5, alpha=0.3)
    assert len(parts) == 5
    total = sum(p["x"].shape[0] for p in parts)
    assert total >= 495                     # empty-client fill may duplicate
    for p in parts:
        assert p["x"].shape[0] >= 1


def test_color_imbalance_grayscale():
    (color, gray), _ = color_imbalance_split(0, 64, n_eval=16)
    gx = np.asarray(gray["x"])
    assert np.allclose(gx[..., 0], gx[..., 1]) and \
        np.allclose(gx[..., 1], gx[..., 2])
    cx = np.asarray(color["x"])
    assert not np.allclose(cx[..., 0], cx[..., 1])


def test_batches_deterministic():
    data = mnist_like(3, 100)
    b1 = list(batches(7, data, 32))
    b2 = list(batches(7, data, 32))
    assert len(b1) == 3
    np.testing.assert_array_equal(np.asarray(b1[0]["x"]),
                                  np.asarray(b2[0]["x"]))


def test_lm_batch_shapes():
    b = synthetic_lm_batch(0, 1000, 4, 16)
    assert b["tokens"].shape == (4, 16)
    assert int(b["tokens"].max()) < 1000
    np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                  np.asarray(b["labels"][:, :-1]))


# ------------------------------------------------------------------ ckpt
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16),
                       "c": jnp.array(3, jnp.int32)}}
    path = os.path.join(tmp_path, "ck.npz")
    save_pytree(path, tree, metadata={"round": 7})
    restored, meta = load_pytree(path, tree)
    assert meta["round"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


# ------------------------------------------------------------------ sharding
def _abstract_mesh(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("multi", [False, True])
def test_param_specs_divisible(arch, multi):
    """Every sharded dim divides the mesh axis — the GSPMD validity
    precondition for all 10 architectures on both production meshes."""
    from repro.launch.steps import param_shapes
    cfg = get_config(arch)
    mesh = _abstract_mesh(multi)
    shapes = param_shapes(cfg)
    specs = shard_lib.param_specs(shapes, mesh)

    def check(path, shp, spec):
        for dim, axis in zip(shp.shape, tuple(spec) + (None,) * 10):
            if axis is not None:
                axes = axis if isinstance(axis, tuple) else (axis,)
                total = 1
                for a in axes:
                    total *= mesh.shape[a]
                assert dim % total == 0, (path, shp.shape, spec)
    jax.tree_util.tree_map_with_path(
        lambda p, s, sp: check(p, s, sp), shapes, specs)


def test_zero1_spec_adds_data_axis():
    mesh = _abstract_mesh()
    spec = shard_lib.zero1_spec(P(None, "model"), (4096, 1024), mesh)
    assert spec == P("data", "model")
    # non-divisible dim stays unsharded
    spec2 = shard_lib.zero1_spec(P(None, "model"), (7, 1024), mesh)
    assert spec2 == P(None, "model")


def test_batch_spec_multi_pod():
    mesh = _abstract_mesh(True)
    s = shard_lib.data_spec(mesh, 256, 2)
    assert s == P(("pod", "data"), None)
    s1 = shard_lib.data_spec(mesh, 1, 2)      # batch 1: replicate
    assert s1 == P(None, None)


# ------------------------------------------------------------------ hlo parse
def test_hlo_parser_counts_trip_multiplied_dots():
    from repro.roofline.hlo_parse import analyze_hlo

    def f(x, w):
        def body(c, _):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y
    w = jnp.eye(64)
    x = jnp.ones((64, 64))
    compiled = jax.jit(f).lower(x, w).compile()
    cost = analyze_hlo(compiled.as_text())
    expected = 2 * 64 * 64 * 64 * 5          # 5 loop iterations
    assert cost.flops == pytest.approx(expected, rel=0.05)


def test_hlo_parser_collectives_synthetic():
    from repro.roofline.hlo_parse import analyze_hlo
    hlo = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[128,256] get-tuple-element(%p), index=1
  %ar = f32[128,256] all-reduce(%x), replica_groups={}, to_apply=%add
  ROOT %t = (s32[], f32[128,256]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[128,256])) -> pred[] {
  %p = (s32[], f32[128,256]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (x: f32[128,256]) -> f32[128,256] {
  %x = f32[128,256] parameter(0)
  %c = s32[] constant(0)
  %t0 = (s32[], f32[128,256]) tuple(%c, %x)
  %w = (s32[], f32[128,256]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[128,256] get-tuple-element(%w), index=1
}
"""
    cost = analyze_hlo(hlo)
    assert cost.collective_bytes == 128 * 256 * 4 * 7
    assert cost.collective_breakdown["all-reduce"] == 128 * 256 * 4 * 7
