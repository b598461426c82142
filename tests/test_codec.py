"""Jit-native codec protocol tests (DESIGN.md §7): spec staticness, jit/vmap
compatibility, batched-decode ≡ per-client-decode, fused decode+aggregate ≡
decode-then-weighted_mean, the shard_map variant, and the kernel dispatch /
mandatory-orig_len satellite contracts."""
try:
    import hypothesis
    import hypothesis.strategies as st
except ModuleNotFoundError:       # dev extra absent: property tests skip
    from _hypothesis_stub import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.configs.paper import AEConfig
from repro.core import (ChunkedAECompressor, ChunkedAEConfig,
                        ComposedCompressor, FCAECompressor,
                        IdentityCompressor, QuantizeCompressor,
                        TopKCompressor, codec, init_chunked_ae, init_fc_ae,
                        normalize_weights, weighted_mean)

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=20,
    suppress_health_check=list(hypothesis.HealthCheck))
hypothesis.settings.load_profile("ci")

N = 1250                                     # deliberately chunk-ragged

_CHUNK_CFG = ChunkedAEConfig(chunk_size=128, hidden=(32,), latent_chunk=4)
_CHUNK_PARAMS = init_chunked_ae(jax.random.PRNGKey(0), _CHUNK_CFG)
_FC_CFG = AEConfig(input_dim=2048, encoder_hidden=(64,), latent_dim=16)
_FC_PARAMS = init_fc_ae(jax.random.PRNGKey(0), _FC_CFG)


def _all_compressors():
    return [
        IdentityCompressor(),
        QuantizeCompressor(bits=8, block=64),
        QuantizeCompressor(bits=4, block=64),
        TopKCompressor(fraction=0.1),
        FCAECompressor(_FC_PARAMS, _FC_CFG),
        ChunkedAECompressor(_CHUNK_PARAMS, _CHUNK_CFG, use_kernel=False),
        ChunkedAECompressor(_CHUNK_PARAMS, _CHUNK_CFG, use_kernel=True),
        ComposedCompressor(
            inner=ChunkedAECompressor(_CHUNK_PARAMS, _CHUNK_CFG,
                                      use_kernel=False), bits=8, block=64),
    ]


def _ids():
    return [c.name + ("_k" if getattr(c, "use_kernel", False) else "")
            for c in _all_compressors()]


def _flat(seed, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), (N,)) * scale


# ------------------------------------------------------------ spec contract
@pytest.mark.parametrize("comp", _all_compressors(), ids=_ids())
def test_spec_is_hashable_and_jit_static(comp):
    """Specs are frozen/hashable → usable as jit static args; two calls with
    the same spec hit the same compiled executable (no orig_len tracing)."""
    spec = comp.spec(N)
    assert hash(spec) == hash(comp.spec(N))
    assert spec == comp.spec(N)
    assert spec.size == N
    p = comp.codec_params()
    enc = jax.jit(codec.encode, static_argnums=0)
    dec = jax.jit(codec.decode, static_argnums=0)
    payload = enc(spec, p, _flat(0))
    out = dec(spec, p, payload)
    assert out.shape == (N,)
    # no length metadata crosses the wire: payload is spec-decodable alone
    assert "orig_len" not in payload and "size" not in payload


@pytest.mark.parametrize("comp", _all_compressors(), ids=_ids())
def test_roundtrip_under_jit_matches_eager(comp):
    spec, p = comp.spec(N), comp.codec_params()
    x = _flat(1)
    eager = codec.decode(spec, p, codec.encode(spec, p, x))
    jitted = jax.jit(
        lambda xx: codec.decode(spec, p, codec.encode(spec, p, xx)))(x)
    np.testing.assert_allclose(np.asarray(jitted), np.asarray(eager),
                               atol=1e-6, rtol=1e-5)


# ------------------------------------------------------- batched ≡ per-client
@pytest.mark.parametrize("comp", _all_compressors(), ids=_ids())
def test_vmap_decode_over_client_axis(comp):
    """decode is vmap-compatible over a stacked client axis and agrees with
    the per-client loop."""
    spec, p = comp.spec(N), comp.codec_params()
    payloads = [codec.encode(spec, p, _flat(i, 1.0 + i)) for i in range(4)]
    stacked = codec.stack_payloads(payloads)
    got = jax.vmap(lambda pl: codec.decode(spec, p, pl))(stacked)
    want = jnp.stack([codec.decode(spec, p, pl) for pl in payloads])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("comp", _all_compressors(), ids=_ids())
def test_decode_batched_matches_per_client(comp):
    spec, p = comp.spec(N), comp.codec_params()
    payloads = [codec.encode(spec, p, _flat(i, 1.0 + i)) for i in range(5)]
    stacked = codec.stack_payloads(payloads)
    got = codec.decode_batched(spec, p, stacked)
    want = jnp.stack([codec.decode(spec, p, pl) for pl in payloads])
    assert got.shape == (5, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


# -------------------------------------------- fused ≡ decode + weighted_mean
@pytest.mark.parametrize("comp", _all_compressors(), ids=_ids())
@pytest.mark.parametrize("use_base", [False, True])
def test_decode_and_aggregate_matches_sequential(comp, use_base):
    """The one-call fused server path ≡ per-client decode then
    weighted_mean (the acceptance equivalence, ≤1e-5 rel)."""
    spec, p = comp.spec(N), comp.codec_params()
    weights = [512.0, 317.0, 100.0]
    payloads = [codec.encode(spec, p, _flat(i, 1.0 + i)) for i in range(3)]
    stacked = codec.stack_payloads(payloads)
    base = _flat(99, 0.5) if use_base else None
    nw = jnp.asarray(normalize_weights(weights), jnp.float32)
    got = codec.decode_and_aggregate(spec, p, stacked, nw, base)

    rows = [codec.decode(spec, p, pl) for pl in payloads]
    if base is not None:
        rows = [r - base for r in rows]
    want, = jax.tree_util.tree_leaves(
        weighted_mean([{"u": r} for r in rows], weights))
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * scale, rtol=1e-5)


@pytest.mark.parametrize("n_chunks", [5, 13, 135])
@pytest.mark.parametrize("use_base", [False, True])
def test_kernel_decode_and_aggregate_pads_latents_to_plan(n_chunks,
                                                          use_base):
    """Chunk counts that are not a multiple of 8: the kernel path pads the
    latents to the kernel plan's rows (5→8, 13→16, 135→144, as the CIFAR
    model's 135 chunks pad), decodes, and slices the padded rows off. It
    must equal the pure-jnp decode then weighted_mean."""
    from repro.kernels.fused_decode_agg import padded_rows
    n = (n_chunks - 1) * _CHUNK_CFG.chunk_size + 77      # ragged last chunk
    kspec = ChunkedAECompressor(_CHUNK_PARAMS, _CHUNK_CFG,
                                use_kernel=True).spec(n)
    jspec = ChunkedAECompressor(_CHUNK_PARAMS, _CHUNK_CFG,
                                use_kernel=False).spec(n)
    assert kspec.n_chunks == n_chunks
    assert padded_rows(3, n_chunks, _CHUNK_CFG.hidden[-1],
                       _CHUNK_CFG.chunk_size) > n_chunks
    weights = [512.0, 317.0, 100.0]
    flats = [jax.random.normal(jax.random.PRNGKey(i), (n,)) * (1.0 + i)
             for i in range(3)]
    payloads = [codec.encode(kspec, _CHUNK_PARAMS, f) for f in flats]
    base = (jax.random.normal(jax.random.PRNGKey(99), (n,)) * 0.5
            if use_base else None)
    nw = jnp.asarray(normalize_weights(weights), jnp.float32)
    got = codec.decode_and_aggregate(kspec, _CHUNK_PARAMS,
                                     codec.stack_payloads(payloads), nw, base)

    rows = [codec.decode(jspec, _CHUNK_PARAMS, pl) for pl in payloads]
    if base is not None:
        rows = [r - base for r in rows]
    want, = jax.tree_util.tree_leaves(
        weighted_mean([{"u": r} for r in rows], weights))
    assert got.shape == (n,)
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * scale, rtol=1e-5)


def test_decode_and_aggregate_per_client_params():
    """Per-client AE decoders ride a stacked params axis (params_batched)."""
    specs = [codec.FCAESpec(size=N, cfg=_FC_CFG)]
    params = [init_fc_ae(jax.random.PRNGKey(i), _FC_CFG) for i in range(3)]
    spec = specs[0]
    payloads = [codec.encode(spec, params[i], _flat(i)) for i in range(3)]
    stacked = codec.stack_payloads(payloads)
    stacked_params = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *params)
    nw = jnp.asarray(normalize_weights([1.0, 2.0, 3.0]), jnp.float32)
    got = codec.decode_and_aggregate(spec, stacked_params, stacked, nw,
                                     params_batched=True)
    want = jnp.einsum("c,cp->p", nw, jnp.stack(
        [codec.decode(spec, params[i], payloads[i]) for i in range(3)]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("comp", [QuantizeCompressor(bits=8, block=64),
                                  ChunkedAECompressor(_CHUNK_PARAMS,
                                                      _CHUNK_CFG,
                                                      use_kernel=True)],
                         ids=["quantize8", "chunked_ae_kernel"])
@pytest.mark.parametrize("cohort", [1, 5])                 # pad path: 1 dev
def test_decode_and_aggregate_sharded_matches_fused(comp, cohort):
    """shard_map client-axis variant (DESIGN.md §7.2) ≡ the fused call,
    including the zero-weight padding path when C % n_devices != 0."""
    spec, p = comp.spec(N), comp.codec_params()
    payloads = [codec.encode(spec, p, _flat(i, 1.0 + i))
                for i in range(cohort)]
    stacked = codec.stack_payloads(payloads)
    nw = jnp.asarray(normalize_weights([1.0 + i for i in range(cohort)]),
                     jnp.float32)
    fused = codec.decode_and_aggregate(spec, p, stacked, nw)
    sharded = codec.decode_and_aggregate_sharded(spec, p, stacked, nw)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(fused),
                               atol=2e-5, rtol=1e-4)


# ------------------------------------------------------ satellite contracts
def test_weighted_mean_stacked_normalizes_array_weights():
    """Same result whether weights arrive as a python list or a jax array
    (device-array weights must not silently skip normalization)."""
    from repro.core import weighted_mean_stacked
    stacked = {"a": jnp.stack([jnp.ones((3,)), 3.0 * jnp.ones((3,))])}
    from_list = weighted_mean_stacked(stacked, [2.0, 2.0])
    from_array = weighted_mean_stacked(stacked, jnp.array([2.0, 2.0]))
    np.testing.assert_allclose(np.asarray(from_list["a"]), 2.0)
    np.testing.assert_allclose(np.asarray(from_array["a"]), 2.0)
    # normalized=True trusts the caller (the fused server path contract)
    pre = weighted_mean_stacked(stacked, jnp.array([0.5, 0.5]),
                                normalized=True)
    np.testing.assert_allclose(np.asarray(pre["a"]), 2.0)


def test_dequantize_blocks_requires_orig_len():
    """orig_len is mandatory: the padded-tail default was a silent-corruption
    footgun (a forgotten slice returned block-padded garbage)."""
    from repro.kernels import ops
    q, s, orig = ops.quantize_blocks(_flat(0), bits=8, block=256)
    with pytest.raises(TypeError):
        ops.dequantize_blocks(q, s, bits=8, block=256)   # no orig_len
    with pytest.raises(ValueError):
        ops.dequantize_blocks(q, s, bits=8, block=256, orig_len=0)
    back = ops.dequantize_blocks(q, s, bits=8, block=256, orig_len=orig)
    assert back.shape == (N,)


def test_use_kernel_autoselects_from_backend(monkeypatch):
    """Kernel dispatch: backend auto-detection with env override — TPU runs
    must not silently take the pure-jnp path (and vice versa on CPU)."""
    from repro.kernels import ops
    monkeypatch.delenv("REPRO_USE_KERNEL", raising=False)
    assert ops.use_kernel_default() == (jax.default_backend() == "tpu")
    monkeypatch.setenv("REPRO_USE_KERNEL", "1")
    assert ops.use_kernel_default() is True
    monkeypatch.setenv("REPRO_USE_KERNEL", "0")
    assert ops.use_kernel_default() is False
    # explicit compressor field wins over everything
    assert ops.use_kernel_default(True) is True
    comp = ChunkedAECompressor(_CHUNK_PARAMS, _CHUNK_CFG, use_kernel=True)
    assert comp.spec(N).use_kernel is True
    monkeypatch.delenv("REPRO_USE_KERNEL", raising=False)
    auto = ChunkedAECompressor(_CHUNK_PARAMS, _CHUNK_CFG)
    assert auto.spec(N).use_kernel == (jax.default_backend() == "tpu")


def test_scheduler_round_uses_single_fused_call(monkeypatch):
    """The acceptance property: a scheduler round makes exactly ONE
    decode_and_aggregate call regardless of cohort size (no per-client
    decode dispatch in the round loop; error feedback is off here)."""
    from repro.configs.paper import MNIST_CLASSIFIER
    from repro.core import FLConfig, FederatedRun, SyncFedAvg
    from repro.core import scheduler as sched_mod
    from repro.data.pipeline import mnist_like, train_eval_split, \
        uniform_partition
    train, ev = train_eval_split(mnist_like(0, 256), 64)
    data = uniform_partition(0, train, 3)
    calls = {"fused": 0, "decode": 0}
    real_fused = codec.decode_and_aggregate
    real_decode = codec.decode
    monkeypatch.setattr(
        sched_mod.codec, "decode_and_aggregate",
        lambda *a, **k: (calls.__setitem__("fused", calls["fused"] + 1),
                         real_fused(*a, **k))[1])
    monkeypatch.setattr(
        sched_mod.codec, "decode",
        lambda *a, **k: (calls.__setitem__("decode", calls["decode"] + 1),
                         real_decode(*a, **k))[1])
    run = FederatedRun(MNIST_CLASSIFIER, data,
                       FLConfig(n_rounds=2, local_epochs=1,
                                payload="update"),
                       compressors=[QuantizeCompressor(bits=8)
                                    for _ in range(3)],
                       eval_data=ev, scheduler=SyncFedAvg())
    run.run()
    assert calls["fused"] == 2           # one per round
    assert calls["decode"] == 0          # zero per-client server decodes


# ------------------------------------------------------------ property tests
@hypothesis.given(st.integers(10, 3000), st.integers(0, 10 ** 6))
def test_property_quantize_codec_jit_roundtrip(n, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed % 2 ** 31), (n,)) * 2.0
    spec = codec.QuantizeSpec(size=n, bits=8, block=128)
    out = jax.jit(
        lambda xx: codec.decode(spec, None,
                                codec.encode(spec, None, xx)))(x)
    assert out.shape == x.shape
    assert float(jnp.max(jnp.abs(out - x))) <= \
        float(jnp.max(jnp.abs(x))) / 127 + 1e-6


@hypothesis.given(st.integers(2, 6), st.integers(0, 10 ** 6))
def test_property_fused_agg_equals_sequential_any_cohort(c, seed):
    spec = codec.QuantizeSpec(size=N, bits=8, block=64)
    payloads = [codec.encode(spec, None, _flat(seed % 2 ** 30 + i))
                for i in range(c)]
    stacked = codec.stack_payloads(payloads)
    nw = jnp.asarray(normalize_weights([1.0] * c), jnp.float32)
    got = codec.decode_and_aggregate(spec, None, stacked, nw)
    want = jnp.mean(jnp.stack([codec.decode(spec, None, pl)
                               for pl in payloads]), axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@hypothesis.given(st.integers(1, 4000))
def test_property_chunked_spec_n_chunks(n):
    spec = codec.ChunkedAESpec(size=n, cfg=_CHUNK_CFG)
    assert spec.n_chunks == -(-n // _CHUNK_CFG.chunk_size)
