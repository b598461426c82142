"""Grouped ragged decode→aggregate launch (DESIGN.md §11): differential
tests of the one-sweep Pallas kernel against the per-bucket kernel and the
pure-jnp oracle, the one-dispatch grouped server round against the
sequential bucket loop and the per-client decode oracle, flag resolution,
end-to-end run equivalence, and a property test (client permutation /
bucket packing order invariance) via hypothesis with the stub fallback."""
try:
    import hypothesis
    import hypothesis.strategies as st
except ModuleNotFoundError:       # dev extra absent: property tests skip
    from _hypothesis_stub import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper import MNIST_CLASSIFIER
from repro.core import (ChunkedAECompressor, ChunkedAEConfig, FLConfig,
                        FederatedRun, QuantizeCompressor, codec,
                        init_chunked_ae, normalize_weights, partition)
from repro.core.scheduler import EncodedUpdate
from repro.kernels import ops
from repro.kernels.fused_decode_agg import (fused_decode_agg,
                                            grouped_fused_decode_agg,
                                            padded_rows)
from repro.kernels.ref import grouped_fused_decode_agg_ref
from repro.data.pipeline import (mnist_like, train_eval_split,
                                 uniform_partition)

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=10,
    suppress_health_check=list(hypothesis.HealthCheck))
hypothesis.settings.load_profile("ci")


# ----------------------------------------------------------- kernel level
def _mk_buckets(seed: int, cohort: int, rungs: int, K: int = 8, N: int = 32):
    """Split a ``cohort`` across ``rungs`` buckets of ragged (C, M) shapes;
    cohort < rungs leaves trailing buckets EMPTY (zero clients) on purpose.
    Per-bucket weights are renormalized to Σ=1 (the kernel's contract)."""
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 2 + 2 * rungs)
    D = rungs
    w_stack = 0.1 * jax.random.normal(keys[0], (D, K, N), jnp.float32)
    b_stack = 0.1 * jax.random.normal(keys[1], (D, N), jnp.float32)
    sizes = [cohort // rungs + (1 if r < cohort % rungs else 0)
             for r in range(rungs)]
    Ms = [16, 24, 8, 40]
    hs, ws, dec_idx = [], [], []
    for r, C_b in enumerate(sizes):
        M = Ms[r % len(Ms)]
        hs.append(jax.random.normal(keys[2 + r], (C_b, M, K), jnp.float32))
        raw = jax.random.uniform(keys[2 + rungs + r], (C_b,)) + 0.1
        ws.append((raw / raw.sum() if C_b else raw).astype(jnp.float32))
        dec_idx.append(r)
    return hs, ws, w_stack, b_stack, dec_idx


def _primitives(jaxpr):
    """Names of every primitive in ``jaxpr`` and the jaxprs nested in it."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                out += _primitives(inner)
    return out


@pytest.mark.parametrize("M", [1, 7, 8, 135, 136, 144])
@pytest.mark.parametrize("C", [1, 9, 64, 1024])
def test_padded_rows_matches_launch_plan(C, M):
    """``padded_rows`` is the row count the launch pads a bucket to, at the
    production chunked AE's widths (K=512, N=4096); it is a fixed point,
    and an ``h`` already at those rows enters the launch with no pad op."""
    K, N = 512, 4096
    Mp = padded_rows(C, M, K, N)
    assert Mp >= M and Mp % 8 == 0
    assert padded_rows(C, Mp, K, N) == Mp

    def launch(m):
        h = jax.ShapeDtypeStruct((C, m, K), jnp.float32)
        w = jax.ShapeDtypeStruct((C,), jnp.float32)
        wl = jax.ShapeDtypeStruct((K, N), jnp.float32)
        bl = jax.ShapeDtypeStruct((N,), jnp.float32)
        return jax.make_jaxpr(
            lambda *a: grouped_fused_decode_agg(
                [a[0]], [a[1]], a[2][None], a[3][None], [0]))(h, w, wl, bl)

    jaxpr = launch(M).jaxpr
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.outvars[0].aval.shape == (Mp, N)
    assert ("pad" in _primitives(jaxpr)) == (Mp != M)
    assert "pad" not in _primitives(launch(Mp).jaxpr)


@pytest.mark.parametrize("cohort", [1, 8, 64])
@pytest.mark.parametrize("rungs", [1, 2, 4])
def test_grouped_kernel_vs_oracle_and_per_bucket(cohort, rungs):
    hs, ws, w_stack, b_stack, dec_idx = _mk_buckets(
        cohort * 10 + rungs, cohort, rungs)
    got = grouped_fused_decode_agg(hs, ws, w_stack, b_stack, dec_idx,
                                   bc=16, interpret=True)
    want = grouped_fused_decode_agg_ref(hs, ws, w_stack, b_stack, dec_idx)
    assert len(got) == len(hs)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=1e-4)
    # vs the per-bucket sequential kernel at the same client-block size:
    # the grouped launch's extra zero-weight padding contributes exact
    # zeros, so the accumulation is BIT-identical (the 1-ulp rule)
    for h, w, d, g in zip(hs, ws, dec_idx, got):
        if h.shape[0] == 0:
            assert not np.asarray(g).any()
            continue
        per = fused_decode_agg(h, w, w_stack[d], b_stack[d], bc=16,
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(per))


def test_grouped_kernel_single_client_bucket_and_dedup():
    # one single-client bucket + two buckets sharing one decoder slot
    key = jax.random.PRNGKey(3)
    k = jax.random.split(key, 6)
    K, N = 8, 32
    w_stack = 0.1 * jax.random.normal(k[0], (2, K, N), jnp.float32)
    b_stack = 0.1 * jax.random.normal(k[1], (2, N), jnp.float32)
    hs = [jax.random.normal(k[2], (1, 16, K), jnp.float32),
          jax.random.normal(k[3], (5, 24, K), jnp.float32),
          jax.random.normal(k[4], (3, 24, K), jnp.float32)]
    ws = [jnp.ones((1,), jnp.float32),
          jnp.full((5,), 0.2, jnp.float32),
          jnp.asarray([0.5, 0.25, 0.25], jnp.float32)]
    dec_idx = [0, 1, 1]                     # buckets 1 and 2 share slot 1
    got = grouped_fused_decode_agg(hs, ws, w_stack, b_stack, dec_idx,
                                   interpret=True)
    want = grouped_fused_decode_agg_ref(hs, ws, w_stack, b_stack, dec_idx)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=1e-4)


def test_grouped_kernel_all_empty_returns_zeros():
    w_stack = jnp.ones((1, 4, 8), jnp.float32)
    b_stack = jnp.ones((1, 8), jnp.float32)
    out = grouped_fused_decode_agg(
        [jnp.zeros((0, 16, 4), jnp.float32)], [jnp.zeros((0,))],
        w_stack, b_stack, [0], interpret=True)
    assert out[0].shape == (16, 8) and not np.asarray(out[0]).any()


def test_grouped_kernel_under_jit():
    hs, ws, w_stack, b_stack, dec_idx = _mk_buckets(11, 6, 2)

    @jax.jit
    def run(hs_, ws_, wst, bst):
        return grouped_fused_decode_agg(list(hs_), list(ws_), wst, bst,
                                        dec_idx, interpret=True)

    got = run(tuple(hs), tuple(ws), w_stack, b_stack)
    want = grouped_fused_decode_agg_ref(hs, ws, w_stack, b_stack, dec_idx)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=1e-4)


# ------------------------------------------------------------ server level
SIZE = 1280
PMAP = partition.PartitionMap(groups=(("bulk", ((0, 768),)),
                                      ("head", ((768, 512),))))
CFG_HI = ChunkedAEConfig(chunk_size=128, hidden=(16,), latent_chunk=8)
CFG_LO = ChunkedAEConfig(chunk_size=128, hidden=(16,), latent_chunk=4)
PRM_HI = init_chunked_ae(jax.random.PRNGKey(20), CFG_HI)
PRM_LO = init_chunked_ae(jax.random.PRNGKey(21), CFG_LO)
SPEC_HI = partition.make_partition_spec(PMAP, {
    "bulk": codec.ChunkedAESpec(size=768, cfg=CFG_HI, use_kernel=True),
    "head": codec.QuantizeSpec(size=512, bits=8)})
SPEC_LO = partition.make_partition_spec(PMAP, {
    "bulk": codec.ChunkedAESpec(size=768, cfg=CFG_LO, use_kernel=True),
    "head": codec.QuantizeSpec(size=512, bits=4)})


def _mixed_cohort(n: int):
    rng = np.random.default_rng(5)
    encs, weights = [], []
    for i in range(n):
        flat = jnp.asarray(rng.normal(size=SIZE), jnp.float32)
        sp = SPEC_HI if i % 3 else SPEC_LO
        prm = {"bulk": PRM_HI if i % 3 else PRM_LO, "head": None}
        encs.append(EncodedUpdate(payload=codec.encode(sp, prm, flat),
                                  spec=sp, params=prm, weight=1.0 + i,
                                  stats={}, metrics={}))
        weights.append(1.0 + i)
    return encs, normalize_weights(weights)


@pytest.mark.parametrize("with_base", [False, True])
def test_grouped_server_round_matches_sequential_and_per_client(with_base):
    encs, nw = _mixed_cohort(7)
    base = (jnp.asarray(np.random.default_rng(9).normal(size=SIZE),
                        jnp.float32) if with_base else None)
    seq = partition.server_decode_aggregate(encs, nw, base,
                                            use_grouped_kernel=False)
    grp = partition.server_decode_aggregate(encs, nw, base,
                                            use_grouped_kernel=True)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(grp),
                               atol=1e-5, rtol=1e-4)
    rows = jnp.stack([codec.decode(e.spec, e.params, e.payload)
                      for e in encs])
    if base is not None:
        rows = rows - base[None, :]
    oracle = jnp.einsum("c,cp->p", jnp.asarray(nw, jnp.float32), rows)
    np.testing.assert_allclose(np.asarray(grp), np.asarray(oracle),
                               atol=1e-5, rtol=1e-4)


def test_grouped_server_round_homogeneous_is_bit_stable():
    # single bucket per group ⇒ the grouped round reduces with the full
    # cohort weights — identical math to the sequential single-bucket path
    rng = np.random.default_rng(6)
    encs, weights = [], []
    for i in range(5):
        flat = jnp.asarray(rng.normal(size=SIZE), jnp.float32)
        prm = {"bulk": PRM_HI, "head": None}
        encs.append(EncodedUpdate(payload=codec.encode(SPEC_HI, prm, flat),
                                  spec=SPEC_HI, params=prm, weight=1.0,
                                  stats={}, metrics={}))
        weights.append(1.0)
    nw = normalize_weights(weights)
    seq = partition.server_decode_aggregate(encs, nw, None,
                                            use_grouped_kernel=False)
    grp = partition.server_decode_aggregate(encs, nw, None,
                                            use_grouped_kernel=True)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(grp),
                               atol=2e-6, rtol=1e-5)


def test_grouped_flat_server_aggregate_matches_oracle():
    rng = np.random.default_rng(7)
    specs = [codec.ChunkedAESpec(size=768, cfg=CFG_HI, use_kernel=True),
             codec.ChunkedAESpec(size=768, cfg=CFG_LO, use_kernel=True),
             codec.QuantizeSpec(size=768, bits=8)]
    prms = [PRM_HI, PRM_LO, None]
    encs = []
    for i in range(9):
        flat = jnp.asarray(rng.normal(size=768), jnp.float32)
        sp, prm = specs[i % 3], prms[i % 3]
        encs.append(EncodedUpdate(payload=codec.encode(sp, prm, flat),
                                  spec=sp, params=prm, weight=2.0 + i,
                                  stats={}, metrics={}))
    nw = normalize_weights([2.0 + i for i in range(9)])
    grp = partition.grouped_flat_server_aggregate(encs, nw, None)
    rows = jnp.stack([codec.decode(e.spec, e.params, e.payload)
                      for e in encs])
    oracle = jnp.einsum("c,cp->p", jnp.asarray(nw, jnp.float32), rows)
    np.testing.assert_allclose(np.asarray(grp), np.asarray(oracle),
                               atol=1e-5, rtol=1e-4)


# ----------------------------------------------------------- flag plumbing
def test_use_grouped_default_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_GROUPED_KERNEL", raising=False)
    assert ops.use_grouped_default() is False          # off by default
    assert ops.use_grouped_default(True) is True
    assert ops.use_grouped_default(False) is False
    monkeypatch.setenv("REPRO_GROUPED_KERNEL", "1")
    assert ops.use_grouped_default() is True
    assert ops.use_grouped_default(False) is False     # override wins
    monkeypatch.setenv("REPRO_GROUPED_KERNEL", "0")
    assert ops.use_grouped_default() is False
    assert ops.use_grouped_default(True) is True


# ------------------------------------------------------------- end to end
def test_end_to_end_run_grouped_matches_sequential():
    data, ev = train_eval_split(mnist_like(0, 192), 48)
    shards = uniform_partition(0, data, 4)
    cfg_ae = ChunkedAEConfig(chunk_size=64, hidden=(8,), latent_chunk=4)
    prm = init_chunked_ae(jax.random.PRNGKey(2), cfg_ae)

    def mk(grouped):
        comps = [ChunkedAECompressor(prm, cfg_ae, use_kernel=True),
                 ChunkedAECompressor(prm, cfg_ae, use_kernel=True),
                 QuantizeCompressor(bits=8),
                 QuantizeCompressor(bits=4)]
        cfg = FLConfig(n_rounds=2, local_epochs=1, payload="update",
                       use_grouped_kernel=grouped)
        return FederatedRun(MNIST_CLASSIFIER, shards, cfg,
                            compressors=comps, eval_data=ev)

    recs_seq = mk(False).run()
    recs_grp = mk(True).run()
    for a, b in zip(recs_seq, recs_grp):
        assert a.global_metrics.keys() == b.global_metrics.keys()
        for key in a.global_metrics:
            np.testing.assert_allclose(a.global_metrics[key],
                                       b.global_metrics[key],
                                       atol=1e-4, rtol=1e-3)
        np.testing.assert_allclose(a.bytes_up, b.bytes_up)


# ------------------------------------------------------------ property test
@hypothesis.given(st.integers(0, 2 ** 31 - 1), st.integers(2, 8))
def test_grouped_aggregate_invariant_to_client_permutation(seed, n_clients):
    """Permuting the cohort permutes bucket discovery order AND the packing
    order of buckets into the grouped launch — the aggregate must not
    move beyond float-add reassociation noise."""
    rng = np.random.default_rng(seed)
    encs, weights = [], []
    for i in range(n_clients):
        flat = jnp.asarray(rng.normal(size=SIZE), jnp.float32)
        sp = (SPEC_HI, SPEC_LO)[rng.integers(2)]
        prm = {"bulk": PRM_HI if sp is SPEC_HI else PRM_LO, "head": None}
        encs.append(EncodedUpdate(payload=codec.encode(sp, prm, flat),
                                  spec=sp, params=prm,
                                  weight=float(rng.uniform(0.5, 2.0)),
                                  stats={}, metrics={}))
        weights.append(encs[-1].weight)
    nw = normalize_weights(weights)
    ref = partition.server_decode_aggregate(encs, nw, None,
                                            use_grouped_kernel=True)
    perm = rng.permutation(n_clients)
    got = partition.server_decode_aggregate(
        [encs[i] for i in perm], [nw[i] for i in perm], None,
        use_grouped_kernel=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-4)
