"""Serving-pipeline invariants (DESIGN.md §12.3).

The serve step is one donated jitted computation; these tests pin the
properties the throughput numbers rely on: FedBuff bookkeeping invariants
hold round over round (clock monotone, version increments, exactly one
in-flight dispatch per client), synthetic payloads have exactly the
encode-shape structure, donation actually recycles buffers, and the step
is deterministic (same config ⇒ same trajectory)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper import AEConfig
from repro.core import codec, init_chunked_ae, init_fc_ae
from repro.core.autoencoder import ChunkedAEConfig
from repro.core.serve import (SCOPES, ServeConfig, init_state, make_step,
                              round_bytes, run_serve, synthetic_payloads)

Q8 = codec.QuantizeSpec(size=512, bits=8, block=128)


def _cfg(**kw):
    base = dict(n_clients=64, buffer_k=8, spec=Q8, jitter=0.4,
                straggler_frac=0.1, seed=1)
    base.update(kw)
    return ServeConfig(**base)


def test_step_invariants_over_rounds():
    cfg = _cfg()
    step = make_step(cfg)
    state = init_state(cfg)
    prev_clock = -1.0
    for r in range(6):
        state = step(state)
        # version increments once per ingest round
        assert int(state["version"]) == r + 1
        # clock is monotone and equals the max popped arrival so far
        clock = float(state["clock"])
        assert clock >= prev_clock
        prev_clock = clock
        # every client has exactly one in-flight dispatch: all times
        # finite, all seqs distinct, next_seq advanced by k per round
        times = np.asarray(state["times"])
        assert np.all(np.isfinite(times))
        seqs = np.asarray(state["seqs"])
        assert len(np.unique(seqs)) == cfg.n_clients
        assert int(state["next_seq"]) == cfg.n_clients + (r + 1) * cfg.buffer_k
        # re-dispatched clients arrive after the clock
        assert np.all(times[seqs >= int(state["next_seq"]) - cfg.buffer_k]
                      >= clock)
        # client versions never exceed the global version
        assert np.asarray(state["versions"]).max() <= int(state["version"])


@pytest.mark.parametrize("spec", [
    Q8,
    codec.IdentitySpec(size=256),
    codec.TopKSpec(size=1024, k=64),
])
def test_synthetic_payloads_match_encode_structure(spec):
    """Payloads must be drop-in for real encoded cohorts: same treedef,
    per-leaf shapes = (k, *encode_shape), same dtypes — so the fused
    decode path compiles and prices identically."""
    k = 4
    want = jax.eval_shape(lambda f: codec.encode(spec, None, f),
                          jax.ShapeDtypeStruct((spec.size,), jnp.float32))
    got = synthetic_payloads(spec, None, k, jax.random.PRNGKey(0))
    w_leaves, w_def = jax.tree_util.tree_flatten(want)
    g_leaves, g_def = jax.tree_util.tree_flatten(got)
    assert w_def == g_def
    for w, g in zip(w_leaves, g_leaves):
        assert g.shape == (k, *w.shape)
        assert g.dtype == w.dtype
    # and the real decode consumes them without retracing errors
    rows = codec.decode_batched(spec, None, got)
    assert rows.shape == (k, spec.size)


def test_step_deterministic():
    cfg = _cfg()
    sa = init_state(cfg)
    sb = init_state(cfg)
    step_a, step_b = make_step(cfg), make_step(cfg)
    for _ in range(4):
        sa, sb = step_a(sa), step_b(sb)
    np.testing.assert_array_equal(np.asarray(sa["global_flat"]),
                                  np.asarray(sb["global_flat"]))
    np.testing.assert_array_equal(np.asarray(sa["times"]),
                                  np.asarray(sb["times"]))


def test_donation_consumes_input_state():
    """donate_argnums=0 really donates: the passed-in state's buffers are
    invalidated after the call (the double-buffering contract)."""
    cfg = _cfg(n_clients=32, buffer_k=4)
    step = make_step(cfg)
    state = init_state(cfg)
    out = step(state)
    assert state["global_flat"].is_deleted()
    # the returned generation is live and usable
    out2 = step(out)
    assert not out2["global_flat"].is_deleted()


def test_run_serve_report_and_bytes():
    cfg = _cfg(n_clients=128, buffer_k=16)
    state, report = run_serve(cfg, n_rounds=3, warmup=1)
    # 1 warmup + 3 timed rounds
    assert int(state["version"]) == 4
    assert report["rounds_per_sec"] > 0
    assert report["round_bytes"] == round_bytes(cfg)
    assert report["bytes_per_sec"] == pytest.approx(
        report["rounds_per_sec"] * report["round_bytes"])
    assert report["sim_time"] > 0


def test_global_flat_seed_passthrough():
    """A caller-provided flat model seeds the loop (the examples path)."""
    cfg = _cfg(n_clients=32, buffer_k=4)
    g0 = jnp.full(Q8.size, 2.0)
    state = init_state(cfg, global_flat=g0)
    np.testing.assert_array_equal(np.asarray(state["global_flat"]),
                                  np.asarray(g0))


@pytest.mark.parametrize("shard", [False, True])
def test_step_compiles_once(shard):
    """The state ``init_state`` places has the shardings the step returns,
    so every round after the first reuses one executable — the timed loop
    of ``run_serve`` (after one warmup round) never compiles."""
    cfg = _cfg(n_clients=32, buffer_k=8, shard=shard)
    step = make_step(cfg)
    state = init_state(cfg)
    for _ in range(3):
        state = step(state)
    assert step._cache_size() == 1


def test_shard_single_device_matches_unsharded():
    """shard=True agrees with the plain fused path up to reduction-order
    float drift (the sharded path sums weighted rows via einsum + psum)."""
    if jax.device_count() != 1:
        pytest.skip("tolerance calibrated for the 1-device mesh")
    cfg_p = _cfg(n_clients=32, buffer_k=8, shard=False)
    cfg_s = _cfg(n_clients=32, buffer_k=8, shard=True)
    sa, sb = init_state(cfg_p), init_state(cfg_s)
    step_p, step_s = make_step(cfg_p), make_step(cfg_s)
    for _ in range(3):
        sa, sb = step_p(sa), step_s(sb)
    np.testing.assert_allclose(np.asarray(sa["global_flat"]),
                               np.asarray(sb["global_flat"]),
                               rtol=1e-4, atol=1e-4)


# ``  ROOT %name = <type> opcode(...)``: the non-greedy type stops at the
# first `` opcode(``, which tuple types never hold
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) ")
# computations the device runs instruction by instruction; a fusion's,
# a sort's comparator or a reduction's body run inside their caller
_RUN_BY = re.compile(r"(?:body|condition|true_computation|false_computation"
                     r")=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}

_CHUNK = ChunkedAEConfig(chunk_size=128, hidden=(32,), latent_chunk=4)
_CHUNK_AE = codec.ChunkedAESpec(size=1250, cfg=_CHUNK, use_kernel=False)
_FC = AEConfig(input_dim=2048, encoder_hidden=(64,), latent_dim=16)


def _device_instructions(text):
    """``(opcode, op_name or None)`` of every instruction in the computations
    the device runs one instruction at a time: ENTRY, and the bodies and
    branches of its control flow."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = _COMPUTATION.match(line)
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
        elif cur is not None and _INSTRUCTION.match(line):
            comps[cur].append(line)
    runs, todo = {entry}, [entry]
    while todo:
        for line in comps[todo.pop()]:
            names = _RUN_BY.findall(line)
            b = _BRANCHES.search(line)
            if b:
                names += [n.strip().lstrip("%") for n in b.group(1).split(",")]
            for n in names:
                if n not in runs:
                    runs.add(n)
                    todo.append(n)
    out = []
    for c in runs:
        for line in comps[c]:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((_INSTRUCTION.match(line).group(2),
                        op.group(1) if op else None))
    return out


@pytest.mark.parametrize("case", ["chunked_ae_q8", "fc_ae", "topk_chain",
                                  "q8_shard"])
def test_step_ops_lie_under_one_scope(case):
    """Every op the compiled step runs lies under exactly one of the four
    layer scopes, so a trace's device time groups by layer. The copies that
    XLA's copy insertion adds (loop state, donated buffers) carry no
    metadata at all: they are the compiler's, not the program's."""
    spec, params, shard = {
        "chunked_ae_q8": (codec.ChainSpec((_CHUNK_AE, codec.QuantizeSpec(
            size=_CHUNK_AE.n_chunks * 4, bits=8, block=8))),
            (init_chunked_ae(jax.random.PRNGKey(0), _CHUNK), None), False),
        "fc_ae": (codec.FCAESpec(size=1250, cfg=_FC),
                  init_fc_ae(jax.random.PRNGKey(0), _FC), False),
        "topk_chain": (codec.ChainSpec((codec.TopKSpec(size=1250, k=128),
                                        codec.QuantizeSpec(size=128, bits=8,
                                                           block=64))),
                       None, False),
        "q8_shard": (Q8, None, True),
    }[case]
    cfg = _cfg(n_clients=32, buffer_k=8, spec=spec, shard=shard)
    text = make_step(cfg, params).lower(
        init_state(cfg, params)).compile().as_text()
    seen, stray = set(), []
    for opcode, op_name in _device_instructions(text):
        if opcode in _NO_WORK or (opcode == "copy" and op_name is None):
            continue
        hits = [p for p in (op_name or "").split("/") if p in SCOPES]
        if len(hits) != 1:
            stray.append((opcode, op_name))
        seen.update(hits)
    assert not stray, stray[:10]
    assert seen == set(SCOPES)
