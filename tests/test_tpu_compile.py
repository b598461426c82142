"""Compile the main path's Pallas kernels for a described TPU v5e, at the
shapes the federated round runs them at (the paper's CIFAR model, 550,586
parameters = 135 chunks of the default 4096-wide chunked AE). Nothing runs:
the TPU compiler refuses what the chip would refuse — VMEM overflow,
blocks that break the (8, 128) tiling — which interpret mode cannot see.

Every chip-compile test lives in this one file: the v5e topology is
described by a module fixture (never at import time), so under several
test workers only the worker that runs this file loads the TPU compiler.
The persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_decode_agg import (fused_decode_agg,
                                            grouped_fused_decode_agg)
from repro.kernels.fused_dense import fused_dense
from repro.kernels.quantize import dequantize_blocks_2d, quantize_blocks_2d

N_CHUNKS = 135          # ceil(550_586 / 4096): CIFAR model, default AE
K, N = 512, 4096        # ChunkedAEConfig(): hidden 512, chunk 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # libtpu otherwise writes its logs under the system temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                             # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("M,Kin,Nout,bm,act", [
    (N_CHUNKS, 4096, 512, 128, "relu"),      # encoder: chunk → hidden
    (N_CHUNKS, 512, 8, 128, "relu"),         # encoder: hidden → latent
    (16 * N_CHUNKS, 8, 512, 512, "relu"),    # server hidden stack, C=16
    (N_CHUNKS, 512, 4096, 128, "linear"),    # client decode: hidden → chunk
])
def test_fused_dense_compiles_for_v5e(one_chip, M, Kin, Nout, bm, act):
    text = _compiled_text(
        lambda x, w, b: fused_dense(x, w, b, act=act, bm=bm),
        _spec(one_chip, (M, Kin)), _spec(one_chip, (Kin, Nout)),
        _spec(one_chip, (Nout,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("C", [8, 64, 4096])
def test_fused_decode_agg_compiles_for_v5e(one_chip, C):
    """The production chunked AE (K=512, N=4096) — refused for VMEM before
    the tile plan — for cohorts from 8 to 4096."""
    text = _compiled_text(
        fused_decode_agg, _spec(one_chip, (C, N_CHUNKS, K)),
        _spec(one_chip, (C,)), _spec(one_chip, (K, N)),
        _spec(one_chip, (N,)))
    assert "tpu_custom_call" in text


def test_grouped_fused_decode_agg_compiles_for_v5e(one_chip):
    """Two chunked-AE rungs with one (K, N) signature, ragged cohorts —
    refused for its (1, bc) weight block before the (B, Cp, 1) layout."""
    def launch(h1, h2, w1, w2, w_stack, b_stack):
        return grouped_fused_decode_agg([h1, h2], [w1, w2], w_stack,
                                        b_stack, [0, 1])

    text = _compiled_text(
        launch, _spec(one_chip, (9, N_CHUNKS, K)),
        _spec(one_chip, (7, N_CHUNKS, K)), _spec(one_chip, (9,)),
        _spec(one_chip, (7,)), _spec(one_chip, (2, K, N)),
        _spec(one_chip, (2, N)))
    assert "tpu_custom_call" in text


def _entry_instructions(text: str):
    """``(name, f32 dims or None, opcode, operand names)`` of each
    instruction of the entry computation of compiled HLO text."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    out = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\(([^)]*)\)",
                     line)
        if m is None:
            continue
        name, ty, op, args = m.groups()
        dims = re.fullmatch(r"f32\[([\d,]+)\]\S*", ty)
        out.append((name,
                    None if dims is None
                    else tuple(int(d) for d in dims.group(1).split(",")),
                    op, re.findall(r"%([\w.-]+)", args)))
    return out


def test_decode_and_aggregate_writes_hidden_once_for_v5e(one_chip,
                                                         monkeypatch):
    """The kernel-path decode→aggregate at the k1024 cohort (C=1024, the
    CIFAR model's 135 chunks, hidden 512, chunk 4096, q8 latents): the
    cohort's hidden activations are produced once, by ``fused_dense``,
    already in the padded layout ``fused_decode_agg`` reads. No pad, copy
    or relayout of them (f32 ``[C, r, K]`` or ``[C·r, K]``) lies between."""
    from repro.core import codec
    from repro.core.autoencoder import ChunkedAEConfig, init_chunked_ae
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    C, size = 1024, 550_586
    cfg = ChunkedAEConfig(chunk_size=N, hidden=(K,), latent_chunk=8)
    ae = codec.ChunkedAESpec(size=size, cfg=cfg, use_kernel=True)
    assert ae.n_chunks == N_CHUNKS
    spec = codec.ChainSpec((ae, codec.QuantizeSpec(
        size=N_CHUNKS * cfg.latent_chunk, bits=8, block=64)))
    params = (jax.eval_shape(
        lambda: init_chunked_ae(jax.random.PRNGKey(0), cfg)), None)
    one = jax.eval_shape(lambda p, x: codec.encode(spec, p, x), params,
                         jax.ShapeDtypeStruct((size,), jnp.float32))
    stacked = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, (C,) + a.shape, a.dtype), one)
    text = _compiled_text(
        lambda p, s, w: codec.decode_and_aggregate(spec, p, s, w),
        jax.tree_util.tree_map(
            lambda a: _spec(one_chip, a.shape, a.dtype), params),
        stacked, _spec(one_chip, (C,)))

    instrs = _entry_instructions(text)
    hidden = {name: op for name, dims, op, _ in instrs
              if dims is not None and dims[-1] == K and (
                  (len(dims) == 3 and dims[0] == C)
                  or (len(dims) == 2 and dims[0] % C == 0))}
    made = sorted(n for n, op in hidden.items() if op != "bitcast")
    assert len(made) == 1 and made[0].startswith("fused_dense"), hidden
    assert hidden[made[0]] == "custom-call"
    readers = {name for name, _, op, args in instrs
               if op != "bitcast" and any(a in hidden for a in args)}
    assert len(readers) == 1 and next(iter(readers)).startswith(
        "fused_decode_agg"), readers


@pytest.mark.parametrize("nb,block,bits", [
    ((1 << 20) // 256, 256, 8),      # 1M elements, 256-blocks
    ((1 << 20) // 256, 256, 4),
    (17, 64, 8),                     # q8 over one client's AE latents
])
def test_quantize_blocks_compile_for_v5e(one_chip, nb, block, bits):
    q_text = _compiled_text(
        lambda x: quantize_blocks_2d(x, bits=bits, block=block),
        _spec(one_chip, (nb, block)))
    d_text = _compiled_text(
        lambda q, s: dequantize_blocks_2d(q, s, block=block),
        _spec(one_chip, (nb, block), jnp.int8), _spec(one_chip, (nb,)))
    assert "tpu_custom_call" in q_text and "tpu_custom_call" in d_text
