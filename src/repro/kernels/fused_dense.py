"""Pallas TPU kernel: fused dense layer ``act(x @ w + b)``.

This is the compute hot-spot of the paper's technique at datacenter scale:
the chunked-AE encode/decode is a batch-of-chunks matmul chain
(``(n_chunks, chunk) @ (chunk, hidden) @ (hidden, latent)``), executed every
communication round over the full flattened update. Fusing bias+activation
into the matmul epilogue keeps each output tile in VMEM for exactly one
HBM round-trip.

Tiling: grid over (M/bm, N/bn) output tiles; each step streams an
(bm, K) row-band of x and a (K, bn) column-band of w into VMEM and drives the
MXU with a single ``jnp.dot``. bm/bn default to 128 — the MXU systolic array
edge — and K (chunk size ≤ 4096) stays resident. The pipeline double-buffers
every block, so one step holds 2·(bm·K + K·bn + bn + bm·bn) f32 values:
≈ 8.1 MiB at K=4096, inside v5e's 16 MiB default scoped-VMEM limit
(compiled for v5e at the chunked AE's shapes in tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _apply_act(y: jax.Array, act: str) -> jax.Array:
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "tanh":
        return jnp.tanh(y)
    if act == "sigmoid":
        return jax.nn.sigmoid(y)
    if act == "linear":
        return y
    raise ValueError(f"unsupported activation {act}")


def _fused_dense_kernel(x_ref, w_ref, b_ref, o_ref, *, act: str):
    x = x_ref[...]                      # (bm, K)
    w = w_ref[...]                      # (K, bn)
    b = b_ref[...]                      # (1, bn)
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    y = y + b.astype(jnp.float32)
    o_ref[...] = _apply_act(y, act).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("act", "bm", "bn", "interpret"))
def fused_dense(x: jax.Array, w: jax.Array, b: jax.Array, *,
                act: str = "relu", bm: int = 128, bn: int = 128,
                interpret: bool = False) -> jax.Array:
    """act(x @ w + b). x: (M, K), w: (K, N), b: (N,) → (M, N).

    Shapes are padded up to (bm, bn) multiples; K is used whole (the chunked
    AE keeps K ≤ 4096 so a full row-band fits VMEM).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and b.shape == (N,)
    bm = min(bm, max(8, M))
    bn = min(bn, max(128, 128))
    Mp, Np = -(-M // bm) * bm, -(-N // bn) * bn
    xp = jnp.pad(x, ((0, Mp - M), (0, 0))) if Mp != M else x
    wp = jnp.pad(w, ((0, 0), (0, Np - N))) if Np != N else w
    bp = (jnp.pad(b, (0, Np - N)) if Np != N else b).reshape(1, Np)

    out = pl.pallas_call(
        functools.partial(_fused_dense_kernel, act=act),
        grid=(Mp // bm, Np // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        interpret=interpret,
    )(xp, wp, bp)
    return out[:M, :N]
