"""Pallas TPU kernel: fused batched decode→aggregate epilogue.

The aggregator's hot path at cohort scale (DESIGN.md §7): after the cohort's
AE latents are pushed through the decoder's hidden stack, the *final*
decoder layer is a linear matmul that expands each client's per-chunk
hidden activations ``h_c`` (small, latent-side) into full-model-sized chunk
reconstructions — and FedAvg immediately reduces those reconstructions
across clients. Materializing the per-client decoded tensors costs
``O(cohort × model)`` HBM; this kernel folds the per-client FedAvg weight
into the decoder-matmul accumulation instead. Because the final layer is
linear and shared, the weighted client reduction commutes with the matmul,
so each grid step reduces its client block *before* the chunk-wide
expansion:

    out = Σ_blocks ( Σ_{c∈block} w_c · h_c ) @ W_dec  + b_dec   (Σ_c w_c = 1)

Grid: ``(N/bn, row tiles, C/bc)`` with the client-block axis innermost.
Each output tile ``(bm, bn)`` stays resident in VMEM while the kernel walks
the cohort blocks: per step, a VPU reduction collapses ``bc`` clients'
hidden tiles into one weighted tile (latent-sided — ``bc·bm·K`` floats), a
single MXU matmul expands it to ``bn`` columns, and the result accumulates
into the output; the bias is added on the first block. The column axis is
outermost, so each ``(K, bn)`` decoder band is fetched once per launch.
Full-model-sized data exists exactly once (the accumulator) — peak memory
``O(model)``, not ``O(cohort × model)`` (memory math in DESIGN.md §7.1).

VMEM: the pipeline double-buffers every block, and the reduction and the
matmul each hold a temporary, so one step needs about
``3·bc·bm·K + 2·K·bn + 3·bm·bn`` floats. :func:`_tile_plan` shrinks
``bc``, then ``bn``, then ``bm`` until that fits :data:`VMEM_BUDGET`, and
the launch raises the scoped-VMEM limit to :data:`VMEM_LIMIT`. At the
production chunked AE (K=512, N=4096) the untiled step would need ~60 MB
at bc=16 and the v5e compiler refuses it; the plan runs bc=8, bn=2048
(≈18 MB). Compiled for v5e in tests/test_tpu_compile.py; validated
against the pure-jnp oracle ``ref.fused_decode_agg_ref`` in interpret mode
(DESIGN.md §7.3, tests/test_kernels.py).

Row padding is the caller's to do. The launch pads each bucket's rows to a
multiple of the plan's ``bm``; a caller that hands in ``h`` already at
:func:`padded_rows` rows has nothing padded here (the launch then adds no
pad op), so the hidden activations are written once, by the layer that
makes them, in the layout this kernel reads. For the chunked AE that means
padding the cohort's *latents* to the plan's rows before the hidden stack
(``core/codec.py::_fused_chunked_decode_agg``): at C=1024, 135 chunks and
hidden 512 the alternative is a 283 MB relayout of the hidden activations
plus a pad to 144 rows. The padded rows decode to values no one reads; the
caller slices the ``(Mp, N)`` output back to its real rows before the
denorm. Every row of the decoder chain is independent, so the real rows
see the same operations and tiles either way (DESIGN.md §7.1).

Under per-layer codec partitions (DESIGN.md §10.2) the grouped server path
launches this kernel once per kernel-path chunked-AE (partition, spec)
bucket per round — ``M`` is then the *group's* chunk count, not the whole
model's; the weighted client reduction still commutes because each
bucket's weights are renormalized to Σ=1 before dispatch.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# scoped VMEM the launch may use, and the per-step estimate the tile plan
# keeps under it (the gap is the compiler's own scratch)
VMEM_LIMIT = 32 * 2 ** 20
VMEM_BUDGET = 20 * 2 ** 20


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _step_vmem_bytes(bc: int, bm: int, K: int, bn: int) -> int:
    """f32 bytes one grid step holds: double-buffered weight, hidden,
    decoder-band, bias and output blocks, plus the weighted-product and
    matmul temporaries."""
    return 4 * (2 * (bc + bc * bm * K + K * bn + bn + bm * bn)
                + bc * bm * K + bm * K + bm * bn)


def _tile_plan(C: int, M: int, K: int, N: int, bm: int,
               bc: int) -> Tuple[int, int, int]:
    """``(bm, bc, bn)`` for a launch over ``C`` clients and ``M`` rows.

    Row tiles are balanced (``ceil(M / tiles)`` rounded up to the 8-row
    sublane) so a 135-row bucket pads to 144, not 256. Then, while the step
    exceeds :data:`VMEM_BUDGET`: halve ``bc`` (free — it only adds grid
    steps), else halve ``bn`` (each column band re-reads the hidden
    activations), else halve ``bm``. Every halving keeps the block shapes
    legal for the TPU tiling: ``bc`` a multiple of 8 or the whole padded
    client axis, ``bn`` a multiple of 128 or the whole ``N``, ``bm`` a
    multiple of 8."""
    tiles = _cdiv(M, bm)
    bm = _cdiv(_cdiv(M, tiles), 8) * 8
    bc = min(bc, C)
    bn = N
    while _step_vmem_bytes(bc, bm, K, bn) > VMEM_BUDGET:
        if bc % 16 == 0:
            bc //= 2
        elif bn % 256 == 0:
            bn //= 2
        elif bm % 16 == 0:
            bm //= 2
        else:
            break
    return bm, bc, bn


def _padded(M: int, bm: int) -> int:
    return _cdiv(M, bm) * bm


def padded_rows(C: int, M: int, K: int, N: int, *, bm: int = 128,
                bc: int = 16) -> int:
    """Rows ``Mp`` a one-bucket launch over ``C`` clients, ``M`` rows, hidden
    width ``K`` and chunk width ``N`` pads to: the multiple of the tile
    plan's row tile that :func:`grouped_fused_decode_agg` pads ``M`` to
    with the same ``bm``/``bc`` caps (144 for the 135-row CIFAR bucket at
    K=512, N=4096). ``padded_rows(C, padded_rows(C, M, ...), ...)`` is the
    same ``Mp``, so an ``h`` built at these rows is launched unpadded."""
    return _padded(M, _tile_plan(C, M, K, N, bm, bc)[0])


def _pad_to(x: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """Zero-pad ``x`` at the end of each axis up to ``shape``; no op where
    it is there already."""
    if x.shape == tuple(shape):
        return x
    return jnp.pad(x, [(0, n - m) for m, n in zip(x.shape, shape)])


def _decode_agg_kernel(desc_ref, w_ref, h_ref, wl_ref, b_ref, o_ref):
    """Per grid step ``(j, t, cb)``: column band ``j``, row tile ``t`` (one
    (bucket, m-tile) pair resolved through the prefetched descriptor
    table), client block ``cb`` (zero-weight padded up to the cohort-wide
    maximum). Reduce the client block, then expand."""
    del desc_ref                             # consumed by the index maps
    cb = pl.program_id(2)
    w = w_ref[0].astype(jnp.float32)         # (bc, 1) this bucket's weights
    h = h_ref[...].astype(jnp.float32)       # (bc, bm, K)
    # weighted client reduction BEFORE the chunk-wide expansion (VPU,
    # latent-sided): Σ_{c∈block} w_c · h_c → (bm, K)
    hbar = jnp.sum(h * w[:, :, None], axis=0)
    y = jnp.dot(hbar, wl_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)

    @pl.when(cb == 0)
    def _init():
        o_ref[...] = (y + b_ref[0].astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when(cb > 0)
    def _accum():
        o_ref[...] = (o_ref[...].astype(jnp.float32) + y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bc", "interpret"))
def fused_decode_agg(h: jax.Array, weights: jax.Array, w_last: jax.Array,
                     b_last: jax.Array, *, bm: int = 128, bc: int = 16,
                     interpret: bool = False) -> jax.Array:
    """``Σ_c weights[c] · (h[c] @ w_last) + b_last`` without materializing
    any per-client ``(M, N)`` tensor.

    h: (C, M, K) per-client penultimate decoder activations;
    weights: (C,) pre-normalized FedAvg weights (must sum to 1 — the bias
    is added once, which equals the weighted mean of per-client biases only
    under that normalization);
    w_last: (K, N), b_last: (N,) final decoder layer → (M, N).
    ``bm``/``bc`` cap the row tile and the client block (zero-weight
    padded); :func:`_tile_plan` may shrink both to fit VMEM. This is the
    one-bucket case of :func:`grouped_fused_decode_agg` — the same launch.
    """
    C, M, K = h.shape
    K2, N = w_last.shape
    assert K == K2 and b_last.shape == (N,) and weights.shape == (C,)
    return grouped_fused_decode_agg([h], [weights], w_last[None],
                                    b_last[None], [0], bm=bm, bc=bc,
                                    interpret=interpret)[0]


# =====================================================================
# grouped ragged launch: one kernel sweep over every bucket of a round
# =====================================================================
def grouped_fused_decode_agg(hs: Sequence[jax.Array],
                             weights: Sequence[jax.Array],
                             w_stack: jax.Array, b_stack: jax.Array,
                             dec_idx: Sequence[int], *, bm: int = 128,
                             bc: int = 16,
                             interpret: bool = False) -> List[jax.Array]:
    """One Pallas launch over every (partition, spec) bucket of a round:
    per bucket ``b``, ``Σ_c weights[b][c] · (hs[b][c] @ w_stack[dec_idx[b]])
    + b_stack[dec_idx[b]]`` — the ragged cohort packed into a single grid.

    hs[b]: (C_b, M_b, K) per-client penultimate decoder activations — the
    client count C_b AND the chunk-row count M_b are ragged across buckets;
    every bucket must share the hidden width ``K`` and the chunk width ``N``
    (the grouped server path groups launches by that (K, N) signature).
    weights[b]: (C_b,) this bucket's FedAvg weights (the caller owns the
    Σ-normalization contract, exactly as for :func:`fused_decode_agg` — the
    bias is added once per output tile). w_stack: (D, K, N) distinct final
    decoder layers, b_stack: (D, N); ``dec_idx[b]`` picks bucket ``b``'s
    decoder, so buckets sharing a decoder share one stacked copy.

    Descriptor layout (DESIGN.md §11.1): a ``(3, T)`` int32 table with one
    column per (bucket, m-tile) grid tile — row 0 the bucket id (selects
    the weight row), row 1 the packed output row-block (selects the h
    column band and the output tile), row 2 the decoder index. The table
    rides the scalar-prefetch operand of a ``PrefetchScalarGridSpec``, so
    the index maps resolve every block address from SMEM before the DMA
    fires — raggedness costs descriptor lookups, not extra launches. The
    packed weights are ``(B, Cp, 1)`` so each ``(1, bc, 1)`` block keeps
    the client axis on sublanes, as the TPU tiling requires.

    Packing: client axis padded to the cohort-wide max block count (zero
    weight ⇒ exact zero contribution), each bucket's rows padded to a
    ``bm`` multiple and laid end-to-end; a bucket already at those rows and
    clients is not copied to pad it. A bucket with zero clients
    contributes nothing to the grid and returns exact zeros (its weight
    mass is zero, so the caller's scale-back drops it anyway).

    Returns the per-bucket ``(M_b, N)`` reconstructions (unpacked views of
    the one packed output). Not jit-wrapped: callers trace it inside the
    round's single jitted dispatch (core/partition.py, DESIGN.md §11.2).
    """
    assert len(hs) == len(weights) == len(dec_idx)
    D, K, N = w_stack.shape
    assert b_stack.shape == (D, N)
    live = [b for b, h in enumerate(hs) if h.shape[0] > 0]
    if not live:
        return [jnp.zeros((h.shape[1], N), jnp.float32) for h in hs]
    for b in live:
        C_b, M_b, K_b = hs[b].shape
        assert K_b == K, (
            f"bucket {b}: hidden width {K_b} != {K} — grouped launches "
            f"require one (K, N) signature; split the launch")
        assert weights[b].shape == (C_b,) and M_b > 0
        assert 0 <= dec_idx[b] < D
    bm, bc, bn = _tile_plan(max(hs[b].shape[0] for b in live),
                            max(hs[b].shape[1] for b in live), K, N, bm, bc)
    Cp = max(_padded(hs[b].shape[0], bc) for b in live)

    # pack: clients → shared padded axis, rows → bm-padded bands, and the
    # (bucket, row-block, decoder) descriptor column per grid tile
    h_bands, w_rows, offsets = [], [], {}
    bucket_of, row_of, dec_of = [], [], []
    pos = 0
    for b in live:
        Mp_b = _padded(hs[b].shape[1], bm)
        h_bands.append(_pad_to(hs[b], (Cp, Mp_b, K)))
        w_rows.append(_pad_to(weights[b].astype(jnp.float32), (Cp,)))
        offsets[b] = pos
        for i in range(Mp_b // bm):
            bucket_of.append(len(w_rows) - 1)   # row in the packed weights
            row_of.append(pos // bm + i)
            dec_of.append(dec_idx[b])
        pos += Mp_b
    h_packed = (h_bands[0] if len(h_bands) == 1
                else jnp.concatenate(h_bands, axis=1))   # (Cp, Mtot, K)
    w_packed = jnp.stack(w_rows)[:, :, None]             # (B_live, Cp, 1)
    desc = jnp.asarray([bucket_of, row_of, dec_of], jnp.int32)
    T = len(bucket_of)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // bn, T, Cp // bc),
        in_specs=[
            pl.BlockSpec((1, bc, 1), lambda j, t, cb, d: (d[0, t], cb, 0)),
            pl.BlockSpec((bc, bm, K), lambda j, t, cb, d: (cb, d[1, t], 0)),
            pl.BlockSpec((1, K, bn), lambda j, t, cb, d: (d[2, t], 0, j)),
            pl.BlockSpec((1, 1, bn), lambda j, t, cb, d: (d[2, t], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, t, cb, d: (d[1, t], j)),
    )
    out = pl.pallas_call(
        _decode_agg_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((pos, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(desc, w_packed, h_packed, w_stack, b_stack.reshape(D, 1, N))

    results: List[jax.Array] = []
    for b, h in enumerate(hs):
        if h.shape[0] == 0:
            results.append(jnp.zeros((h.shape[1], N), jnp.float32))
        else:
            off = offsets[b]
            results.append(out[off:off + h.shape[1]])
    return results
