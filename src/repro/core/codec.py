"""Jit-native codec protocol: static specs + pure encode/decode functions.

The stateful ``Compressor`` classes (core/compressor.py) are host-side
adapters over this layer. A codec here is a pair of pure functions driven by
a **spec** — a small frozen (hashable) dataclass carrying everything static:
original length, bit widths, chunking, AE shapes. Specs are valid
``jax.jit`` static arguments, payloads are dicts of fixed-shape arrays, and
nothing in ``decode`` round-trips a traced value through Python. That makes
every codec:

* jit-compatible: ``jax.jit(decode, static_argnums=0)`` just works;
* vmap-compatible over a leading client axis, which is what the batched
  aggregator path needs (DESIGN.md §7);
* shard_map-compatible: the client axis splits across devices with a psum
  epilogue (DESIGN.md §7.2).

Dispatch is a **per-stage ops protocol** (DESIGN.md §13): each stage spec
registers one small ops class (``fwd`` / ``inv`` / ``inv_batched`` /
``carry_key`` / ``carry_shape`` / ``out_size``) in ``_STAGE_OPS``, and every
entry point below — ``encode``, ``decode``, ``decode_batched``,
``decode_and_aggregate``, ``wire_bytes`` — is a fold over stages instead of
an isinstance ladder. :class:`ChainSpec` composes stages (FedZip direction:
sparsify → AE → quantize → entropy-priced wire); a single-stage chain is
bit-identical to the bare codec at every entry point, and
:class:`ComposedSpec` survives as a thin alias for the 2-stage
``(AE, quantize)`` chain with its historical payload keys.

The server-side entry point is :func:`decode_and_aggregate`: stack the
cohort's payloads along a leading client axis (:func:`stack_payloads`) and
decode + FedAvg-reduce the whole cohort in **one** jitted call. The generic
path is a natively-batched decode followed by a per-element ``einsum`` over
the client axis; kernel-terminal AE stacks (``ChunkedAESpec(use_kernel)``
bare or behind pointwise suffix stages) route the final decoder layer
through the fused Pallas kernel (kernels/fused_decode_agg.py), which folds
the FedAvg weight into the matmul accumulation so per-client decoded
tensors are never materialized (memory math in DESIGN.md §7.1).
Scatter-terminal chains (top-k sparsification first) reduce by a weighted
scatter-add over the shipped indices instead of densifying per client.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.paper import AEConfig
from repro.core import autoencoder as ae
from repro.core.autoencoder import ChunkedAEConfig

Params = Any
Payload = Dict[str, jax.Array]


# =====================================================================
# specs — frozen, hashable, jit-static
# =====================================================================
@dataclasses.dataclass(frozen=True)
class IdentitySpec:
    """No compression: the flat update crosses the wire as-is."""
    size: int


@dataclasses.dataclass(frozen=True)
class QuantizeSpec:
    """Blockwise absmax int8 / packed-int4 (FedPAQ-style baseline)."""
    size: int
    bits: int = 8
    block: int = 256


@dataclasses.dataclass(frozen=True)
class TopKSpec:
    """Top-k magnitudes (DGC/STC-style); ships (values, int32 indices).

    As a chain *prefix* the values vector (length ``k``) is the carry fed to
    the next stage, and only the int32 indices ship from this stage — the
    FedZip sparsify-then-compress layout."""
    size: int
    k: int


@dataclasses.dataclass(frozen=True)
class FCAESpec:
    """Paper-faithful full FC AE; ``cfg.input_dim ≥ size`` (padded)."""
    size: int
    cfg: AEConfig


@dataclasses.dataclass(frozen=True)
class ChunkedAESpec:
    """Shared-chunk AE (DESIGN.md §3.2); ``use_kernel`` routes through the
    Pallas fused-dense / fused decode→aggregate kernels."""
    size: int
    cfg: ChunkedAEConfig
    use_kernel: bool = False

    @property
    def n_chunks(self) -> int:
        return -(-self.size // self.cfg.chunk_size)


@dataclasses.dataclass(frozen=True)
class KMeansSpec:
    """K-means codebook quantization (FedZip's clustered quantization).

    The codebook is fit on-device at encode time (``iters`` Lloyd steps,
    quantile-seeded or warm-started from ``params["codebook"]``) and ships
    with the codes — wire format is ``{"codes", "codebook"}``. Codes are
    uint8 for ``k ≤ 256``. Terminal-only stage: codes are not a vector the
    next stage could transform."""
    size: int
    k: int = 16
    iters: int = 8


@dataclasses.dataclass(frozen=True)
class EntropySpec:
    """Entropy-coded wire size, priced analytically (DESIGN.md §13.3).

    Pure pricing stage: encode stays dense on device (no payload entries),
    but :func:`measured_bytes` prices every integer payload leaf of the
    chain at its empirical Shannon entropy plus ``table_bytes_per_symbol``
    per distinct symbol. Only valid as the *last* stage of a chain; chains
    carrying it are not shape-static (``is_shape_static`` → False), so rate
    controllers keep planning with the dense :func:`wire_bytes` price while
    the measured channel reports what an entropy coder would have shipped."""
    table_bytes_per_symbol: int = 4


@dataclasses.dataclass(frozen=True)
class ComposedSpec:
    """AE latents further quantized (§4.2 "orthogonal add-on").

    Since the stage refactor this is a thin alias for the 2-stage chain
    ``ChainSpec((inner, QuantizeSpec(n_latent, bits, block)))`` — every
    entry point canonicalizes through :func:`composed_chain` — but it keeps
    its historical flat payload keys ``{"z_q", "z_scales"}`` and its
    bare-AE-params convention, so pre-refactor payloads, checkpoints and
    golden trajectories stay bit-compatible."""
    inner: Union[FCAESpec, ChunkedAESpec]
    bits: int = 8
    block: int = 64

    @property
    def size(self) -> int:
        return self.inner.size


@dataclasses.dataclass(frozen=True)
class ChainSpec:
    """Composable codec stack: ``stages`` applied left-to-right at encode.

    Every non-terminal vector stage must be *carrying* (its payload has a
    designated carry entry the next stage consumes, flattened 1-D);
    terminal-only stages (quantize, k-means) may appear once, last.
    ``EntropySpec`` may trail the vector stages as a pure pricing stage.
    Payload entries are namespaced ``{"s0": {...}, "s1": {...}}`` (stages
    that ship nothing are omitted). Frozen and hashable — a valid jit-static
    argument like every other spec, and a first-class ``CodecSpec`` union
    member accepted by ladders, partitions and the grouped server path."""
    stages: Tuple[Any, ...]

    def __post_init__(self):
        stages = tuple(self.stages)
        object.__setattr__(self, "stages", stages)
        if not stages:
            raise ValueError("ChainSpec needs at least one stage")
        for s in stages:
            if isinstance(s, (ChainSpec, ComposedSpec)):
                raise TypeError(
                    f"ChainSpec stages must be atomic, got {type(s).__name__}"
                    " (flatten nested chains; use composed_chain() for"
                    " ComposedSpec)")
            if type(s).__name__ == "PartitionSpec":
                raise TypeError("PartitionSpec cannot be a chain stage — "
                                "put chains inside partition groups instead")
        if isinstance(stages[0], EntropySpec):
            raise ValueError("EntropySpec cannot lead a chain")
        for s in stages[:-1]:
            if isinstance(s, EntropySpec):
                raise ValueError("EntropySpec only valid as the last stage")
        vs = tuple(s for s in stages if not isinstance(s, EntropySpec))
        n_ae = sum(isinstance(s, (FCAESpec, ChunkedAESpec)) for s in vs)
        if n_ae > 1:
            raise ValueError("at most one AE stage per chain")
        for i, s in enumerate(vs[:-1]):
            ops = stage_ops(s)
            if ops.carry_key is None:
                raise ValueError(
                    f"{type(s).__name__} is terminal-only (no carry) and "
                    f"cannot precede {type(vs[i + 1]).__name__}")
            out = ops.out_size(s)
            if vs[i + 1].size != out:
                raise ValueError(
                    f"chain size mismatch: {type(s).__name__} emits {out} "
                    f"values but {type(vs[i + 1]).__name__} expects "
                    f"{vs[i + 1].size}")

    @property
    def size(self) -> int:
        return self.stages[0].size

    @property
    def vector_stages(self) -> Tuple[Any, ...]:
        """The stages that transform data (everything but EntropySpec)."""
        return tuple(s for s in self.stages
                     if not isinstance(s, EntropySpec))


# ``partition.PartitionSpec`` (one frozen sub-spec per named leaf group,
# DESIGN.md §10) is also a member of this union: every entry point below
# dispatches it to the pure per-group functions in core/partition.py
# (imported lazily — partition.py imports this module at top level).
CodecSpec = Union[IdentitySpec, QuantizeSpec, TopKSpec, FCAESpec,
                  ChunkedAESpec, KMeansSpec, ComposedSpec, ChainSpec,
                  "PartitionSpec"]


def _partition_mod():
    from repro.core import partition
    return partition


def is_partitioned(spec) -> bool:
    """True for a ``partition.PartitionSpec`` (per-layer codec partitions,
    DESIGN.md §10) — the schedulers route those through the grouped fused
    server path instead of the single-spec one."""
    return isinstance(spec, _partition_mod().PartitionSpec)


# =====================================================================
# stage ops protocol — one class per stage spec, registered in _STAGE_OPS
# =====================================================================
# Each ops class defines:
#   carry_key     name of the payload entry the next chain stage consumes,
#                 or None for terminal-only stages (quantize, k-means)
#   carry_shape   natural (unbatched) shape of that carry entry
#   out_size      flattened carry length == next stage's required ``size``
#   fwd           (spec, params, x) → payload dict   [bare wire keys]
#   inv           (spec, params, payload) → x, shape (spec.size,)
#   inv_batched   (spec, params, stacked) → (C, spec.size), shared params
# The fwd/inv bodies are the pre-refactor per-codec branches verbatim, so
# bare specs (and single-stage chains) stay bit-identical across the
# refactor.
def _dequant_to(spec_bits: int, spec_block: int, n: int,
                q: jax.Array, scales: jax.Array) -> jax.Array:
    from repro.kernels import ops
    return ops.dequantize_blocks(q, scales, bits=spec_bits,
                                 block=spec_block, orig_len=n)


class _IdentityOps:
    carry_key = "flat"

    @staticmethod
    def carry_shape(spec):
        return (spec.size,)

    @staticmethod
    def out_size(spec):
        return spec.size

    @staticmethod
    def fwd(spec, params, flat):
        return {"flat": flat}

    @staticmethod
    def inv(spec, params, payload):
        return payload["flat"]

    @staticmethod
    def inv_batched(spec, params, stacked):
        return stacked["flat"]


class _QuantizeOps:
    carry_key = None

    @staticmethod
    def carry_shape(spec):
        raise TypeError("QuantizeSpec is terminal-only")

    @staticmethod
    def out_size(spec):
        return None

    @staticmethod
    def fwd(spec, params, flat):
        from repro.kernels import ops
        q, scales, _ = ops.quantize_blocks(flat, bits=spec.bits,
                                           block=spec.block)
        return {"q": q, "scales": scales}

    @staticmethod
    def inv(spec, params, payload):
        return _dequant_to(spec.bits, spec.block, spec.size,
                           payload["q"], payload["scales"])

    @staticmethod
    def inv_batched(spec, params, stacked):
        q, scales = stacked["q"], stacked["scales"]
        C = scales.shape[0]
        from repro.kernels import ops
        if spec.bits == 4:
            q = ops.unpack_nibbles(q).reshape(C, -1, spec.block)
        nb = q.shape[1]
        from repro.kernels.ops import interpret_default
        from repro.kernels.quantize import dequantize_blocks_2d
        x = dequantize_blocks_2d(q.reshape(C * nb, spec.block),
                                 scales.reshape(C * nb),
                                 block=spec.block,
                                 interpret=interpret_default())
        return x.reshape(C, -1)[:, :spec.size]


class _TopKOps:
    carry_key = "values"

    @staticmethod
    def carry_shape(spec):
        return (spec.k,)

    @staticmethod
    def out_size(spec):
        return spec.k

    @staticmethod
    def fwd(spec, params, flat):
        _, idx = jax.lax.top_k(jnp.abs(flat), spec.k)
        idx = idx.astype(jnp.int32)
        return {"values": flat[idx], "indices": idx}

    @staticmethod
    def inv(spec, params, payload):
        flat = jnp.zeros((spec.size,), payload["values"].dtype)
        return flat.at[payload["indices"]].set(payload["values"])

    @staticmethod
    def inv_batched(spec, params, stacked):
        return jax.vmap(lambda pl: _TopKOps.inv(spec, None, pl))(stacked)


class _FCAEOps:
    carry_key = "z"

    @staticmethod
    def carry_shape(spec):
        return (spec.cfg.latent_dim,)

    @staticmethod
    def out_size(spec):
        return spec.cfg.latent_dim

    @staticmethod
    def fwd(spec, params, flat):
        pad = spec.cfg.input_dim - spec.size
        assert pad >= 0, (
            f"AE input_dim {spec.cfg.input_dim} < update size {spec.size}")
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return {"z": ae.fc_encode(params, spec.cfg, flat)}

    @staticmethod
    def inv(spec, params, payload):
        flat = ae.fc_decode(params, spec.cfg, payload["z"])
        return flat[:spec.size]

    @staticmethod
    def inv_batched(spec, params, stacked):
        # fc_decode is rank-polymorphic: (C, latent) → (C, input_dim)
        return ae.fc_decode(params, spec.cfg, stacked["z"])[:, :spec.size]


class _ChunkedAEOps:
    carry_key = "z"

    @staticmethod
    def carry_shape(spec):
        return (spec.n_chunks, spec.cfg.latent_chunk)

    @staticmethod
    def out_size(spec):
        return spec.n_chunks * spec.cfg.latent_chunk

    @staticmethod
    def fwd(spec, params, flat):
        if spec.use_kernel:
            from repro.kernels import ops
            return {"z": ops.ae_encode(params, spec.cfg, flat)}
        return {"z": ae.chunked_encode(params, spec.cfg, flat)}

    @staticmethod
    def inv(spec, params, payload):
        if spec.use_kernel:
            from repro.kernels import ops
            return ops.ae_decode(params, spec.cfg, payload["z"], spec.size)
        return ae.chunked_decode(params, spec.cfg, payload["z"], spec.size)

    @staticmethod
    def inv_batched(spec, params, stacked):
        z = stacked["z"]                       # (C, n_chunks, latent)
        C = z.shape[0]
        chunks = _chunked_dec_chunks(spec, params, z)
        return chunks.reshape(C, -1)[:, :spec.size]


class _KMeansOps:
    carry_key = None

    @staticmethod
    def carry_shape(spec):
        raise TypeError("KMeansSpec is terminal-only")

    @staticmethod
    def out_size(spec):
        return None

    @staticmethod
    def fwd(spec, params, flat):
        x = flat.astype(jnp.float32)
        if params is not None and "codebook" in params:
            cb0 = params["codebook"].astype(jnp.float32)
        else:
            probs = (jnp.arange(spec.k, dtype=jnp.float32) + 0.5) / spec.k
            cb0 = jnp.quantile(x, probs)

        def lloyd(cb, _):
            a = jnp.argmin(jnp.abs(x[:, None] - cb[None, :]), axis=1)
            sums = jnp.zeros((spec.k,), jnp.float32).at[a].add(x)
            cnts = jnp.zeros((spec.k,), jnp.float32).at[a].add(1.0)
            # empty clusters keep their old centroid instead of going NaN
            cb = jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1.0), cb)
            return cb, None

        cb, _ = jax.lax.scan(lloyd, cb0, None, length=spec.iters)
        codes = jnp.argmin(jnp.abs(x[:, None] - cb[None, :]), axis=1)
        dt = jnp.uint8 if spec.k <= 256 else jnp.int32
        return {"codes": codes.astype(dt), "codebook": cb}

    @staticmethod
    def inv(spec, params, payload):
        return payload["codebook"][payload["codes"].astype(jnp.int32)]

    @staticmethod
    def inv_batched(spec, params, stacked):
        return jax.vmap(lambda pl: _KMeansOps.inv(spec, None, pl))(stacked)


_STAGE_OPS = {
    IdentitySpec: _IdentityOps,
    QuantizeSpec: _QuantizeOps,
    TopKSpec: _TopKOps,
    FCAESpec: _FCAEOps,
    ChunkedAESpec: _ChunkedAEOps,
    KMeansSpec: _KMeansOps,
}


def stage_ops(spec):
    """The registered ops class for an atomic stage spec."""
    try:
        return _STAGE_OPS[type(spec)]
    except KeyError:
        raise TypeError(f"unknown codec stage {type(spec).__name__}")


def stage_out_size(spec) -> Optional[int]:
    """Flattened carry length a stage emits (next stage's ``size``), or
    None for terminal-only stages."""
    return stage_ops(spec).out_size(spec)


def stage_carry_shape(spec) -> Tuple[int, ...]:
    """Natural (unbatched) shape of a carrying stage's carry entry."""
    return stage_ops(spec).carry_shape(spec)


# =====================================================================
# chain helpers
# =====================================================================
def composed_chain(spec: ComposedSpec) -> ChainSpec:
    """The 2-stage chain a ``ComposedSpec`` canonicalizes to."""
    n_latent = 1
    for d in latent_shape(spec.inner):
        n_latent *= d
    return ChainSpec((spec.inner,
                      QuantizeSpec(size=n_latent, bits=spec.bits,
                                   block=spec.block)))


def _composed_params(params) -> Tuple[Params, None]:
    # ComposedSpec keeps the historical bare-AE-params convention
    return (params, None)


def _composed_wrap_payload(payload: Payload) -> Payload:
    """Chain payload ``{"s1": {q, scales}}`` → historical flat keys."""
    return {"z_q": payload["s1"]["q"], "z_scales": payload["s1"]["scales"]}


def _composed_unwrap_payload(payload: Payload) -> Payload:
    """Historical flat keys → chain payload for the canonical 2-stage."""
    return {"s1": {"q": payload["z_q"], "scales": payload["z_scales"]}}


def _chain_params(spec: ChainSpec, params: Optional[Params]
                  ) -> Tuple[Optional[Params], ...]:
    """Per-vector-stage params tuple (None-filled when ``params is None``)."""
    n = len(spec.vector_stages)
    if params is None:
        return (None,) * n
    if not isinstance(params, tuple) or len(params) != n:
        raise ValueError(
            f"ChainSpec params must be a tuple of {n} per-stage entries "
            f"(None for stateless stages), got {type(params).__name__}")
    return params


def _chain_encode(spec: ChainSpec, params, flat: jax.Array) -> Payload:
    vs = spec.vector_stages
    ps = _chain_params(spec, params)
    out: Payload = {}
    x = flat
    last = len(vs) - 1
    for i, st in enumerate(vs):
        ops = stage_ops(st)
        pl = ops.fwd(st, ps[i], x)
        if i < last:
            carry = pl.pop(ops.carry_key)
            if pl:                     # side entries (e.g. top-k indices)
                out[f"s{i}"] = pl
            x = carry.reshape(-1)      # mid-chain carries travel flat
        else:
            out[f"s{i}"] = pl          # terminal stage ships its carry too
    return out


def _chain_decode(spec: ChainSpec, params, payload: Payload) -> jax.Array:
    vs = spec.vector_stages
    ps = _chain_params(spec, params)
    last = len(vs) - 1
    x = stage_ops(vs[last]).inv(vs[last], ps[last], payload[f"s{last}"])
    for i in range(last - 1, -1, -1):
        st = vs[i]
        ops = stage_ops(st)
        pl = dict(payload.get(f"s{i}", {}))
        pl[ops.carry_key] = x.reshape(ops.carry_shape(st))
        x = ops.inv(st, ps[i], pl)
    return x


def _chain_decode_batched(spec: ChainSpec, params, stacked: Payload, *,
                          upto: int = 0) -> jax.Array:
    """Backward fold of ``inv_batched`` down to (and excluding) vector stage
    ``upto``. ``upto=0`` is the full batched decode → ``(C, spec.size)``;
    ``upto=i`` stops with stage ``i``'s carry, ``(C, out_size(stage i))`` —
    how the scatter and kernel aggregate paths peel pointwise suffixes."""
    vs = spec.vector_stages
    ps = _chain_params(spec, params)
    last = len(vs) - 1
    X = stage_ops(vs[last]).inv_batched(vs[last], ps[last],
                                        stacked[f"s{last}"])
    for i in range(last - 1, upto - 1, -1):
        st = vs[i]
        ops = stage_ops(st)
        C = X.shape[0]
        pl = dict(stacked.get(f"s{i}", {}))
        pl[ops.carry_key] = X.reshape((C,) + ops.carry_shape(st))
        X = ops.inv_batched(st, ps[i], pl)
    return X


def ae_spec(spec: CodecSpec) -> Optional[Union[FCAESpec, ChunkedAESpec]]:
    """The AE spec inside ``spec`` (unwrapping ``ComposedSpec`` and chain
    interiors), or None for pointwise stacks — how the AE lifecycle
    (DESIGN.md §8) finds the chunking/shape config to build refit datasets
    with."""
    if isinstance(spec, ComposedSpec):
        return ae_spec(spec.inner)
    if isinstance(spec, ChainSpec):
        for st in spec.vector_stages:
            if isinstance(st, (FCAESpec, ChunkedAESpec)):
                return st
        return None
    if isinstance(spec, (FCAESpec, ChunkedAESpec)):
        return spec
    return None


def ae_stage_params(spec: CodecSpec, params: Optional[Params]
                    ) -> Optional[Params]:
    """The AE stage's params entry inside a (possibly chained) spec — the
    object whose identity keys decoder-table slots in the grouped launch and
    whose shapes price decoder ships."""
    if isinstance(spec, ComposedSpec):
        return params
    if isinstance(spec, ChainSpec):
        ps = _chain_params(spec, params)
        for st, p in zip(spec.vector_stages, ps):
            if isinstance(st, (FCAESpec, ChunkedAESpec)):
                return p
        return None
    return params


def ae_stage_input(spec: CodecSpec, params: Optional[Params],
                   flat: jax.Array) -> jax.Array:
    """Forward-fold ``flat`` through chain prefix stages up to the AE stage:
    the vector the AE actually encodes. Identity for non-chain specs (the
    AE sees the raw update) — the lifecycle builds refit datasets from this
    so chained AEs train on what they will compress."""
    if not isinstance(spec, ChainSpec):
        return flat
    vs = spec.vector_stages
    ps = _chain_params(spec, params)
    x = flat
    for i, st in enumerate(vs):
        if isinstance(st, (FCAESpec, ChunkedAESpec)):
            return x
        ops = stage_ops(st)
        pl = ops.fwd(st, ps[i], x)
        x = pl[ops.carry_key].reshape(-1)
    return x


def kernel_terminal_ae(spec: CodecSpec) -> Optional[ChunkedAESpec]:
    """The kernel-path chunked-AE stage when ``spec`` can take the fused
    Pallas decode→aggregate launch: a bare ``ChunkedAESpec(use_kernel)``, or
    a chain whose AE expansion is the *last* decode transform (identity-only
    prefix, pointwise-quantizer-only suffix). None otherwise — e.g.
    sparsified chains, whose final decode transform is a scatter."""
    if isinstance(spec, ChunkedAESpec) and spec.use_kernel:
        return spec
    if isinstance(spec, ChainSpec):
        vs = spec.vector_stages
        idx = [i for i, s in enumerate(vs)
               if isinstance(s, (FCAESpec, ChunkedAESpec))]
        if len(idx) != 1:
            return None
        i = idx[0]
        st = vs[i]
        if not (isinstance(st, ChunkedAESpec) and st.use_kernel):
            return None
        if any(not isinstance(s, IdentitySpec) for s in vs[:i]):
            return None
        if any(not isinstance(s, (QuantizeSpec, KMeansSpec))
               for s in vs[i + 1:]):
            return None
        return st
    return None


def kernel_chain_latents(spec: CodecSpec, params: Optional[Params],
                         stacked: Payload) -> Tuple[jax.Array, Params]:
    """``(z, ae_params)`` feeding the fused kernel for a
    :func:`kernel_terminal_ae` spec: the stacked latents ``(C, n_chunks,
    latent)`` after batched-inverting any pointwise suffix stages."""
    if isinstance(spec, ChunkedAESpec):
        return stacked["z"], params
    vs = spec.vector_stages
    ps = _chain_params(spec, params)
    i = next(j for j, s in enumerate(vs) if isinstance(s, ChunkedAESpec))
    st = vs[i]
    if i == len(vs) - 1:
        return stacked[f"s{i}"]["z"], ps[i]
    Z = _chain_decode_batched(spec, params, stacked, upto=i + 1)
    C = Z.shape[0]
    return Z.reshape((C,) + stage_carry_shape(st)), ps[i]


# =====================================================================
# wire pricing
# =====================================================================
def _require_priceable(spec: CodecSpec, params: Optional[Params]) -> None:
    """AE-bearing specs cannot be priced without their parameter shapes —
    raise a clear error instead of letting ``eval_shape`` trace None."""
    if is_partitioned(spec):
        for name, _, cspec in spec.groups:
            p = None if params is None else params.get(name)
            _require_priceable(cspec, p)
        return
    if isinstance(spec, ComposedSpec):
        _require_priceable(spec.inner, params)
        return
    if isinstance(spec, ChainSpec):
        ps = _chain_params(spec, params)
        for st, p in zip(spec.vector_stages, ps):
            _require_priceable(st, p)
        return
    if isinstance(spec, (FCAESpec, ChunkedAESpec)) and params is None:
        raise ValueError(
            f"wire_bytes({type(spec).__name__}(size={spec.size})): this "
            "spec encodes through an autoencoder, so pricing needs the AE "
            "parameter shapes — pass params (e.g. "
            "compressor.codec_params()) instead of None")


def wire_bytes(spec: CodecSpec, params: Optional[Params] = None) -> int:
    """Static uplink cost of one encoded payload for ``spec``, in bytes.

    Computed by abstract evaluation (``jax.eval_shape``) of :func:`encode`,
    so nothing runs and no params are read — only their shapes. This is the
    single pricing rule the rate controllers (DESIGN.md §9.1) plan ladder
    allocations with, and it is asserted equal to ``tree_bytes`` of a real
    encode in tests/test_ratecontrol.py, so planned and observed uplink can
    never diverge. Chains ending in :class:`EntropySpec` are priced at
    their *dense* wire size here (entropy-coded sizes are data-dependent);
    :func:`measured_bytes` reports the entropy-coded price per payload."""
    _require_priceable(spec, params)
    shapes = jax.eval_shape(
        lambda f: encode(spec, params, f),
        jax.ShapeDtypeStruct((spec.size,), jnp.float32))
    total = 0
    for s in jax.tree_util.tree_leaves(shapes):
        n = 1
        for d in s.shape:
            n *= d
        total += n * s.dtype.itemsize
    return int(total)


def is_shape_static(spec: CodecSpec) -> bool:
    """True when the real wire size of every payload equals the eval-shape
    :func:`wire_bytes` price — i.e. the spec carries no entropy-coded
    stage. Rate controllers require this invariant; entropy-coded chains
    report their data-dependent size via :func:`measured_bytes` only."""
    if is_partitioned(spec):
        return all(is_shape_static(c) for _, _, c in spec.groups)
    if isinstance(spec, ChainSpec):
        return not any(isinstance(s, EntropySpec) for s in spec.stages)
    return True


def measured_bytes(spec: CodecSpec, payload: Payload) -> float:
    """Host-side measured wire size of one real payload, in bytes.

    For shape-static specs this equals ``tree_bytes(payload)`` (and hence
    :func:`wire_bytes`). For chains ending in :class:`EntropySpec`, every
    integer payload leaf (quantize codes, k-means codes, top-k indices) is
    priced at ``min(raw, n·H/8 + table_bytes_per_symbol·n_distinct)`` — its
    empirical Shannon entropy plus the code table, with the adaptive-coder
    raw fallback for incompressible leaves — while float leaves (scales,
    codebooks, raw values) ship uncoded. So measured ≤ dense always. This
    is the *measured-bytes channel*: reported alongside, never instead of,
    the shape-static plan price."""
    import numpy as np

    if is_partitioned(spec):
        return float(sum(measured_bytes(c, payload[n])
                         for n, _, c in spec.groups))
    entropy = None
    if isinstance(spec, ChainSpec) and isinstance(spec.stages[-1],
                                                  EntropySpec):
        entropy = spec.stages[-1]
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(payload):
        a = np.asarray(leaf)
        if a.size == 0:
            continue
        raw = a.size * a.dtype.itemsize
        if entropy is not None and np.issubdtype(a.dtype, np.integer):
            _, cnts = np.unique(a, return_counts=True)
            p = cnts / a.size
            H = float(-(p * np.log2(p)).sum())
            coded = (a.size * H / 8.0
                     + cnts.size * entropy.table_bytes_per_symbol)
            # an adaptive coder ships incompressible leaves raw (top-k
            # indices are near-uniform: table cost would exceed the win)
            total += min(raw, coded)
        else:
            total += raw
    return float(total)


def latent_shape(spec: Union[FCAESpec, ChunkedAESpec]) -> Tuple[int, ...]:
    """Static shape of the AE latent payload entry ``z``."""
    if isinstance(spec, FCAESpec):
        return (spec.cfg.latent_dim,)
    if isinstance(spec, ChunkedAESpec):
        return (spec.n_chunks, spec.cfg.latent_chunk)
    raise TypeError(f"no latent for {type(spec).__name__}")


# =====================================================================
# encode: flat (size,) → payload dict of fixed-shape arrays
# =====================================================================
def encode(spec: CodecSpec, params: Optional[Params],
           flat: jax.Array) -> Payload:
    """Pure collaborator-side encoder. ``params`` is the AE parameter pytree
    for the AE specs, a per-stage tuple for chains, ``None`` otherwise.
    Jit-able with ``spec`` static."""
    if is_partitioned(spec):
        return _partition_mod().encode_tree(spec, params, flat)
    if isinstance(spec, ComposedSpec):
        pl = _chain_encode(composed_chain(spec), _composed_params(params),
                           flat)
        return _composed_wrap_payload(pl)
    if isinstance(spec, ChainSpec):
        return _chain_encode(spec, params, flat)
    return stage_ops(spec).fwd(spec, params, flat)


# =====================================================================
# decode: payload → flat (size,)
# =====================================================================
def decode(spec: CodecSpec, params: Optional[Params],
           payload: Payload) -> jax.Array:
    """Pure aggregator-side decoder → flat ``(spec.size,)`` vector. No
    traced→Python casts: every length/shape is static spec data, so the
    whole function stages into one XLA computation under ``jax.jit``."""
    if is_partitioned(spec):
        return _partition_mod().decode_tree(spec, params, payload)
    if isinstance(spec, ComposedSpec):
        return _chain_decode(composed_chain(spec), _composed_params(params),
                             _composed_unwrap_payload(payload))
    if isinstance(spec, ChainSpec):
        return _chain_decode(spec, params, payload)
    return stage_ops(spec).inv(spec, params, payload)


# =====================================================================
# batched decode over a leading client axis
# =====================================================================
def stack_payloads(payloads) -> Payload:
    """Stack per-client payload dicts along a new leading client axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *payloads)


def decode_batched(spec: CodecSpec, params: Optional[Params],
                   stacked: Payload, *,
                   params_batched: bool = False) -> jax.Array:
    """Decode a whole cohort at once: stacked payload ``(C, ...)`` →
    ``(C, size)``. With ``params_batched`` the AE params carry a leading
    client axis too (per-client decoders) and the decode vmaps over both;
    otherwise the shared-params fast path reshapes the client axis into the
    existing batch dimension of each kernel, which is bit-identical to
    per-client decoding for the pointwise codecs."""
    if is_partitioned(spec):
        return _partition_mod().decode_tree_batched(
            spec, params, stacked, params_batched=params_batched)
    if params_batched:
        return jax.vmap(lambda p, pl: decode(spec, p, pl))(params, stacked)
    if isinstance(spec, ComposedSpec):
        return _chain_decode_batched(composed_chain(spec),
                                     _composed_params(params),
                                     _composed_unwrap_payload(stacked))
    if isinstance(spec, ChainSpec):
        return _chain_decode_batched(spec, params, stacked)
    return stage_ops(spec).inv_batched(spec, params, stacked)


def _chunked_dec_chunks(spec: ChunkedAESpec, params: Params,
                        z: jax.Array) -> jax.Array:
    """(C, n_chunks, latent) → (C, n_chunks, chunk_size): the client axis is
    folded into the chunk batch, so the decode is one matmul chain whichever
    path (Pallas fused_dense or pure-jnp) runs."""
    C, nc, latent = z.shape
    z2 = z.reshape(C * nc, latent)
    if spec.use_kernel:
        from repro.kernels import ops
        flat = ops.ae_decode(params, spec.cfg,
                             z2, C * nc * spec.cfg.chunk_size)
    else:
        flat = ae.chunked_decode(params, spec.cfg,
                                 z2, C * nc * spec.cfg.chunk_size)
    return flat.reshape(C, nc, spec.cfg.chunk_size)


# =====================================================================
# fused decode→aggregate: the one-jitted-call-per-round server path
# =====================================================================
@functools.partial(jax.jit, static_argnames=("spec", "params_batched"))
def decode_and_aggregate(spec: CodecSpec, params: Optional[Params],
                         stacked: Payload, weights: jax.Array,
                         base: Optional[jax.Array] = None, *,
                         params_batched: bool = False) -> jax.Array:
    """One jitted call per round: decode the stacked cohort payloads and
    FedAvg-reduce along the client axis → mean flat update ``(size,)``.

    ``weights`` must already be normalized (Σ=1; use
    ``aggregate.normalize_weights`` — normalizing host-side keeps this path
    bit-identical to the sequential decode-then-``weighted_mean`` path).
    ``base`` (e.g. the flat global params under the §5.2 weights-payload
    protocol) is subtracted from each decoded row before the reduction.

    Three fused routes, picked by terminal decode transform:

    * scatter-terminal chains (top-k prefix, DESIGN.md §13.4): batched-
      invert the suffix down to the top-k carry ``(C, k)`` and reduce by
      one weighted ``scatter-add`` over the shipped indices — dense
      per-client rows are never built;
    * kernel-terminal AE stacks (:func:`kernel_terminal_ae`): hidden
      decoder layers on the folded (C·n_chunks) batch, then the fused
      Pallas kernel folds ``weights`` into the final decoder matmul
      (DESIGN.md §7.1);
    * everything else: natively-batched decode + per-element ``einsum``."""
    w = weights.astype(jnp.float32)
    if is_partitioned(spec):
        # partitioned homogeneous cohort: one fused reduction per group,
        # all inlined into this single jitted call (kernel-path chunked-AE
        # groups still take the Pallas fused branch). Heterogeneous
        # partitioned cohorts go through the scheduler's grouped path
        # (partition.server_decode_aggregate, DESIGN.md §10.2) instead.
        part = _partition_mod()
        means = {}
        for name, slices, cspec in spec.groups:
            p = None if params is None else params.get(name)
            base_g = None if base is None else part.gather(slices, base)
            means[name] = decode_and_aggregate(
                cspec, p, stacked[name], w, base_g,
                params_batched=params_batched and p is not None)
        return part.scatter_groups(spec.structure, means, spec.size)
    if not params_batched:
        if (isinstance(spec, ChainSpec)
                and isinstance(spec.vector_stages[0], TopKSpec)
                and len(spec.vector_stages) > 1):
            vals = _chain_decode_batched(spec, params, stacked, upto=1)
            idx = stacked["s0"]["indices"]              # (C, k)
            wv = vals.astype(jnp.float32) * w[:, None]
            out = jnp.zeros((spec.size,), jnp.float32)
            out = out.at[idx.reshape(-1)].add(wv.reshape(-1))
            return out if base is None else out - base  # Σw=1
        kspec = kernel_terminal_ae(spec)
        if kspec is not None:
            z, ae_prm = kernel_chain_latents(spec, params, stacked)
            mean = _fused_chunked_decode_agg(kspec, ae_prm, z, w)
            return mean if base is None else mean - base
    rows = decode_batched(spec, params, stacked,
                          params_batched=params_batched)
    if base is not None:
        rows = rows - base[None, :]
    return jnp.einsum("c,cp->p", w, rows.astype(jnp.float32))


def chunked_hidden(spec: ChunkedAESpec, params: Params, z: jax.Array,
                   rows: Optional[int] = None) -> jax.Array:
    """Kernel-path hidden decoder stack: ``(C, n_chunks, latent)`` latents →
    ``(C, rows, K)`` penultimate activations, everything latent-sided.
    Shared by the per-bucket fused path below and the grouped ragged launch
    (core/partition.py, DESIGN.md §11.2) — both then expand to chunk width
    inside a weighted-accumulation kernel.

    ``rows`` (default ``n_chunks``) zero-pads each client's chunk axis on
    the latent side, in the flat lane-dense ``(C, n_chunks·latent)`` form,
    so the hidden activations come out of ``fused_dense`` already in the
    row-padded layout the kernel reads (DESIGN.md §7.1); the padded rows
    hold the decode of a zero latent, which the caller slices off."""
    from repro.kernels.fused_dense import fused_dense
    from repro.kernels.ops import interpret_default
    interp = interpret_default()
    C, nc, latent = z.shape
    rows = nc if rows is None else rows
    x = z.reshape(C, nc * latent)
    if rows != nc:
        x = jnp.pad(x, ((0, 0), (0, (rows - nc) * latent)))
    x = x.reshape(C * rows, latent)
    for layer in params["dec"][:-1]:           # hidden stack, act throughout
        # large bm: the folded (C·n_chunks) batch is tall and the hidden
        # widths narrow, so row-fat tiles stay far under VMEM while cutting
        # the grid-step count (which is what interpret-mode costs scale on)
        x = fused_dense(x, layer["w"], layer["b"],
                        act=spec.cfg.activation, bm=512, interpret=interp)
    return x.reshape(C, rows, x.shape[-1])


def _fused_chunked_decode_agg(spec: ChunkedAESpec, params: Params,
                              z: jax.Array, weights: jax.Array) -> jax.Array:
    """ChunkedAE fused path: per-client work stays latent-sided (the hidden
    stack output ``(C, Mp, hidden)``); the chunk_size-wide expansion
    happens inside the weighted-accumulation kernel, once. The latents are
    padded to the kernel plan's ``Mp`` rows, so the hidden activations are
    written once in the layout the kernel reads; the ``Mp - n_chunks``
    padded rows are sliced off before the denorm."""
    from repro.kernels.fused_decode_agg import fused_decode_agg, padded_rows
    from repro.kernels.ops import interpret_default
    dec = params["dec"]
    C, nc, _ = z.shape
    K, N = dec[-1]["w"].shape
    h = chunked_hidden(spec, params, z, rows=padded_rows(C, nc, K, N))
    chunks = fused_decode_agg(h, weights, dec[-1]["w"], dec[-1]["b"],
                              interpret=interpret_default())[:nc]
    norm = params["norm"]                             # (nc, chunk_size)
    chunks = chunks * norm["std"] + norm["mean"]      # Σw=1 ⇒ mean denorm
    return chunks.reshape(-1)[:spec.size]


# =====================================================================
# shard_map variant: client axis split across devices (DESIGN.md §7.2)
# =====================================================================
@functools.lru_cache(maxsize=None)
def _sharded_callable(spec: CodecSpec, mesh: jax.sharding.Mesh):
    """Build (once per (spec, mesh)) the jitted shard_map reduction so
    repeated rounds dispatch a cached executable instead of re-tracing."""
    from jax.sharding import PartitionSpec as P

    def shard_fn(params, stacked_shard, w_shard):
        rows = decode_batched(spec, params, stacked_shard)
        partial = jnp.einsum("c,cp->p", w_shard.astype(jnp.float32),
                             rows.astype(jnp.float32))
        return jax.lax.psum(partial, "clients")

    # check_vma=False: pallas_call (the quantize/fused-dense kernels inside
    # decode_batched) has no varying-manual-axes rule
    return jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                                 in_specs=(P(), P("clients"), P("clients")),
                                 out_specs=P(), check_vma=False))


def decode_and_aggregate_sharded(spec: CodecSpec, params: Optional[Params],
                                 stacked: Payload, weights: jax.Array,
                                 base: Optional[jax.Array] = None,
                                 mesh: Optional[jax.sharding.Mesh] = None
                                 ) -> jax.Array:
    """Large-cohort variant: shard the client axis over a 1-D ``clients``
    device mesh; each device computes its shard's weighted *sum* (weights
    are globally pre-normalized, so no renormalization is needed; AE params
    are replicated), and a single ``psum`` produces the cohort mean. The
    cohort is zero-weight padded up to a device multiple (zero payloads
    decode to finite values for every codec, so padded rows contribute
    exactly 0). Layout notes in DESIGN.md §7.2."""
    import numpy as np

    if mesh is None:
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("clients",))
    n_dev = mesh.devices.size
    C = weights.shape[0]
    pad = (-C) % n_dev
    if pad:
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)),
            stacked)
        weights = jnp.pad(weights, (0, pad))
    mean = _sharded_callable(spec, mesh)(params, stacked, weights)
    return mean if base is None else mean - base
