"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch × shape × mesh), in seconds, against the peaks of
the named chip (``roofline/peaks.py``, keyed by ``device_kind``):

  compute    = HLO_FLOPs_per_device / peak bf16 FLOP/s
  memory     = HLO_bytes_per_device / HBM bandwidth
  collective = collective_bytes_per_device / ICI bandwidth per link

``cost_analysis()`` is already per-device (the SPMD-partitioned module), so
no extra division by chip count. Collective bytes are NOT in cost_analysis —
we parse the post-partitioning HLO text and sum the operand sizes of every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute /
ragged-all-to-all instruction (shapes in SPMD HLO are per-device shards).
ICI assumption: one effective link per chip (conservative; v5e has four
— we report the term, not a latency promise).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.roofline.peaks import ChipPeaks, peaks_for

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

# shape token e.g. f32[16,128] or bf16[2,4,8]{2,1,0} or f32[] (scalar)
_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z]*\d*(?:e\d+m\d+(?:fn)?)?)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes per collective kind from (SPMD-partitioned) HLO."""
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    out["total"] = 0.0
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(
            r"=\s*(?:\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES)
            + r")(?:-start|-done)?\(", stripped)
        if not m:
            continue
        kind = m.group(1)
        if "-done(" in stripped:
            continue                        # avoid double count start/done
        # operand shapes: everything inside the call parens
        call = stripped[m.end():]
        depth = 1
        end = 0
        for i, ch in enumerate(call):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = call[:end]
        nbytes = sum(_shape_bytes(d, s)
                     for d, s in _SHAPE_RE.findall(operands))
        if nbytes == 0.0:
            # fall back to the result shape (left of '=')
            lhs = stripped.split("=")[0]
            prefix = stripped[:m.start()]
            nbytes = sum(_shape_bytes(d, s)
                         for d, s in _SHAPE_RE.findall(prefix))
            del lhs
        out[kind] += nbytes
        out["total"] += nbytes
        out["count"] += 1
    return out


@dataclasses.dataclass
class RooflineReport:
    name: str
    n_devices: int
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    collective_breakdown: Dict[str, float]
    peak_memory_per_device: float
    model_flops: float                  # 6*N*D (or mode-appropriate)
    peaks: ChipPeaks
    attn_loop_bytes_per_device: float = 0.0
    cross_pod_bytes_per_device: float = 0.0
    compute_s: float = 0.0
    memory_s: float = 0.0
    memory_fused_s: float = 0.0
    collective_s: float = 0.0

    def __post_init__(self):
        pk = self.peaks
        self.compute_s = self.flops_per_device / pk.flops_bf16
        self.memory_s = self.hbm_bytes_per_device / pk.hbm_bw
        # fused-attention estimate: on TPU the flash score/prob tiles stay
        # in VMEM inside the Pallas kernel (kernels/flash_attention.py) —
        # remove their modeled HBM traffic from the memory term.
        self.memory_fused_s = (self.hbm_bytes_per_device
                               - self.attn_loop_bytes_per_device) / pk.hbm_bw
        self.collective_s = self.collective_bytes_per_device / pk.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_fused_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        total = self.flops_per_device * self.n_devices
        return self.model_flops / total if total else 0.0

    def row(self) -> Dict[str, object]:
        return {
            "name": self.name,
            "devices": self.n_devices,
            "compute_ms": round(self.compute_s * 1e3, 3),
            "memory_ms": round(self.memory_s * 1e3, 3),
            "memory_fused_ms": round(self.memory_fused_s * 1e3, 3),
            "collective_ms": round(self.collective_s * 1e3, 3),
            "dominant": self.dominant,
            "hbm_gb_per_dev": round(self.peak_memory_per_device / 2**30, 2),
            "model_flops_frac": round(self.useful_flops_fraction, 3),
            "collective_gb_per_dev": round(
                self.collective_bytes_per_device / 2**30, 4),
            "cross_pod_gb_per_dev": round(
                self.cross_pod_bytes_per_device / 2**30, 6),
        }


def analyze_compiled(name: str, compiled, n_devices: int,
                     model_flops: float, *, device_kind: str,
                     pod_boundary: int = 0) -> RooflineReport:
    """Roofline terms from the compiled SPMD executable, against the peaks
    of ``device_kind`` (the chip the program is compiled for).

    Uses the trip-count-aware static HLO analyzer (hlo_parse) — XLA's own
    ``cost_analysis()`` visits while bodies once, undercounting scanned-layer
    models by ~n_layers. The memory term is the un-fused upper bound
    (every top-level HLO op reads operands / writes results through HBM);
    compute and collective terms are exact up to elementwise FLOPs.
    """
    from repro.roofline.hlo_parse import analyze_hlo
    hlo = compiled.as_text()
    cost = analyze_hlo(hlo, pod_boundary=pod_boundary)
    mem = compiled.memory_analysis()
    peak = 0.0
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes"):
        peak += float(getattr(mem, attr, 0.0) or 0.0)
    alias = float(getattr(mem, "alias_size_in_bytes", 0.0) or 0.0)
    peak -= alias
    return RooflineReport(
        name=name, n_devices=n_devices, flops_per_device=cost.flops,
        hbm_bytes_per_device=cost.memory_bytes,
        attn_loop_bytes_per_device=cost.attn_loop_bytes,
        cross_pod_bytes_per_device=cost.cross_pod_bytes,
        collective_bytes_per_device=cost.collective_bytes,
        collective_breakdown=dict(cost.collective_breakdown),
        peak_memory_per_device=peak, model_flops=model_flops,
        peaks=peaks_for(device_kind))


# =====================================================================
# MODEL_FLOPS estimates (6·N·D dense / 6·N_active·D MoE)
# =====================================================================
def active_params(cfg) -> float:
    """Approximate active parameter count per token."""
    D, L, V = cfg.d_model, cfg.n_layers, cfg.padded_vocab
    emb = V * D * (1 if cfg.tie_embeddings else 2)
    if cfg.family == "ssm":
        s = cfg.ssm
        d_in = s.expand * D
        nh = d_in // s.head_dim
        per_layer = D * (2 * d_in + 2 * s.n_groups * s.d_state + nh) \
            + d_in * D
        return emb + L * per_layer
    if cfg.attn_type == "mla":
        m = cfg.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        attn = (D * m.q_lora_rank + m.q_lora_rank * cfg.n_heads * qk
                + D * (m.kv_lora_rank + m.qk_rope_head_dim)
                + m.kv_lora_rank * cfg.n_heads
                * (m.qk_nope_head_dim + m.v_head_dim)
                + cfg.n_heads * m.v_head_dim * D)
    else:
        attn = D * (cfg.n_heads * cfg.head_dim) * 2 \
            + D * (cfg.n_kv_heads * cfg.head_dim) * 2
    if cfg.family == "moe":
        moe = cfg.moe
        ff = 3 * D * moe.d_ff_expert * moe.top_k
        if moe.shared_expert:
            ff += 3 * D * moe.d_ff_expert
        ff += D * moe.n_experts                      # router
    else:
        n_mats = 3 if cfg.activation in ("swiglu", "geglu") else 2
        ff = n_mats * D * cfg.d_ff if cfg.d_ff else 0
    per_layer = attn + ff
    if cfg.family == "hybrid":
        rg = cfg.rglru
        W = rg.lru_width
        rec = D * W * 2 + 2 * W * W + W * D          # rglru block
        n_rec = sum(1 for k in cfg.rglru.pattern if k == "rglru")
        plen = len(cfg.rglru.pattern)
        frac_attn = (plen - n_rec) / plen
        per_layer = frac_attn * (attn + ff) + (1 - frac_attn) * (rec + ff)
    total_layers = L
    if cfg.family == "audio":
        total_layers = L + cfg.encdec.n_encoder_layers
        per_layer = per_layer + attn / 2             # cross-attn on dec half
    return emb + total_layers * per_layer


def attention_flops(cfg, shape) -> float:
    """Exact-ish attention MODEL_FLOPS (scores + PV, causal-halved)."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "ssm":
        return 0.0
    if cfg.attn_type == "mla":
        m = cfg.mla
        width = cfg.n_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim
                               + m.v_head_dim)
    else:
        width = cfg.n_heads * cfg.head_dim * 2          # scores + pv
    L_attn = cfg.n_layers
    ctx = S
    if cfg.family == "hybrid":
        rg = cfg.rglru
        plen = len(rg.pattern)
        n_attn = sum(1 for k in rg.pattern if k == "attn")
        L_attn = (cfg.n_layers // plen) * n_attn
        ctx = min(S, rg.window)
    if shape.mode == "decode":
        # one query token against the cached context
        window = cfg.long_context_window
        if shape.name == "long_500k" and window:
            ctx = min(ctx, window)
        fwd = 2.0 * B * ctx * width * L_attn
        return fwd
    causal = 0.5
    fwd = 2.0 * B * S * ctx * causal * width * L_attn
    if cfg.family == "audio":
        F = cfg.encdec.n_frames
        enc = 2.0 * B * F * F * width * cfg.encdec.n_encoder_layers
        cross = 2.0 * B * S * F * width * cfg.n_layers
        fwd += enc + cross
    return fwd * (3.0 if shape.mode == "train" else 1.0)


def model_flops(cfg, shape) -> float:
    n_act = active_params(cfg)
    attn = attention_flops(cfg, shape)
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens + attn
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens + attn
    # decode: one token per sequence
    return 2.0 * n_act * shape.global_batch + attn


# =====================================================================
# analytic decode→aggregate roofline (DESIGN.md §11.3): where the four
# server-aggregation variants sit against the HBM roof, from shapes alone
# =====================================================================
def decode_agg_roofline(cohort: int, n_chunks: int, latent: int,
                        hidden: Tuple[int, ...], chunk: int, *,
                        device_kind: str, n_buckets: int = 1,
                        dtype_bytes: int = 4) -> Dict[str, Dict]:
    """Place the chunked-AE decode→aggregate variants on the memory roofline.

    Every variant runs the same decoder math — ``cohort`` clients ×
    ``n_chunks`` chunks through ``latent → hidden... → chunk`` per bucket,
    ``n_buckets`` buckets per round — so FLOPs are identical; what differs
    is HBM traffic and launch count:

    * ``loop``    — per-client sequential decode + host reduce: every client
      materializes its full reconstruction to HBM and it is read back for
      the reduction; decoder params are re-read per client. C·B launches.
    * ``vmap``    — batched decode per bucket: params read once per bucket,
      but the (C, model) reconstruction block still round-trips HBM before
      the einsum. B launches.
    * ``fused``   — the per-bucket Pallas kernel (DESIGN.md §7.1): hidden
      activations round-trip at latent width, the chunk-wide expansion is
      reduced in-kernel, only the (model)-sized mean is written. B launches.
    * ``grouped`` — the ragged grouped launch (DESIGN.md §11.1): same
      traffic as ``fused`` minus repeated decoder-stack reads (each distinct
      decoder ships once into the stacked operand), in ONE launch.

    Returns per-variant dicts with ``flops``, ``hbm_bytes``,
    ``arith_intensity`` (FLOPs/byte), ``pct_of_roof`` (attainable FLOP/s at
    that intensity over the peak of ``device_kind``), ``bound`` and
    ``launches``, plus the machine constants used — all finite for any
    positive shapes
    (tests/test_roofline_decode_agg.py)."""
    assert cohort > 0 and n_chunks > 0 and latent > 0 and chunk > 0
    assert n_buckets > 0 and dtype_bytes > 0
    widths = (latent,) + tuple(hidden) + (chunk,)
    K = widths[-2]                                  # penultimate width
    # identical compute for every variant: 2mnk per layer matmul, per
    # (client, chunk) row, per bucket
    flops_per_row = sum(2.0 * a * b for a, b in zip(widths[:-1], widths[1:]))
    flops = n_buckets * cohort * n_chunks * flops_per_row
    param_bytes = sum((a * b + b) * dtype_bytes
                      for a, b in zip(widths[:-1], widths[1:]))
    z_bytes = n_buckets * cohort * n_chunks * latent * dtype_bytes
    model_bytes = n_buckets * n_chunks * chunk * dtype_bytes   # one mean
    recon_bytes = cohort * model_bytes          # C materialized decodes
    hidden_rt = n_buckets * cohort * n_chunks * K * dtype_bytes
    pk = peaks_for(device_kind)
    ridge = pk.flops_bf16 / pk.hbm_bw

    def variant(hbm_bytes: float, launches: int) -> Dict[str, float]:
        ai = flops / hbm_bytes
        attainable = min(pk.flops_bf16, ai * pk.hbm_bw)
        return {
            "flops": flops,
            "hbm_bytes": hbm_bytes,
            "arith_intensity": ai,
            "pct_of_roof": 100.0 * attainable / pk.flops_bf16,
            "bound": "memory" if ai < ridge else "compute",
            "launches": launches,
        }

    return {
        "shape": {"cohort": cohort, "n_chunks": n_chunks, "latent": latent,
                  "hidden": list(hidden), "chunk": chunk,
                  "n_buckets": n_buckets},
        "machine": {"device_kind": device_kind, "hbm_bw": pk.hbm_bw,
                    "peak_flops": pk.flops_bf16, "ridge_intensity": ridge},
        "loop": variant(
            z_bytes + n_buckets * cohort * param_bytes    # params per client
            + 2.0 * recon_bytes                           # write + read back
            + model_bytes,                                # mean write
            launches=cohort * n_buckets),
        "vmap": variant(
            z_bytes + n_buckets * param_bytes
            + 2.0 * recon_bytes + model_bytes,
            launches=n_buckets),
        "fused": variant(
            z_bytes + n_buckets * param_bytes
            + 2.0 * hidden_rt                             # latent-sided only
            + model_bytes,
            launches=n_buckets),
        "grouped": variant(
            z_bytes + param_bytes                         # deduped decoders
            + 2.0 * hidden_rt + model_bytes,
            launches=1),
    }
