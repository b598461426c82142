"""Analytic decode→aggregate roofline (repro.roofline.analysis, DESIGN.md
§11.3): the four server-aggregation variants produce finite arithmetic
intensities and %-of-roof placements on synthetic shapes, with the traffic
ordering the kernels were built to achieve."""
import math

import pytest

from repro.roofline.analysis import decode_agg_roofline
from repro.roofline.peaks import PEAKS, V5E, peaks_for

VARIANTS = ("loop", "vmap", "fused", "grouped")

SHAPES = [
    dict(cohort=8, n_chunks=128, latent=8, hidden=(32,), chunk=256),
    dict(cohort=64, n_chunks=120, latent=4, hidden=(32,), chunk=256,
         n_buckets=2),
    dict(cohort=1, n_chunks=1, latent=2, hidden=(), chunk=8),
    dict(cohort=256, n_chunks=4096, latent=8, hidden=(64, 32), chunk=512,
         n_buckets=4),
]


@pytest.mark.parametrize("shape", SHAPES)
def test_all_variants_finite_and_positive(shape):
    roof = decode_agg_roofline(**shape, device_kind=V5E)
    for v in VARIANTS:
        r = roof[v]
        for field in ("flops", "hbm_bytes", "arith_intensity",
                      "pct_of_roof"):
            assert math.isfinite(r[field]) and r[field] > 0, (v, field, r)
        assert 0.0 < r["pct_of_roof"] <= 100.0
        assert r["bound"] in ("memory", "compute")
        assert r["launches"] >= 1
    assert math.isfinite(roof["machine"]["ridge_intensity"])


def test_variant_ordering_matches_design():
    roof = decode_agg_roofline(cohort=64, n_chunks=128, latent=8,
                               hidden=(32,), chunk=256, n_buckets=2,
                               device_kind=V5E)
    # same decoder math everywhere
    assert len({roof[v]["flops"] for v in VARIANTS}) == 1
    # traffic strictly shrinks loop → vmap → fused → grouped (the fused
    # paths never materialize the C× reconstruction block; the grouped
    # launch additionally dedupes decoder-stack reads)
    assert roof["loop"]["hbm_bytes"] > roof["vmap"]["hbm_bytes"]
    assert roof["vmap"]["hbm_bytes"] > roof["fused"]["hbm_bytes"]
    assert roof["fused"]["hbm_bytes"] > roof["grouped"]["hbm_bytes"]
    # so intensity (and roof placement) strictly improves
    assert (roof["grouped"]["arith_intensity"]
            > roof["fused"]["arith_intensity"]
            > roof["vmap"]["arith_intensity"])
    # launch accounting: C·B, B, B, 1
    assert roof["loop"]["launches"] == 64 * 2
    assert roof["vmap"]["launches"] == roof["fused"]["launches"] == 2
    assert roof["grouped"]["launches"] == 1


def test_rejects_degenerate_shapes():
    with pytest.raises(AssertionError):
        decode_agg_roofline(cohort=0, n_chunks=1, latent=1, hidden=(),
                            chunk=8, device_kind=V5E)


def test_peaks_keyed_by_device_kind():
    """The roof is the named chip's: v5e's published peaks, and an error
    (never a default) for a kind the table does not hold."""
    roof = decode_agg_roofline(cohort=8, n_chunks=4, latent=8, hidden=(32,),
                               chunk=256, device_kind=V5E)
    assert roof["machine"]["peak_flops"] == PEAKS[V5E].flops_bf16 == 197e12
    assert roof["machine"]["hbm_bw"] == 819e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            peaks_for(kind)
        with pytest.raises(KeyError):
            decode_agg_roofline(cohort=8, n_chunks=4, latent=8,
                                hidden=(32,), chunk=256, device_kind=kind)
