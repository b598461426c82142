"""The decode→aggregate kernels' share of their roofline: the least time
of the stage's required work (``bench/work.py``: hidden decoder layers per
client, the weighted client reduction, the last layer once; payloads,
decoder and mean update moved once) over the summed device time, per
round, of the stage's Pallas kernels named below. Moves ``updates_per_s``.
Returns nothing where none of the kernels ran (the FC AE path has none)."""
from bench import peaks, work

# Pallas kernels of the stage, by the instruction names XLA gives their
# custom calls in v5e traces: the hidden stack (kernels/fused_dense.py),
# the weighted last layer (kernels/fused_decode_agg.py) and the q8
# dequantization (kernels/quantize.py::dequantize_blocks_2d)
KERNELS = ("fused_dense", "fused_decode_agg", "dequantize_blocks_2d")


def read(ctx):
    sec = ctx.summary.op_seconds(lambda n: n.split(".")[0] in KERNELS)
    if sec <= 0.0:
        return None
    c, codec = ctx.counters, ctx.cell.codec
    least = peaks.least_seconds(
        work.stage_flops(codec, c["buffer_k"]),
        work.stage_bytes(codec, codec["size"], c["buffer_k"]),
        ctx.device_kind)
    return 100.0 * least / (sec / c["rounds"])
