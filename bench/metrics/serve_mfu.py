"""Whole serve round's share of the chip's peak: the least time one
round's required work could take (``bench/work.py``: FLOPs over the bf16
peak or bytes over HBM bandwidth, whichever is larger) over the measured
time per round, the traced window divided by the rounds completed in it.
Moves ``updates_per_s``."""
from bench import peaks, work


def read(ctx):
    s, c = ctx.summary, ctx.counters
    codec, k = ctx.cell.codec, c["buffer_k"]
    least = peaks.least_seconds(
        work.round_flops(codec, k),
        work.round_bytes(codec, codec["size"], c["population"], k),
        ctx.device_kind)
    return 100.0 * least / (s.window_s / c["rounds"])
