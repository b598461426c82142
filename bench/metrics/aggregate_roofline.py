"""The decode→aggregate layer's share of its roofline: the least time of
the stage's required work (``bench/work.py``, the same count as
``decode_agg_roofline``) over the device time per round of every operation
under the serve step's ``serve.aggregate`` scope (``bench/scopes.py``):
kernels, layout copies and the global update alike. Moves
``updates_per_s``. Returns nothing where no operation of the window lies
under the scope, or where the scope map fails its checks."""
from bench import peaks, scopes, work


def read(ctx):
    sec = scopes.scope_seconds(ctx, "serve.aggregate")
    if not sec:
        return None
    c, codec = ctx.counters, ctx.cell.codec
    least = peaks.least_seconds(
        work.stage_flops(codec, c["buffer_k"]),
        work.stage_bytes(codec, codec["size"], c["buffer_k"]),
        ctx.device_kind)
    return 100.0 * least / (sec / c["rounds"])
