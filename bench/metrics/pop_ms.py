"""Device milliseconds per round of the arrival pop, the ``(time, seq)``
sort of ``repro.core.arrival.pop_k_device``: the summed device time of the
operations named below, over the rounds completed in the traced window.
Moves ``updates_per_s``. Returns nothing where no such operation ran."""

# XLA names the sort instruction ``sort`` (``sort.<n>`` where there are
# several); as seen in v5e traces of these cells
NAMES = ("sort",)


def read(ctx):
    sec = ctx.summary.op_seconds(lambda n: n.split(".")[0] in NAMES)
    if sec <= 0.0:
        return None
    return 1e3 * sec / ctx.counters["rounds"]
