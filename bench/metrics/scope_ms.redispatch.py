"""Device milliseconds per round under the serve step's
``serve.redispatch`` scope (``bench/scopes.py``): the latency draw and
the scatters that re-dispatch the cohort. Moves ``updates_per_s``.
Returns nothing where no operation of the window lies under the scope."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "serve.redispatch")
