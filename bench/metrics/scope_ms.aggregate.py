"""Device milliseconds per round under the serve step's ``serve.aggregate``
scope (``bench/scopes.py``): the decode→aggregate, its layout copies and
kernels alike, and the global model's update. Moves ``updates_per_s``.
Returns nothing where no operation of the window lies under the scope."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "serve.aggregate")
