"""Device milliseconds per round under the serve step's ``serve.pop`` scope
(``bench/scopes.py``): the first-K pop's sort, the clock and the
staleness weights. Moves ``updates_per_s``. Returns nothing where no
operation of the window lies under the scope."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "serve.pop")
