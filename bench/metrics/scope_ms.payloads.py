"""Device milliseconds per round under the serve step's ``serve.payloads``
scope (``bench/scopes.py``): the PRNG keys and the synthetic cohort of
encoded payloads, work that only the simulation does (a deployed server
receives these bytes). Moves ``updates_per_s``. Returns nothing where no
operation of the window lies under the scope."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "serve.payloads")
