"""Device self time per train step, in ms, of device operations under none
of the program's named scopes: the ``unscoped`` region of the traced
window (``harness/scopes.py``)."""
from bench.harness import scopes


def read(ctx):
    return scopes.per_step(ctx, "unscoped")
