"""Device self time per train step, in ms, of the optimizer (global norm,
clipping, the STEP moments with the frozen preconditioner, the
AutoSwitch sample, the update): the ``optimizer`` region of the traced
window (``harness/scopes.py``)."""
from bench.harness import scopes


def read(ctx):
    return scopes.per_step(ctx, "optimizer")
