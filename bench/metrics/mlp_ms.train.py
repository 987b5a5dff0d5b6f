"""Device self time per train step, in ms, of the MLP (both projections and
the activation), forward, backward and remat recompute: the ``mlp``
region of the traced window (``harness/scopes.py``)."""
from bench.harness import scopes


def read(ctx):
    return scopes.per_step(ctx, "mlp")
