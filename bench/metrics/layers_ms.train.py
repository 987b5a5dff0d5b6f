"""Device self time per train step, in ms, of the layer stack outside
attention and MLP (norms, residual adds, the layer scan's carry and its
stacking of saved activations): the ``layers`` region of the traced
window (``harness/scopes.py``)."""
from bench.harness import scopes


def read(ctx):
    return scopes.per_step(ctx, "layers")
