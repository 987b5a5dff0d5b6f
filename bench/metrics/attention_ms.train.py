"""Device self time per train step, in ms, of attention (qkv and output
projections, rope, the key-chunk scan of chunked_attention), forward,
backward and remat recompute: the ``attention`` region of the traced
window (``harness/scopes.py``)."""
from bench.harness import scopes


def read(ctx):
    return scopes.per_step(ctx, "attention")
