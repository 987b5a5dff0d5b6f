"""Device self time per train step, in ms, of the N:M mask (the top_k sort
and scatter of masks_for_step) and its straight-through application,
forward and backward: the ``nm_mask`` region of the traced window
(``harness/scopes.py``)."""
from bench.harness import scopes


def read(ctx):
    return scopes.per_step(ctx, "nm_mask")
