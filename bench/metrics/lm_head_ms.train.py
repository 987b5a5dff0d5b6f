"""Device self time per train step, in ms, of the LM head (embedding
lookup, final norm, logits and the loss over the vocabulary), forward
and backward: the ``lm_head`` region of the traced window
(``harness/scopes.py``)."""
from bench.harness import scopes


def read(ctx):
    return scopes.per_step(ctx, "lm_head")
