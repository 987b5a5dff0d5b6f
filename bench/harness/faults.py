"""Faults planted in the timed path, to show that ``correct`` catches them.

Each is a context manager that patches the program where the fault would
live; the patch must be in place before the program traces the function.
Used by the fault tests and by ``tools/readings.py`` on the chip.

- ``half_batch``: the loss sees only the first half of the batch rows, the
  mean taken over the rest.
- ``frozen``: the train step returns the parameters unchanged.
"""
from __future__ import annotations

import contextlib
import unittest.mock


@contextlib.contextmanager
def half_batch():
    from repro.models import model

    real = model.loss_fn

    def half(params, cfg, batch, **k):
        n = batch["tokens"].shape[0] // 2
        return real(params, cfg, {key: v[:n] for key, v in batch.items()}, **k)

    with unittest.mock.patch.object(model, "loss_fn", half):
        yield


@contextlib.contextmanager
def frozen():
    from repro.train import loop

    with unittest.mock.patch.object(loop, "apply_updates", lambda p, u: p):
        yield


FAULTS = {"none": contextlib.nullcontext, "half_batch": half_batch, "frozen": frozen}
