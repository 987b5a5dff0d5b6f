"""A whole run with the timed path broken underneath comes out not correct.

These drive ``cells.run`` as ``run.py`` does, past its look for a chip, on
the test configuration: the unbroken run is correct, and each fault a
training cell can have (a step that returns its state unchanged; half of
the batch left out, the mean taken over the rest) fails the check, as does
the control (the reference one precision step down, fp8, in the program's
place) judged by the same check.  One-chip cells have no exchange between
chips.
"""
from __future__ import annotations

import time

import jax
import pytest

from bench.harness import cells, faults, spec, train
from bench.tests import tiny

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, name, seed, fault="none", keep=None):
    cell = spec.load_cell(name, root=root)
    with faults.FAULTS[fault]():
        return cells.run(cell, jax.devices()[:1], PEAKS, seed, 1.5, False,
                         time.perf_counter(), keep)


def test_train_run_is_correct_and_the_control_is_not(root):
    keep: dict = {}
    ok = _run(root, "tiny.train", 5, keep=keep)
    assert ok["correct"], ok["checks"]
    assert set(ok["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(ok)[-1] == "checks"
    cell = spec.load_cell("tiny.train", root=root)
    ctl = spec.reference(cell.config).train_steps(
        cell.config, 5, keep["batches"], train.opt_args(cell.mix),
        mode="fp8", rows_per_block=cell.mix["reference_rows"])
    lim = cell.config["limits"]
    correct, checks = train.judge(
        train.compare(ctl, keep["ref"], lim["train_change_trim"]),
        ctl["phase2"], lim)
    assert not correct, checks


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_train_run_with_a_fault_is_not_correct(root, fault):
    bad = _run(root, "tiny.train", 6, fault=fault)
    assert not bad["correct"], bad["checks"]
    if fault == "frozen":
        # unchanged parameters read 1 on every leaf the reference moves
        assert bad["checks"]["train_change_norm_gap"]["value"] > 0.9
