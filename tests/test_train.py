"""Trainer: fault tolerance (kill/resume exactness), compression, data state."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.checkpoint import Checkpointer
from repro.data import DataIterator, SyntheticTask
from repro.train import Trainer, TrainerConfig

jax.config.update("jax_platform_name", "cpu")

TASK = SyntheticTask(seed=11, heavy_tail=False)
SCFG = core.StepConfig(
    learning_rate=2e-3,
    b2=0.99,
    autoswitch=core.AutoSwitchConfig(eps=1e-4, window=20, t_min=10, t_max=60),
)


def _loss(p, batch):
    x, y = batch
    l = TASK.loss(p, x, y)
    return l, {"mse": l}


def _make_trainer(tmpdir, total, ckpt_every=20, **kw):
    recipe = core.make_recipe("step", core.SparsityConfig(default=core.NMSparsity(2, 4)))
    data = DataIterator(batch_fn=lambda s, bs: TASK.batch(s, bs), batch_size=32, prefetch=0)
    return Trainer(
        _loss,
        recipe,
        SCFG,
        data,
        TrainerConfig(total_steps=total, log_every=0, ckpt_every=ckpt_every, **kw),
        checkpointer=Checkpointer(str(tmpdir), keep_last=3) if tmpdir else None,
    )


def test_loss_decreases_and_switches(tmp_path):
    tr = _make_trainer(None, 120)
    params = TASK.student_init(jax.random.PRNGKey(0))
    state, _ = tr.run(params)
    assert bool(state.opt.phase2)
    x, y = TASK.batch(10_000, 256)
    final = float(TASK.loss(tr.recipe.export_sparse(state.params), x, y))
    initial = float(TASK.loss(params, x, y))
    assert final < initial * 0.3


def test_kill_and_resume_is_exact(tmp_path):
    """A restart from checkpoint must reproduce the uninterrupted run bit-for-
    bit (same data stream, same optimizer state, same phase flags)."""
    params = TASK.student_init(jax.random.PRNGKey(1))
    # uninterrupted run to 60
    tr_full = _make_trainer(tmp_path / "a", 60, ckpt_every=25)
    s_full, _ = tr_full.run(params)
    # interrupted: run to 50 (checkpoint lands at 50), then "crash"; resume to 60
    tr1 = _make_trainer(tmp_path / "b", 50, ckpt_every=25)
    tr1.run(params)
    tr2 = _make_trainer(tmp_path / "b", 60, ckpt_every=25)
    s_resumed, _ = tr2.run(params)
    np.testing.assert_allclose(
        np.asarray(s_full.params["fc1"]["w"]),
        np.asarray(s_resumed.params["fc1"]["w"]),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(s_full.opt.v["fc1"]["w"]),
        np.asarray(s_resumed.opt.v["fc1"]["w"]),
        rtol=1e-6,
    )
    assert int(s_full.opt.t0) == int(s_resumed.opt.t0)


def test_resume_restores_data_stream(tmp_path):
    tr1 = _make_trainer(tmp_path, 30, ckpt_every=10)
    params = TASK.student_init(jax.random.PRNGKey(2))
    tr1.run(params)
    tr2 = _make_trainer(tmp_path, 40, ckpt_every=10)
    state, start = tr2.restore_or_init(params)
    assert start == 30
    assert tr2.data.state.step == 30


def test_ef_compression_activates_in_phase2_only():
    recipe = core.make_recipe("step", core.SparsityConfig(default=core.NMSparsity(2, 4)))
    data = DataIterator(batch_fn=lambda s, bs: TASK.batch(s, bs), batch_size=32, prefetch=0)
    tr = Trainer(
        _loss, recipe, SCFG, data,
        TrainerConfig(total_steps=80, log_every=0, ckpt_every=0, compress_phase2=True),
    )
    params = TASK.student_init(jax.random.PRNGKey(3))
    state, _ = tr.run(params)
    assert state.comp is not None
    res = np.asarray(state.comp.residual["fc1"]["w"])
    if bool(state.opt.phase2):
        assert np.abs(res).sum() > 0  # error feedback engaged
    # training still converged reasonably
    x, y = TASK.batch(10_001, 256)
    assert float(TASK.loss(state.params, x, y)) < 1.0


def test_straggler_deadline_flag():
    tr = _make_trainer(None, 3)
    tr.cfg = dataclasses.replace(tr.cfg, log_every=1)
    params = TASK.student_init(jax.random.PRNGKey(4))
    _, hist = tr.run(params, step_timeout=1e-9)  # everything is a straggler
    assert any(m.get("straggler") for m in hist)


def test_logged_step_time_is_the_mean_since_the_last_sync(monkeypatch):
    """Steps run ahead of the host, so a logged step's time is the host
    time since the previous logged step over the steps in between."""
    import types

    import repro.train.loop as loop

    tr = _make_trainer(None, 5)
    tr.cfg = dataclasses.replace(tr.cfg, log_every=2)
    clock = iter([0.0, 4.0, 10.0, 20.0])  # loop start, then steps 0, 2, 4
    monkeypatch.setattr(loop, "time", types.SimpleNamespace(
        perf_counter=lambda: next(clock)))
    _, hist = tr.run(TASK.student_init(jax.random.PRNGKey(5)))
    assert [m["step"] for m in hist] == [0, 2, 4]
    assert [m["step_time_s"] for m in hist] == [4.0, 3.0, 5.0]


def test_train_step_keeps_lower_and_counts_its_calls():
    tr = _make_trainer(None, 1)
    state = tr.init_state(TASK.student_init(jax.random.PRNGKey(6)))
    batch = next(tr.data)
    assert tr._step.lower(state, batch).compile() is not None
    state, m = tr._step(state, batch)
    state, m = tr._step(state, next(tr.data))
    assert tr._step._calls == 2 and np.isfinite(float(m["loss"]))
