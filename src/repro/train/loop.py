"""The training loop: recipe + STEP optimizer + model, with fault tolerance.

``make_train_step`` builds the single jitted step implementing the paper's
Algorithm 1 end-to-end:

    masks  = recipe.masks_for_step(params, phase2)      # Π_t (or 1s)
    grads  = ∇ loss(Π_t ⊙ w; ζ_t)                        # STE forward
    grads += λ(1-Π_t)⊙w                                  # SR-STE (if recipe)
    grads  = pmean(compress(grads))                      # DP (+1-bit in p2)
    updates, opt = step_optimizer.update(grads, ...)     # 2-phase Adam
                                                         #  + AutoSwitch

:class:`Trainer` wraps the loop with checkpoint/auto-resume (kill -9 safe),
eval, telemetry, and a straggler deadline hook. The same Trainer object runs
the smoke tests, the paper-reproduction benchmarks, and (with pjit shardings
from launch/) the production meshes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.recipes import Recipe, RecipeState
from repro.core.step_optimizer import StepConfig, StepState, step_optimizer
from repro.optim.base import GradientTransformation, apply_updates
from repro.optim.compression import (
    CompressionState,
    ef_sign_compress,
    init_compression_state,
)
from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DataIterator, IteratorState
from repro.utils.tree import global_norm


class TrainState(NamedTuple):
    params: Any
    opt: Any  # StepState (or any GradientTransformation state)
    recipe: RecipeState
    comp: Optional[CompressionState]
    rng: jnp.ndarray
    data_state: jnp.ndarray  # (2,) int32: (seed, step) mirror of the iterator


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    total_steps: int = 1000
    log_every: int = 50
    ckpt_every: int = 200
    eval_every: int = 0
    grad_clip: Optional[float] = 1.0
    compress_phase2: bool = False  # 1-bit EF gradient compression in phase 2
    donate: bool = True


def make_train_step(
    loss_fn: Callable[..., tuple[jnp.ndarray, dict]],
    recipe: Recipe,
    opt: GradientTransformation,
    *,
    grad_clip: Optional[float] = 1.0,
    compress_phase2: bool = False,
    axis_name: Optional[str] = None,
) -> Callable[[TrainState, Any], tuple[TrainState, dict]]:
    """Build the jittable train step.

    ``loss_fn(params, batch) -> (loss, metrics)``; the recipe decides what
    the model sees. ``axis_name``: if set, gradients are psum-averaged over
    it (for shard_map/pmap use; under pjit the mean is implicit).
    """

    def step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        phase2 = getattr(state.opt, "phase2", jnp.zeros((), jnp.bool_))
        with jax.named_scope("nm_mask"):
            mask, active, rstate = recipe.masks_for_step(
                state.params, state.recipe, phase2
            )

        def masked_loss(p):
            fp = recipe.forward_params(p, mask, active)
            return loss_fn(fp, batch)

        (loss, metrics), grads = jax.value_and_grad(masked_loss, has_aux=True)(
            state.params
        )
        grads = recipe.grad_postprocess(grads, state.params, mask, active)

        comp = state.comp
        if compress_phase2 and comp is not None:
            grads, comp = ef_sign_compress(grads, comp, phase2)
        if axis_name is not None:
            grads = jax.lax.pmean(grads, axis_name)
            loss = jax.lax.pmean(loss, axis_name)

        with jax.named_scope("optimizer"):
            gnorm = global_norm(grads)
            if grad_clip is not None:
                scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-12))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            updates, ostate = opt.update(grads, state.opt, state.params)
            params = apply_updates(state.params, updates)
        new_state = TrainState(
            params=params,
            opt=ostate,
            recipe=rstate,
            comp=comp,
            rng=jax.random.fold_in(state.rng, 1),
            data_state=state.data_state + jnp.array([0, 1], jnp.int32),
        )
        metrics = dict(metrics)
        metrics.update(
            loss=loss,
            grad_norm=gnorm,
            phase2=phase2.astype(jnp.int32),
            mask_active=active.astype(jnp.int32),
        )
        if hasattr(ostate, "z_bar"):
            metrics["z_bar"] = ostate.z_bar
            metrics["t0"] = ostate.t0
        return new_state, metrics

    return step


class TracedStep:
    """A jitted train step whose every call runs inside a host span
    ``train.step`` (``jax.profiler.StepTraceAnnotation``, numbered from 1),
    so a profile places each step's dispatch on the device's clock.  With no
    profiler session open the span costs about a microsecond."""

    def __init__(self, fn: Callable):
        self._fn = fn
        self.lower = fn.lower
        self._calls = 0

    def __call__(self, state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        self._calls += 1
        with jax.profiler.StepTraceAnnotation("train.step", step_num=self._calls):
            return self._fn(state, batch)


@dataclasses.dataclass
class Trainer:
    """Fault-tolerant driver around ``make_train_step``."""

    loss_fn: Callable[..., tuple[jnp.ndarray, dict]]
    recipe: Recipe
    step_cfg: StepConfig
    data: DataIterator
    cfg: TrainerConfig = dataclasses.field(default_factory=TrainerConfig)
    checkpointer: Optional[Checkpointer] = None
    eval_fn: Optional[Callable[[Any, int], dict]] = None
    log_fn: Callable[[int, dict], None] = lambda step, m: None

    def __post_init__(self):
        self.opt = step_optimizer(self.step_cfg)
        self._step = TracedStep(
            jax.jit(
                make_train_step(
                    self.loss_fn,
                    self.recipe,
                    self.opt,
                    grad_clip=self.cfg.grad_clip,
                    compress_phase2=self.cfg.compress_phase2,
                ),
                donate_argnums=(0,) if self.cfg.donate else (),
            )
        )

    def init_state(self, params: Any, seed: int = 0) -> TrainState:
        # the jitted step donates its input state; copy the caller's params so
        # they survive the first step (callers reuse them for baselines/evals)
        params = jax.tree_util.tree_map(jnp.array, params)
        comp = (
            init_compression_state(params) if self.cfg.compress_phase2 else None
        )
        return TrainState(
            params=params,
            opt=self.opt.init(params),
            recipe=self.recipe.init_state(params),
            comp=comp,
            rng=jax.random.PRNGKey(seed),
            data_state=jnp.array([self.data.state.seed, self.data.state.step], jnp.int32),
        )

    # -- fault-tolerant run ---------------------------------------------------

    def restore_or_init(self, params: Any, seed: int = 0) -> tuple[TrainState, int]:
        state = self.init_state(params, seed)
        start = 0
        if self.checkpointer is not None:
            latest = self.checkpointer.latest_step()
            if latest is not None:
                state, meta = self.checkpointer.load(state)
                start = int(meta.get("step", latest))
                # resynchronize the data stream with the restored state
                ds = jax.device_get(state.data_state)
                self.data.set_state(IteratorState(int(ds[0]), int(ds[1])))
        return state, start

    def run(
        self, params: Any, seed: int = 0, step_timeout: Optional[float] = None
    ) -> tuple[TrainState, list[dict]]:
        """Train until total_steps, checkpointing and auto-resuming.

        ``step_timeout``: straggler deadline in seconds; a step exceeding it
        is logged (on a real cluster the launcher uses this signal to evict
        the slow host and restart from the last checkpoint — the elastic
        restore path exercised in tests).

        A logged step's ``step_time_s`` is the mean step time since the
        host last waited for the device (the previous logged step or
        checkpoint save): steps are dispatched ahead, so one step timed
        alone would carry the steps still queued before it."""
        state, start = self.restore_or_init(params, seed)
        history: list[dict] = []
        t_sync, since = time.perf_counter(), 0
        for step in range(start, self.cfg.total_steps):
            batch = next(self.data)
            state, metrics = self._step(state, batch)
            since += 1
            if self.cfg.log_every and (step % self.cfg.log_every == 0):
                metrics = {
                    k: float(v) if jnp.ndim(v) == 0 else v for k, v in metrics.items()
                }
                metrics["step"] = step
                now = time.perf_counter()
                dt = (now - t_sync) / since
                t_sync, since = now, 0
                metrics["step_time_s"] = dt
                if step_timeout and dt > step_timeout:
                    metrics["straggler"] = True
                history.append(metrics)
                self.log_fn(step, metrics)
            if (
                self.checkpointer is not None
                and self.cfg.ckpt_every
                and step > 0
                and step % self.cfg.ckpt_every == 0
            ):
                self.checkpointer.save(step, state, {"step": step})
                t_sync, since = time.perf_counter(), 0
            if self.eval_fn is not None and self.cfg.eval_every and step % self.cfg.eval_every == 0:
                history.append({"step": step, **self.eval_fn(state.params, step)})
        if self.checkpointer is not None:
            self.checkpointer.save(self.cfg.total_steps, state, {"step": self.cfg.total_steps})
        return state, history
