"""The STEP optimizer (paper Algorithm 1): two-phase Adam with preconditioned
variance for learning N:M masks from scratch.

Phase 1 (precondition): plain Adam; the variance ``v`` is updated every step
and AutoSwitch monitors the per-coordinate variance change. No mask is
applied in the forward pass.

Phase 2 (mask learning): the bias-corrected variance at the switch step is
frozen into the preconditioner ``P* = sqrt(v̂_{t0}) + eps`` and never updated
again; only the momentum keeps integrating the (STE) gradients:

    w_{t+1} = w_t - γ_t * m̂_{t+1} / P*            (Algorithm 1, line 20)

The whole state machine is branchless-traced (``jnp.where`` on a phase flag),
so a single jitted train step covers both phases, the switch happens
on-device with no host synchronization, and checkpoints capture the phase
exactly. ``lax.cond`` is used only where the phases differ in *work*
(the mask computation — see recipes.py), not in the optimizer itself, since
the Adam math is elementwise and cheap relative to the model.

Ablation hooks (paper §6):
- ``switch_at``: fixed switching step instead of AutoSwitch (Ablation III).
- ``update_v_in_phase2``: keep updating v during mask learning (Ablation IV —
  the paper shows this *hurts*; we reproduce that).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.autoswitch import (
    AutoSwitchConfig,
    AutoSwitchState,
    autoswitch_step,
    init_autoswitch,
    variance_change_sample,
)
from repro.optim.base import GradientTransformation

Schedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


@dataclasses.dataclass(frozen=True)
class StepConfig:
    learning_rate: Schedule = 1e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    autoswitch: AutoSwitchConfig = dataclasses.field(
        default_factory=AutoSwitchConfig
    )
    switch_at: Optional[int] = None  # fixed t0 (overrides AutoSwitch)
    update_v_in_phase2: bool = False  # Ablation IV (paper shows: keep False)

    def __post_init__(self):
        # keep the AutoSwitch window consistent with beta2 unless overridden
        if self.autoswitch.beta2 != self.b2:
            object.__setattr__(
                self,
                "autoswitch",
                dataclasses.replace(self.autoswitch, beta2=self.b2),
            )


class StepState(NamedTuple):
    step: jnp.ndarray  # int32: global step t
    m: Any  # first moment
    v: Any  # second moment (live during phase 1; frozen afterwards)
    precond: Any  # P* = sqrt(v̂_{t0}) + eps (ones until the switch)
    phase2: jnp.ndarray  # bool: inside the mask-learning phase?
    t0: jnp.ndarray  # int32: switch step (0 until it happens)
    autoswitch: AutoSwitchState
    z_bar: jnp.ndarray  # last window-mean of the variance change (telemetry)


def _lr(schedule: Schedule, step: jnp.ndarray) -> jnp.ndarray:
    if callable(schedule):
        return schedule(step)
    return jnp.asarray(schedule, jnp.float32)


def step_optimizer(cfg: StepConfig) -> GradientTransformation:
    """Build STEP as a GradientTransformation.

    ``update(grads, state, params)`` expects the gradients already computed
    through the recipe's forward masking (Eq. 8/9 — see recipes.py); the
    optimizer itself only implements the two-phase moment logic.
    """
    asw_cfg = cfg.autoswitch

    def init(params) -> StepState:
        zeros = lambda: jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, dtype=jnp.float32), params
        )
        ones = jax.tree_util.tree_map(
            lambda p: jnp.ones_like(p, dtype=jnp.float32), params
        )
        return StepState(
            step=jnp.zeros((), jnp.int32),
            m=zeros(),
            v=zeros(),
            precond=ones,
            phase2=jnp.zeros((), jnp.bool_),
            t0=jnp.zeros((), jnp.int32),
            autoswitch=init_autoswitch(asw_cfg),
            z_bar=jnp.asarray(jnp.inf, jnp.float32),
        )

    def update(grads, state: StepState, params=None):
        t = state.step + 1
        tf = t.astype(jnp.float32)
        in_p2 = state.phase2  # phase flag *entering* this step
        b1, b2, eps = cfg.b1, cfg.b2, cfg.eps

        # --- momentum: updated identically in both phases (Alg.1 l.4 & l.18)
        m = jax.tree_util.tree_map(
            lambda mm, g: b1 * mm + (1 - b1) * g.astype(jnp.float32),
            state.m,
            grads,
        )
        bc1 = 1 - b1**tf

        # --- variance: live in phase 1, frozen in phase 2 (unless ablating)
        def v_new_leaf(vv, g):
            nv = b2 * vv + (1 - b2) * jnp.square(g.astype(jnp.float32))
            if cfg.update_v_in_phase2:
                return nv
            return jnp.where(in_p2, vv, nv)

        v = jax.tree_util.tree_map(v_new_leaf, state.v, grads)
        bc2 = 1 - b2**tf

        # --- AutoSwitch sampling (phase-1 signal; harmless but unused in p2)
        with jax.named_scope("autoswitch"):
            z_t = variance_change_sample(grads, state.v, asw_cfg)
            asw_state, z_bar, crit = autoswitch_step(
                state.autoswitch, z_t, t, asw_cfg
            )
        if cfg.switch_at is not None:
            crit = t >= cfg.switch_at
        switch_now = jnp.logical_and(jnp.logical_not(in_p2), crit)
        phase2 = jnp.logical_or(in_p2, crit)
        t0 = jnp.where(switch_now, t, state.t0)

        # --- freeze the preconditioner at the switch step (Alg.1 l.11)
        precond = jax.tree_util.tree_map(
            lambda pc, vv: jnp.where(switch_now, jnp.sqrt(vv / bc2) + eps, pc),
            state.precond,
            v,
        )

        # --- the update direction
        def direction(mm, vv, pc):
            live = (mm / bc1) / (jnp.sqrt(vv / bc2) + eps)  # phase-1 Adam
            frozen = (mm / bc1) / pc  # phase-2 preconditioned (Alg.1 l.20)
            if cfg.update_v_in_phase2:
                # Ablation IV: even in phase 2 use the live v̂
                return jnp.where(in_p2, (mm / bc1) / (jnp.sqrt(vv / bc2) + eps), live)
            return jnp.where(in_p2, frozen, live)

        d = jax.tree_util.tree_map(direction, m, v, precond)
        lr = _lr(cfg.learning_rate, t)
        updates = jax.tree_util.tree_map(lambda u: -lr * u, d)

        return updates, StepState(
            step=t,
            m=m,
            v=v,
            precond=precond,
            phase2=phase2,
            t0=t0,
            autoswitch=asw_state,
            z_bar=z_bar,
        )

    return GradientTransformation(init, update)


def phase2_flag(state: StepState) -> jnp.ndarray:
    """The traced bool the recipe layer reads to decide whether to mask."""
    return state.phase2
