"""Sparsity training recipes: dense / STE / SR-STE / ASP / Decaying-Mask / STEP.

A recipe decides (a) which weights are fed into the model's forward pass at
each step (masked or not, straight-through or not) and (b) how the raw
gradients are post-processed (SR-STE's decay term). The optimizer is chosen
independently (Adam, momentum SGD, or the STEP two-phase optimizer), matching
the paper's framing where SR-STE×SGD works but SR-STE×Adam fails and
STEP = STE recipe + preconditioned Adam fixes it.

All recipe logic is jit-traceable: phase switches are traced booleans, the
Decaying-Mask N-schedule is a traced integer, and the ASP one-shot prune is a
``jnp.where`` latch. ``lax.cond`` guards the mask computation so the
precondition phase pays nothing for masks it does not use.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import masking
from repro.core.masking import NMSparsity
from repro.core.sparsity_config import SparsityConfig, maskable_map
from repro.utils.tree import tree_map_with_name, tree_paths

RECIPES = ("dense", "ste", "sr_ste", "asp", "decay", "step", "step_sr")


class RecipeState(NamedTuple):
    """Traced per-recipe state carried in the train state."""

    step: jnp.ndarray  # int32 (recipes keep their own count: robust to resume)
    fixed_mask: Any  # ASP's one-shot mask (ones until pruned); () otherwise
    pruned: jnp.ndarray  # bool: ASP latch


@dataclasses.dataclass(frozen=True)
class Recipe:
    """A sparsity training recipe bound to a SparsityConfig.

    kind:
      dense    — no masking ever (paper's "Dense" row).
      ste      — mask every step, straight-through gradients (Eq. 8).
      sr_ste   — ste + λ(1−Π)⊙w gradient decay (Eq. 9, Zhou et al.).
      asp      — dense until ``prune_at``; then one-shot magnitude mask,
                 frozen, with true masked gradients (Mishra et al.).
      decay    — dense until ``dense_until``; then STE with N decaying
                 (M-1) → M/2 → M/4 → … → target N every ``decay_interval``
                 steps (Kao et al.).
      step     — mask only in the optimizer's phase 2 (Algorithm 1); pairs
                 with ``core.step_optimizer``. Plain STE in phase 2.
      step_sr  — STEP whose phase-2 gradients also carry the SR-STE term.
    """

    kind: str = "step"
    sparsity: SparsityConfig = dataclasses.field(default_factory=SparsityConfig)
    sr_lambda: float = 2e-4  # SR-STE λ (paper uses SR-STE's tuned value)
    prune_at: int = 0  # ASP: one-shot prune step
    dense_until: int = 0  # decay: length of dense warmup
    decay_interval: int = 100  # decay: steps between N reductions

    def __post_init__(self):
        if self.kind not in RECIPES:
            raise ValueError(f"unknown recipe {self.kind!r}; choose from {RECIPES}")

    # -- state ---------------------------------------------------------------

    def init_state(self, params: Any) -> RecipeState:
        if self.kind == "asp":
            fixed = jax.tree_util.tree_map(lambda p: jnp.ones_like(p), params)
        else:
            fixed = ()
        return RecipeState(
            step=jnp.zeros((), jnp.int32),
            fixed_mask=fixed,
            pruned=jnp.zeros((), jnp.bool_),
        )

    # -- masks ---------------------------------------------------------------

    def _mask_tree(self, params: Any, n_override: Optional[jnp.ndarray] = None) -> Any:
        """Compute the N:M mask for every maskable leaf (ones elsewhere)."""

        def leaf(name, p):
            pat = self.sparsity.pattern_for(name, tuple(p.shape))
            if pat is None:
                return jnp.ones_like(p)
            if n_override is not None:
                n_eff = jnp.minimum(
                    jnp.maximum(n_override, pat.n), pat.m
                )  # decay floor = target N
                return masking.nm_mask_dynamic(p, n_eff, pat.m, pat.group_axis)
            return masking.nm_mask(p, pat.n, pat.m, pat.group_axis)

        return tree_map_with_name(leaf, params)

    def _ones_tree(self, params: Any) -> Any:
        return jax.tree_util.tree_map(lambda p: jnp.ones_like(p), params)

    def _decay_n(self, t: jnp.ndarray, m: int) -> jnp.ndarray:
        """Kao et al. decaying schedule: N_i = M-1, then ⌊M/2^i⌋, floored at
        the target N (applied per-leaf via n_override clamping)."""
        i = jnp.maximum(0, (t - self.dense_until) // self.decay_interval)
        n_pow = jnp.maximum(1, m // (2**jnp.minimum(i, 30)))
        return jnp.where(i == 0, m - 1, n_pow).astype(jnp.int32)

    # -- the recipe's step-level API ------------------------------------------

    def masks_for_step(
        self, params: Any, state: RecipeState, phase2: jnp.ndarray
    ) -> tuple[Any, jnp.ndarray, RecipeState]:
        """Return (mask_tree, active, new_state) for this step.

        ``active`` is a traced bool: whether masking applies this step.
        ``phase2`` is the STEP optimizer's phase flag (ignored by other
        recipes).
        """
        t = state.step
        kind = self.kind

        if kind == "dense":
            return self._ones_tree(params), jnp.zeros((), jnp.bool_), state._replace(step=t + 1)

        if kind in ("ste", "sr_ste"):
            return self._mask_tree(params), jnp.ones((), jnp.bool_), state._replace(step=t + 1)

        if kind in ("step", "step_sr"):
            active = phase2
            mask = jax.lax.cond(
                active,
                lambda p: self._mask_tree(p),
                lambda p: self._ones_tree(p),
                params,
            )
            return mask, active, state._replace(step=t + 1)

        if kind == "decay":
            active = t >= self.dense_until
            # max M across leaves bounds the schedule; per-leaf clamp handles
            # heterogeneous (n, m) patterns.
            pats = [
                self.sparsity.pattern_for(name, tuple(p.shape))
                for name, p in zip(
                    tree_paths(params), jax.tree_util.tree_leaves(params)
                )
            ]
            m_global = max([p.m for p in pats if p is not None] or [4])
            n_t = self._decay_n(t, m_global)
            mask = jax.lax.cond(
                active,
                lambda p: self._mask_tree(p, n_override=n_t),
                lambda p: self._ones_tree(p),
                params,
            )
            return mask, active, state._replace(step=t + 1)

        if kind == "asp":
            prune_now = jnp.logical_and(
                jnp.logical_not(state.pruned), t >= self.prune_at
            )
            new_mask_tree = jax.lax.cond(
                prune_now,
                lambda p: self._mask_tree(p),
                lambda p: state.fixed_mask,
                params,
            )
            fixed = jax.tree_util.tree_map(
                lambda old, new: jnp.where(prune_now, new, old),
                state.fixed_mask,
                new_mask_tree,
            )
            pruned = jnp.logical_or(state.pruned, prune_now)
            new_state = RecipeState(step=t + 1, fixed_mask=fixed, pruned=pruned)
            return fixed, pruned, new_state

        raise AssertionError(kind)

    def forward_params(self, params: Any, mask: Any, active: jnp.ndarray) -> Any:
        """The weights fed to the model this step (Eq. 8's Π⊙w, via STE)."""
        if self.kind == "dense":
            return params
        # ASP: true masked gradient, pruned weights stay dead; STE family:
        # straight-through, the full gradient reaches the dense weights
        apply = (
            masking.masked_no_ste
            if self.kind == "asp"
            else masking.straight_through_mask
        )
        with jax.named_scope("nm_mask"):
            return jax.tree_util.tree_map(
                lambda p, mk: apply(p, jnp.where(active, mk, jnp.ones_like(mk))),
                params,
                mask,
            )

    def grad_postprocess(
        self, grads: Any, params: Any, mask: Any, active: jnp.ndarray
    ) -> Any:
        """Add the SR-STE λ(1−Π)⊙w term where applicable (Eq. 9)."""
        if self.kind not in ("sr_ste", "step_sr"):
            return grads
        lam = self.sr_lambda

        def leaf(g, p, mk):
            term = masking.sr_ste_grad_term(p.astype(jnp.float32), mk, lam)
            return g + jnp.where(active, term, 0.0).astype(g.dtype)

        return jax.tree_util.tree_map(leaf, grads, params, mask)

    # -- export ---------------------------------------------------------------

    def final_masks(self, params: Any) -> Any:
        """Π_T for inference (Algorithm 1, line 23)."""
        if self.kind == "dense":
            return self._ones_tree(params)
        return self._mask_tree(params)

    def export_sparse(self, params: Any) -> Any:
        """Π_T ⊙ w_T — the deployable sparse model (Algorithm 1, line 24)."""
        masks = self.final_masks(params)
        return jax.tree_util.tree_map(lambda p, mk: p * mk, params, masks)


def make_recipe(kind: str, sparsity: Optional[SparsityConfig] = None, **kw) -> Recipe:
    return Recipe(kind=kind, sparsity=sparsity or SparsityConfig(), **kw)
