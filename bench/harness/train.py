"""Training runner: the program's STEP train step, one object from set-up
through the window.

Set-up makes the weights and a ring of token batches on the device from the
seed, builds ``train/loop.py``'s ``Trainer`` with the mix's optimizer
settings and ``switch_at`` (the STEP optimizer's own fixed switch: the
variance of the precondition steps freezes into the preconditioner at that
step, and masking starts the step after), and drives the compiled step
through the checked steps.  The window then runs the same step, in the
mask-learning phase, on the next batches.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import system
from bench.harness import weights as W

clock = time.perf_counter


def opt_args(mix: dict) -> dict:
    return {"lr": mix["lr"], "b1": mix["b1"], "b2": mix["b2"],
            "eps": mix["eps"], "clip": mix["grad_clip"],
            "switch_at": mix["switch_at"]}


def batches(key, n: int, b: int, s: int, vocab: int) -> dict:
    """``n`` batches of ``b`` rows of ``s`` uniform token ids; labels are
    the next token (the first token wraps to the end)."""
    tok = jax.random.randint(jax.random.fold_in(key, 0x5EED), (n, b, s), 0,
                             vocab, jnp.int32)
    return {"tokens": tok, "labels": jnp.roll(tok, -1, axis=-1)}


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel()), tree)


@jax.jit
def _change(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)


def _named(tree, cast=float) -> dict:
    return {W.path_str(p): cast(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


class Job:
    def __init__(self, config: dict, mix: dict, seed: int):
        import repro.core as core
        from repro.data import DataIterator
        from repro.train import Trainer, TrainerConfig

        self.mix = mix
        mdl = system.model(config)
        key = W.seed_key(seed)
        self.params0 = system.dense_params(mdl, key)
        self.data = jax.jit(batches, static_argnums=(1, 2, 3, 4))(
            key, mix["batches"], mix["batch"], mix["seq_len"],
            config["model"]["vocab_size"])
        scfg = core.StepConfig(learning_rate=mix["lr"], b1=mix["b1"],
                               b2=mix["b2"], eps=mix["eps"],
                               switch_at=mix["switch_at"])
        chunk = mix["loss_chunk"]
        self.trainer = Trainer(
            lambda p, b: mdl.loss(p, b, chunk=chunk), system.recipe(config),
            scfg,
            DataIterator(batch_fn=None, batch_size=mix["batch"], prefetch=0),
            TrainerConfig(grad_clip=mix["grad_clip"]),
        )
        self.state = self.trainer.init_state(self.params0)
        self.i = 0

    def _batch(self) -> dict:
        j = self.i % self.mix["batches"]
        self.i += 1
        return {"tokens": self.data["tokens"][j], "labels": self.data["labels"][j]}

    def first_steps(self, precond: bool = False) -> dict:
        """The checked steps, through the window's own call and feed:
        losses, the phase each step ran in, the first gradient as the
        optimizer got it (its first moment after one step over 1 - b1), and
        per leaf on the host the parameters' change after the last checked
        step (and, with ``precond``, the frozen preconditioner P*)."""
        losses, phase2, grads = [], [], None
        for t in range(self.mix["checked_steps"]):
            self.state, met = self.trainer._step(self.state, self._batch())
            losses.append(float(met["loss"]))
            phase2.append(int(met["phase2"]))
            if t == 0:
                grads = {k: v / (1 - self.mix["b1"])
                         for k, v in _named(_norms(self.state.opt.m)).items()}
        host = lambda t: _named(jax.device_get(t), np.asarray)
        out = {"losses": losses, "phase2": phase2, "grad_norms": grads,
               "change": host(_change(self.state.params, self.params0))}
        if precond:
            out["precond"] = host(self.state.opt.precond)
        self.params0 = None
        return out

    def checked_batches(self) -> list:
        n = self.mix["checked_steps"]
        return [{k: np.asarray(v[j]) for k, v in self.data.items()}
                for j in range(n)]

    def window(self, seconds: float) -> dict:
        """Train steps back to back for ``seconds``; the window closes when
        the last step started in it completes."""
        ws = clock()
        steps = 0
        while clock() - ws < seconds:
            self.state, met = self.trainer._step(self.state, self._batch())
            jax.block_until_ready(met["loss"])
            steps += 1
        end = clock()
        return {"start": ws, "end": end, "steps": steps,
                "tokens": steps * self.mix["batch"] * self.mix["seq_len"]}

    def close(self) -> None:
        self.state = None
        self.trainer = None


def trimmed_norm(v: np.ndarray, trim: float) -> float:
    """The norm of ``v`` without its largest ``trim`` share of magnitudes."""
    a = np.abs(v).ravel()
    n = int(trim * a.size)
    if n:
        a = np.partition(a, a.size - n)[:a.size - n]
    return float(np.linalg.norm(a))


def compare(prog: dict, ref: dict, trim: float, tiny: float = 1e-3) -> dict:
    """The gaps the check compares.  For a norm, a leaf's gap is the gap
    between the program's norm and the reference's, over the larger of
    the reference's norm of that leaf and of the median leaf.

    - ``loss``: the largest loss gap over the checked steps;
    - ``grad``: the worst leaf's gap of the first gradient;
    - ``change``: the worst leaf's gap of the parameters' change after the
      checked steps, each side's norm without its largest ``trim`` share
      of coordinates.  The mask-learning update divides by the frozen
      P*, and a coordinate whose precondition-step gradients cancelled to
      nearly zero multiplies its rounding noise by 1/P* (PERF.md): a few
      coordinates of a leaf on one side; ``change_all`` is the same gap
      over every coordinate, reported.
    Leaves whose reference gradient is under ``tiny`` times the median
    leaf's (a key's bias under softmax) move by round-off alone and are
    left out of the change."""
    loss = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))

    def gaps(p: dict, r: dict, keys) -> dict:
        med = float(np.median([r[k] for k in keys]))
        return {k: abs(p[k] - r[k]) / max(r[k], med) for k in keys}

    def norms(tree: dict, q: float) -> dict:
        return {k: trimmed_norm(v, q) for k, v in tree.items()}

    gr = ref["grad_norms"]
    med_g = float(np.median(list(gr.values())))
    keep = [k for k in gr if gr[k] >= tiny * med_g]
    change = gaps(norms(prog["change"], trim), norms(ref["change"], trim),
                  keep)
    every = gaps(norms(prog["change"], 0.0), norms(ref["change"], 0.0), keep)
    worst = max(change, key=change.get)
    return {"loss": loss,
            "grad": max(gaps(prog["grad_norms"], gr, list(gr)).values()),
            "change": change[worst], "change_leaf": worst,
            "change_all": max(every.values()),
            "left_out": sorted(set(gr) - set(keep))}


CHECKS = (("train_loss_gap", "loss"), ("train_grad_norm_gap", "grad"),
          ("train_change_norm_gap", "change"))


def judge(gaps: dict, phase2: list, limits: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: each compared number beside its limit (a
    number whose limit is null is not compared; PERF.md gives why), and
    whether the last checked step ran in the mask-learning phase."""
    checks = {name: {"value": gaps[key], "limit": limits[name]}
              for name, key in CHECKS if limits[name] is not None}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    checks["mask_learning_steps"] = {"value": phase2[-1], "limit": 1}
    return ok and phase2[-1] == 1, checks
