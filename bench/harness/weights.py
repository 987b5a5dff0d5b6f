"""Seeded weights, made by the benchmark and never by the program.

Every leaf is a pure function of ``(seed, leaf path, layer)``: a leaf of
the scan-stacked body (leading layer axis) draws each layer from its own
key, so the plain reference can make one layer's weights alone, and the
program's tree is made whole in one jitted call.  Values are uniform with
the usual initialisation's spread (``std = sqrt(2 / (fan_in + fan_out))``
for matrices, 0.02 for the token embedding), drawn by exact arithmetic so
that the program's and the reference's weights are the same bits on any
backend; biases and norm parameters are drawn too (not zeros/ones), so the
comparison with the reference covers them.  A norm scale is drawn around a
centre the caller gives: 1 where the norm multiplies by its scale, 0 where
it multiplies by ``1 + scale``, so the effective scale is ``1 +- 0.02 sqrt 3``
either way.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits are used)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _uniform(key, shape: tuple, scale: float) -> jax.Array:
    """Uniform on [-scale, scale) from integer bits with one rounding per
    value (a 24-bit integer times one constant), so that every backend and
    every fusion of the generating program gives the same values."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    i = (bits >> 8).astype(jnp.int32) - (1 << 23)
    return i.astype(jnp.float32) * (scale / (1 << 23))


def _draw(key, path: str, shape: tuple, dtype, centre: float) -> jax.Array:
    """One layer's (or an unstacked) leaf: uniform with the standard
    deviation the usual initialisation gives, a norm scale around
    ``centre``."""
    name = path.rsplit("/", 1)[-1]
    if name == "tok_embed" or len(shape) == 1:
        std = 0.02  # the token embedding, biases and norm offsets
    else:
        std = (2.0 / (shape[-2] + shape[-1])) ** 0.5
    x = _uniform(key, shape, std * 3.0 ** 0.5)
    if name == "norm_scale":
        x = centre + x
    return x.astype(dtype)


def leaf(key, path: str, shape: tuple, dtype, stacked: bool,
         layer=None, centre: float = 1.0) -> jax.Array:
    """The values of one leaf; for a stacked leaf, ``layer`` picks one
    layer (``shape`` is then the per-layer shape), else all layers."""
    k = _leaf_key(key, path)
    if not stacked:
        return _draw(k, path, tuple(shape), dtype, centre)
    if layer is not None:
        return _draw(jax.random.fold_in(k, layer), path, tuple(shape), dtype,
                     centre)
    # layer by layer, each layer by the very call that makes it alone (no
    # vmap), so the stacked leaf and the reference's layers are the same
    # program for the random-bit generator on every backend
    return jnp.stack([_draw(jax.random.fold_in(k, l), path, tuple(shape[1:]),
                            dtype, centre) for l in range(shape[0])])


def path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(p.name)
        else:
            parts.append(str(getattr(p, "idx", p)))
    return "/".join(parts)


def make_tree(shapes, key, centre: float = 1.0) -> dict:
    """Fill a tree of ``ShapeDtypeStruct`` leaves; leaves under ``body``
    carry a leading layer axis; norm scales are drawn around ``centre``."""
    def fill(path, s):
        p = path_str(path)
        return leaf(key, p, s.shape, s.dtype, stacked=p.startswith("body/"),
                    centre=centre)

    return jax.tree_util.tree_map_with_path(fill, shapes)
