"""Random bf16 weights drawn from ``--seed``: what every model family
(``bench/families``) draws its leaves with.

A family's program weights and its plain reference draw through the
same functions, so they start from the same bf16 numbers; each then
quantizes them its own way.  Every leaf has its own key, folded from
the seed, the layer and the leaf's name, so a layer can be drawn alone,
on the device, in any program.

Each weight matrix has outlier input channels, as trained models do:
for every input of a layer a seed-drawn set of exactly as many channels
as the recipe keeps salient is drawn ``salient_scale`` times wider than
the rest (``_row_scales``), shared by the weights that read that input.
Their mean |w| then stands far from the others', so any quantizer
following the recipe keeps the same channels salient whatever order it
sums in; with iid rows, channels within float32 rounding of the cut
would be kept by one quantizer and binarized by another.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size up to 64 bits (``PRNGKey`` alone
    keeps only the low 32)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _draw(key, shape, kind: str, init: Dict[str, float],
          rows=None) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        v = z * init["std"]
        if rows is not None:
            v = v * rows[:, None]
    elif kind == "bias":
        v = z * init["bias_std"]
    else:                                   # norm scale
        v = 1.0 + z * init["norm_jitter"]
    return v.astype(jnp.bfloat16)


def layer_key(seed_k, layer) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(seed_k, 2), layer)


def _row_scales(key, k: int, k_s: int, scale: float) -> jax.Array:
    """(k,) 1 for every input channel but ``k_s`` drawn ones, ``scale``."""
    rows = jax.random.permutation(key, k)[:k_s]
    return jnp.ones((k,), jnp.float32).at[rows].set(scale)
