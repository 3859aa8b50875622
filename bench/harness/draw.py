"""Random bf16 weights of a configuration, drawn from ``--seed``.

Both the program's weights (``weights.py``) and the plain reference draw
through these functions, so they start from the same bf16 numbers; each
then quantizes them its own way.  Every leaf has its own key, folded
from the seed, the layer and the leaf's name, so a layer can be drawn
alone, on the device, in any program.

Each weight matrix has outlier input channels, as trained models do:
for every input of a layer (the one QKV and gate+up share, the output
projection's, the down projection's) a seed-drawn set of exactly as many
channels as the recipe keeps salient is drawn ``salient_scale`` times
wider than the rest.  Their mean |w| then stands far from the others', so
any quantizer following the recipe keeps the same channels salient
whatever order it sums in; with iid rows, channels within float32
rounding of the cut would be kept by one quantizer and binarized by
another.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from harness.spec import Dims, salient_channels

# which input each weight reads: weights of one input share their
# salient channels (QKV and gate+up are quantized fused)
INPUT_OF = {"q_proj": "attn_in", "k_proj": "attn_in", "v_proj": "attn_in",
            "o_proj": "o_in", "gate_proj": "mlp_in", "up_proj": "mlp_in",
            "down_proj": "down_in"}


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size up to 64 bits (``PRNGKey`` alone
    keeps only the low 32)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def layer_leaves(dm: Dims) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Name -> (shape, kind) of one decoder layer, weights (in, out)."""
    qd, kvd = dm.hq * dm.dh, dm.hkv * dm.dh
    out = {
        "input_norm": ((dm.d,), "norm"),
        "q_proj": ((dm.d, qd), "matrix"),
        "k_proj": ((dm.d, kvd), "matrix"),
        "v_proj": ((dm.d, kvd), "matrix"),
        "o_proj": ((qd, dm.d), "matrix"),
        "post_attention_norm": ((dm.d,), "norm"),
        "gate_proj": ((dm.d, dm.ff), "matrix"),
        "up_proj": ((dm.d, dm.ff), "matrix"),
        "down_proj": ((dm.ff, dm.d), "matrix"),
    }
    if dm.qkv_bias:
        out.update({"q_bias": ((qd,), "bias"), "k_bias": ((kvd,), "bias"),
                    "v_bias": ((kvd,), "bias")})
    if dm.qk_norm:
        out.update({"q_norm": ((dm.dh,), "norm"),
                    "k_norm": ((dm.dh,), "norm")})
    return out


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


def draw_layer(key, dm: Dims, init: Dict[str, float],
               recipe: Tuple[float, int]) -> Dict[str, jax.Array]:
    """One layer's bf16 leaves under ``key`` (from :func:`layer_key`);
    ``recipe`` is (salient ratio, multiple), which sizes the outlier
    channels."""
    ratio, multiple = recipe
    rows: Dict[str, jax.Array] = {}
    out = {}
    for name, (shape, kind) in layer_leaves(dm).items():
        r = None
        if kind == "matrix":
            group = INPUT_OF[name]
            if group not in rows:
                rows[group] = _row_scales(
                    _leaf_key(key, group), shape[0],
                    salient_channels(shape[0], ratio, multiple),
                    float(init["salient_scale"]))
            r = rows[group]
        out[name] = _draw(_leaf_key(key, name), shape, kind, init, r)
    return out


def draw_embed(seed_k, dm: Dims, init: Dict[str, float]) -> jax.Array:
    """(vocab, d) bf16 token embedding (also the tied head)."""
    return _draw(jax.random.fold_in(seed_k, 0), (dm.vocab, dm.d), "matrix",
                 init)


def draw_final_norm(seed_k, dm: Dims, init: Dict[str, float]) -> jax.Array:
    return _draw(jax.random.fold_in(seed_k, 1), (dm.d,), "norm", init)
