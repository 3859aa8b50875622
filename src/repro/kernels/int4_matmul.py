"""Pallas TPU kernel: packed int4 × bf16 matmul, per-input-channel grid.

Serves the salient 20% channels of a PTQ1.61 layer (and any plain
int4-quantized linear).  Same tiling discipline as binary_matmul; nibbles
unpack to (q−z)·s inside VMEM.  Because s, z are per *input* channel the
dequant folds into the x side:  x @ ((q−z)·s) = (x·s) @ q − (x·s·z)·Σ... —
we keep the direct form (unpack→dequant→MXU) for clarity; the fused
variant is in mixed_matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune


def _unpack_nibbles_block(packed: jax.Array, bk: int, bn: int) -> jax.Array:
    """(bk//2, bn) u8 -> (bk, bn) f32 codes 0..15 (low nibble = even k)."""
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = p >> 4
    inter = jnp.stack([lo, hi], axis=1)              # (bk/2, 2, bn)
    return inter.reshape(bk, bn).astype(jnp.float32)


def _kernel(x_ref, w4_ref, s_ref, z_ref, o_ref, *, bk, bn):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = _unpack_nibbles_block(w4_ref[...], bk, bn)
    w = (q - z_ref[...]) * s_ref[...]
    o_ref[...] += jax.lax.dot(x_ref[...].astype(jnp.bfloat16),
                              w.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int4_matmul(x: jax.Array, w4: jax.Array, s4: jax.Array, z4: jax.Array,
                *, bm: int = None, bn: int = None, bk: int = None,
                interpret: bool = True) -> jax.Array:
    """Blocks default to the autotuner (see :mod:`repro.kernels.autotune`)."""
    m, kdim = x.shape
    n = w4.shape[1]
    if w4.shape[0] * 2 != kdim:
        raise ValueError(f"w4 K span {w4.shape[0] * 2} != x K {kdim}")
    bm, bn, bk = autotune.resolve_blocks(m, kdim, 0, n, bm, bn, bk,
                                         align=2, tpu_tiling=not interpret)
    if bk is None or m % bm or n % bn or kdim % bk or bk % 2:
        raise ValueError(
            f"infeasible int4 blocks (bm,bn,bk)=({bm},{bn},{bk}) for "
            f"(M,K,N)=({m},{kdim},{n})")

    grid = (m // bm, n // bn, kdim // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        name="int4_matmul", interpret=interpret,
    )(x, w4, s4.astype(jnp.float32).reshape(kdim, 1),
      z4.astype(jnp.float32).reshape(kdim, 1))
    return out.astype(x.dtype)
