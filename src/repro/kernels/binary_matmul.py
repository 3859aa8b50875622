"""Pallas TPU kernel: packed 1-bit × bf16 matmul with Eq.-9 scales.

The decode-time hot spot of a sub-2-bit-quantized LLM: weights stream
HBM→VMEM as PACKED bytes (K/8 the footprint of bf16), unpack to ±1 bf16
inside VMEM, and feed the MXU as a dense matmul.  There is no TPU
XNOR-popcount datapath (DESIGN.md §3) — the win is the 16× weight-byte
reduction on a bandwidth-bound op, not the multiply itself.

Tiling: grid (M/bm, N/bn, K/bk); K innermost for accumulation.
  x tile     (bm, bk)     bf16
  bits tile  (bk/8, bn)   u8     -> unpack -> (bk, bn) ±1 bf16
  acc        (bm, bn)     f32 in the output ref (revisited across K steps)
Block sizes default to the :mod:`repro.kernels.autotune` cost model
(VMEM-budgeted, HBM-byte-minimizing per (M, K, N)); decode-shaped calls
get bm=M and a whole-N column block so the activation streams once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune


def _unpack_bits_block(packed: jax.Array, bk: int, bn: int) -> jax.Array:
    """(bk//8, bn) u8 -> (bk, bn) bf16 ±1 (bit j of byte i -> k=8i+j)."""
    p = packed.astype(jnp.int32)                     # (bk/8, bn)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
    bits = (p[:, None, :] >> shifts) & 1             # (bk/8, 8, bn)
    return (bits.reshape(bk, bn) * 2 - 1).astype(jnp.bfloat16)


def _kernel(x_ref, bits_ref, a_in_ref, a_out_ref, o_ref, *, bk, bn):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32) * a_in_ref[...]
    sign = _unpack_bits_block(bits_ref[...], bk, bn)
    acc = jax.lax.dot(x.astype(jnp.bfloat16), sign,
                      preferred_element_type=jnp.float32)
    o_ref[...] += acc

    @pl.when(k == pl.num_programs(2) - 1)
    def _scale():
        o_ref[...] = o_ref[...] * a_out_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def binary_matmul(x: jax.Array, bits: jax.Array, alpha_out: jax.Array,
                  alpha_in: jax.Array, *, bm: int = None, bn: int = None,
                  bk: int = None, interpret: bool = True) -> jax.Array:
    """y (M,N) f32 = ((x·α_in) @ unpack(bits)) · α_out.

    Block sizes default to the :mod:`repro.kernels.autotune` cost model
    (decode-shaped M picks bm=M and, VMEM permitting, bn=N); explicit
    values are clamped/repaired to feasible divisors.
    """
    m, kdim = x.shape
    n = bits.shape[1]
    if bits.shape[0] * 8 != kdim:
        raise ValueError(f"bits K span {bits.shape[0] * 8} != x K {kdim}")
    bm, bn, bk = autotune.resolve_blocks(m, 0, kdim, n, bm, bn, bk,
                                         tpu_tiling=not interpret)
    if bk is None or m % bm or n % bn or kdim % bk or bk % 8:
        raise ValueError(
            f"infeasible binary blocks (bm,bn,bk)=({bm},{bn},{bk}) for "
            f"(M,K,N)=({m},{kdim},{n})")

    grid = (m // bm, n // bn, kdim // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 8, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        name="binary_matmul", interpret=interpret,
    )(x, bits, alpha_in.astype(jnp.float32).reshape(1, kdim),
      alpha_out.astype(jnp.float32).reshape(1, n))
    return out.astype(x.dtype)
