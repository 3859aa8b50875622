"""Pallas TPU kernel: the fused PTQ1.61 linear.

One pallas_call computes  y = x_s @ W4deq + ((x_b·α_r2) @ sign)·(α_s·α_r1)
over a salient-first-permuted input x (the structured mask as a contiguous
channel split — DESIGN.md §3).  The K grid covers k_s/bk int4 steps then
k_b/bk binary steps; `pl.when` selects the unpack path, so each step
streams only its own packed bytes (no second kernel launch, no (M,N)
re-read between the two halves — that is the fusion win over calling
int4_matmul + binary_matmul).

The salient-first permutation itself can run INSIDE the kernel: pass
``perm`` and it rides in as a scalar-prefetch operand, the activation
block spec widens to the full (bm, K) row (fetched once per M tile), and
each K step gathers its own ``perm[k·bk:(k+1)·bk]`` columns in VMEM —
no host-side gather materializes a permuted copy of x in HBM.
``ops.mixed_matmul`` enables this whenever the full-K tile fits the
VMEM budget (``autotune.gather_in_kernel_ok``), which always holds at
decode M.

Requires a K block that divides BOTH k_s and k_b (QuantConfig.multiple
guarantees one at production shapes); block sizes default to the
:mod:`repro.kernels.autotune` cost model and a requested ``bk`` that
only divides one span is repaired to the largest common divisor rather
than asserting.  ops.mixed_matmul falls back to the XLA path before
calling in when no feasible tiling exists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.binary_matmul import _unpack_bits_block
from repro.kernels.int4_matmul import _unpack_nibbles_block


def _body(x_tile, w4_ref, s_ref, z_ref, bits_ref, a_in_ref, a_out_ref,
          o_ref, *, k, bk, bn, k4_steps):
    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(k < k4_steps)
    def _int4():
        q = _unpack_nibbles_block(w4_ref[...], bk, bn)
        w = (q - z_ref[...]) * s_ref[...]
        o_ref[...] += jax.lax.dot(x_tile.astype(jnp.bfloat16),
                                  w.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32)

    @pl.when(k >= k4_steps)
    def _binary():
        x = x_tile.astype(jnp.float32) * a_in_ref[...]
        sign = _unpack_bits_block(bits_ref[...], bk, bn)
        acc = jax.lax.dot(x.astype(jnp.bfloat16), sign,
                          preferred_element_type=jnp.float32)
        o_ref[...] += acc * a_out_ref[...]


def _kernel(x_ref, w4_ref, s_ref, z_ref, bits_ref, a_in_ref, a_out_ref,
            o_ref, *, bk, bn, k4_steps):
    _body(x_ref[...], w4_ref, s_ref, z_ref, bits_ref, a_in_ref, a_out_ref,
          o_ref, k=pl.program_id(2), bk=bk, bn=bn, k4_steps=k4_steps)


def _kernel_gather(perm_ref, x_ref, w4_ref, s_ref, z_ref, bits_ref,
                   a_in_ref, a_out_ref, o_ref, *, bk, bn, k4_steps):
    """Gather-in-kernel variant: x_ref holds the UNpermuted (bm, K) row
    block; this step's salient-first columns are selected in VMEM from
    the scalar-prefetched perm."""
    k = pl.program_id(2)
    idx = perm_ref[pl.ds(k * bk, bk)]
    _body(jnp.take(x_ref[...], idx, axis=1), w4_ref, s_ref, z_ref,
          bits_ref, a_in_ref, a_out_ref, o_ref, k=k, bk=bk, bn=bn,
          k4_steps=k4_steps)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def mixed_matmul(x: jax.Array, w4: jax.Array, s4: jax.Array, z4: jax.Array,
                 bits: jax.Array, alpha_out: jax.Array, alpha_in: jax.Array,
                 perm: jax.Array = None, *, bm: int = None, bn: int = None,
                 bk: int = None, interpret: bool = True) -> jax.Array:
    """x (M,K) permuted salient-first; returns (M,N) in x.dtype.

    With ``perm`` given, x is taken in ORIGINAL channel order and the
    permutation happens inside the kernel (scalar-prefetched indices,
    full-K x tile) — bit-identical to pre-gathering, since the gather is
    pure data movement.

    ``bm``/``bn``/``bk`` default to the autotuner's pick for this
    (M, k_s, k_b, N).  An explicit ``bk`` acts as a cap: the kernel uses
    the largest common divisor of (k_s, k_b) at or below it — a bk that
    divides only one span (e.g. k_s=128, k_b=192 with bk=128) is
    repaired to 64 instead of tripping an assert mid-trace.
    """
    m, kdim = x.shape
    n = bits.shape[1]
    k_s = w4.shape[0] * 2
    k_b = bits.shape[0] * 8
    if k_s + k_b != kdim:
        raise ValueError(f"k_s+k_b={k_s}+{k_b} != x K {kdim}")
    bm, bn, bk = autotune.resolve_blocks(m, k_s, k_b, n, bm, bn, bk,
                                         bk_default=128,
                                         tpu_tiling=not interpret)
    if bk is None or m % bm or n % bn or bk % 8:
        raise ValueError(
            f"infeasible mixed blocks (bm,bn,bk)=({bm},{bn},{bk}) for "
            f"(M,k_s,k_b,N)=({m},{k_s},{k_b},{n}); route through "
            f"repro.kernels.ops.mixed_matmul for the XLA fallback")
    k4_steps = k_s // bk
    kb_steps = k_b // bk
    grid = (m // bm, n // bn, k4_steps + kb_steps)

    # index maps: clamp into each operand's own K range
    def w4_map(i, j, k):
        return (jnp.minimum(k, max(k4_steps - 1, 0)), j)

    def sz_map(i, j, k):
        return (jnp.minimum(k, max(k4_steps - 1, 0)), 0)

    def bits_map(i, j, k):
        return (jnp.clip(k - k4_steps, 0, max(kb_steps - 1, 0)), j)

    def ain_map(i, j, k):
        return (0, jnp.clip(k - k4_steps, 0, max(kb_steps - 1, 0)))

    # per-K scales ride as (K, 1) columns and per-N / binary-K scales as
    # (1, ·) rows: Mosaic only accepts 2-D blocks that match XLA's tiled
    # layout, which 1-D (bk,) slices of a longer vector do not
    operands = (x, w4, s4.astype(jnp.float32).reshape(k_s, 1),
                z4.astype(jnp.float32).reshape(k_s, 1), bits,
                alpha_in.astype(jnp.float32).reshape(1, k_b),
                alpha_out.astype(jnp.float32).reshape(1, n))
    kern = functools.partial(
        _kernel if perm is None else _kernel_gather,
        bk=bk, bn=bn, k4_steps=k4_steps)
    out_spec_args = dict(
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        name="mixed_matmul", interpret=interpret)
    if perm is None:
        in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))]
        tail = lambda f: f                      # 3-arg index maps as-is
        out_map = lambda i, j, k: (i, j)
    else:
        # scalar-prefetch mode: every index map gains a trailing perm
        # ref arg; x widens to the full-K row block, fetched once per i
        in_specs = [pl.BlockSpec((bm, kdim), lambda i, j, k, p: (i, 0))]
        tail = lambda f: (lambda i, j, k, p: f(i, j, k))
        out_map = lambda i, j, k, p: (i, j)
    in_specs += [
        pl.BlockSpec((bk // 2, bn), tail(w4_map)),
        pl.BlockSpec((bk, 1), tail(sz_map)),
        pl.BlockSpec((bk, 1), tail(sz_map)),
        pl.BlockSpec((bk // 8, bn), tail(bits_map)),
        pl.BlockSpec((1, bk), tail(ain_map)),
        pl.BlockSpec((1, bn), tail(lambda i, j, k: (0, j))),
    ]
    if perm is None:
        out = pl.pallas_call(
            kern, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), out_map), **out_spec_args,
        )(*operands)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), out_map))
        out = pl.pallas_call(kern, grid_spec=grid_spec, **out_spec_args,
                             )(perm.astype(jnp.int32), *operands)
    return out.astype(x.dtype)
