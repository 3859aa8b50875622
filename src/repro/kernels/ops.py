"""Jit'd public wrappers dispatching QLinear forwards to Pallas kernels.

``INTERPRET`` is fixed when this module is imported: False when
``jax.default_backend()`` is a TPU, so the kernels compile through
Mosaic, and True on any other backend, where every kernel runs in the
Pallas interpreter (correct, and slow).  Nothing else sets it; tests
that need the TPU-side gates monkeypatch it.  ``chip_smoke.py`` refuses
to run with it True.

Decode fast path notes (§Perf):

* Feasibility is checked BEFORE the salient-first activation gather, so
  an unaligned-shape call falls back to the XLA dequant path without
  paying a dead (M, K) gather first.
* Block sizes come from the :mod:`repro.kernels.autotune` cost model
  (memoized per shape — the dispatch cache; Mosaic's tiling floors
  apply off interpret mode), not fixed constants: decode
  calls at M = n_slots get M-sized row blocks and, VMEM permitting, a
  whole-N column block so the activation streams HBM→VMEM once per call.
* ``pre_permuted=True`` skips the gather entirely for callers that
  already hold salient-first activations — the N-fused QLinearGroup path
  gathers once per group (QKV, gate+up) instead of once per projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.binary_matmul import binary_matmul
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.mixed_matmul import mixed_matmul as _mixed
from repro.kernels.paged_attention import paged_attention as _paged_attn
from repro.kernels.paged_prefill import paged_prefill as _paged_prefill
from repro.kernels.paged_prefill import paged_prefill_xla

INTERPRET = jax.default_backend() != "tpu"


def _kernel_choice(m: int, k_s: int, k_b: int, n: int):
    """Autotuned blocks, or None when the kernel cannot serve the shape
    (misaligned N, no common K block, or an empty int4/binary span —
    the kernel's block specs need at least one step on each span)."""
    if k_s <= 0 or k_b <= 0:
        return None
    return autotune.choose_blocks(m, k_s, k_b, n, tpu_tiling=not INTERPRET)


def mixed_matmul(x: jax.Array, q, *, pre_permuted: bool = False) -> jax.Array:
    """PTQ1.61 linear forward for a QLinear `q` (2-D weights).

    Flattens batch dims, checks kernel feasibility, then runs the fused
    kernel with autotuned blocks; falls back to the XLA dequant path for
    unaligned shapes.  The salient-first channel permutation happens
    INSIDE the kernel when the full-K activation tile fits VMEM (the
    perm rides in as a scalar-prefetch operand — no host-side gather at
    all); otherwise one XLA gather precedes the call.  With
    ``pre_permuted=True`` the caller asserts ``x`` is already in
    salient-first channel order and no gather is issued on any path.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for d in lead:
        m *= d
    choice = _kernel_choice(m, q.k_s, q.k_b, q.n)
    if choice is None:
        if pre_permuted:
            return q.__matmul_permuted__(x)
        import dataclasses
        return dataclasses.replace(q, use_kernel=False).__matmul_x__(x)
    xf = x.reshape(-1, k)
    perm = None
    if pre_permuted:
        xp = xf
    elif INTERPRET and autotune.gather_in_kernel_ok(choice, m, k):
        # gather moves into the kernel (scalar-prefetched perm).  Pinned
        # to interpret mode: Mosaic refuses the vector slice of the
        # SMEM perm ("Can only load scalars from SMEM"), so on a TPU the
        # host-side gather below stays.
        xp, perm = xf, q.perm
    else:
        xp = jnp.take(xf, q.perm, axis=-1)
    alpha_out = (q.alpha_s * q.alpha_r1).astype(jnp.float32)
    y = _mixed(xp.astype(jnp.bfloat16), q.w4, q.s4, q.z4, q.bits,
               alpha_out, q.alpha_r2.astype(jnp.float32), perm=perm,
               bm=choice.bm, bn=choice.bn, bk=choice.bk,
               interpret=INTERPRET)
    return y.reshape(lead + (q.n,)).astype(x.dtype)


LANE = autotune.LANE    # TPU register-tile lane width (last-dim floor)


def padded_head_dim(dh: int) -> int:
    """Head dim the paged KV *pool* allocates for a logical ``dh``.

    On a real TPU the flash-decode kernel's K/V page tiles must land on
    the 128-lane register tiling, so pools for archs with
    ``dh % 128 != 0`` are rounded up and the tail zero-padded — exact,
    because zero lanes add nothing to q·k (contraction dim) and the
    padded output columns are sliced off before the output projection.
    Interpret mode keeps the logical dh (no constraint, no memory tax);
    tests monkeypatch this to exercise the padded layout on CPU."""
    if INTERPRET or dh % LANE == 0:
        return dh
    return ((dh + LANE - 1) // LANE) * LANE


def paged_attention_blocks(ps: int, hkv: int, rep: int, dh: int, nblk: int,
                           pool_dh: int = None):
    """Feasibility gate for the paged flash-decode kernel (``nblk`` =
    block-table width): the autotuned pages-per-block choice, or None
    when the kernel cannot serve the shape and the caller must keep the
    XLA-gather reference path.  On a
    real TPU backend the pool layout must respect the MXU/VPU tiling
    floors — ``dh`` misalignment is absorbed by the pool's padded head
    dim (:func:`padded_head_dim`; ``pool_dh`` is the pool's actual last
    dim when the caller holds the cache), leaving only the page-size
    sublane floor; interpret mode has no such constraint."""
    pool_dh = padded_head_dim(dh) if pool_dh is None else pool_dh
    if pool_dh < dh:
        return None
    if not INTERPRET and (pool_dh % LANE != 0
                          or ps % autotune.SUBLANE != 0):
        return None
    return autotune.choose_paged_blocks(hkv, rep, pool_dh, ps, nblk)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array, *,
                    layer, window=None, softcap=None,
                    ppcb=None) -> jax.Array:
    """Paged flash-decode forward over ``layer`` of the stacked pool (see
    kernels.paged_attention); the caller is expected to have consulted
    :func:`paged_attention_blocks` first — this wrapper only pins the
    interpret mode."""
    return _paged_attn(q, k_pool, v_pool, block_tables, context_lens,
                       layer=layer, window=window, softcap=softcap,
                       ppcb=ppcb, interpret=INTERPRET)


def paged_prefill_blocks(c: int, ps: int, hkv: int, rep: int, dh: int,
                         pool_dh: int = None):
    """Feasibility gate for the chunked paged-prefill kernel: the
    autotuned KV-tile choice, or None when the kernel cannot serve the
    shape and the caller must keep the XLA dense-gather fallback
    (:func:`repro.kernels.paged_prefill.paged_prefill_xla`).  Same
    tiling-floor rules as :func:`paged_attention_blocks`, plus the
    chunk must tile evenly into pages."""
    pool_dh = padded_head_dim(dh) if pool_dh is None else pool_dh
    if pool_dh < dh or c % ps:
        return None
    if not INTERPRET and (pool_dh % LANE != 0
                          or ps % autotune.SUBLANE != 0):
        return None
    return autotune.choose_prefill_blocks(c, hkv, rep, pool_dh, ps)


def paged_prefill(q, k_new, v_new, k_pool, v_pool, bt_read, bt_write,
                  start, length, *, layer, window=None, softcap=None,
                  bh=None):
    """Fused chunk scatter+attend (see kernels.paged_prefill); the
    caller is expected to have consulted :func:`paged_prefill_blocks`
    first — this wrapper only pins the interpret mode."""
    return _paged_prefill(q, k_new, v_new, k_pool, v_pool, bt_read,
                          bt_write, start, length, layer=layer,
                          window=window, softcap=softcap, bh=bh,
                          interpret=INTERPRET)


__all__ = ["binary_matmul", "int4_matmul", "mixed_matmul",
           "paged_attention", "paged_attention_blocks",
           "paged_prefill", "paged_prefill_blocks", "paged_prefill_xla",
           "padded_head_dim", "LANE", "INTERPRET", "autotune"]
