"""Pallas TPU kernel: paged flash-decode attention over the shared KV pool.

One pallas_call attends every decode slot's query against its own pages
of the position-aligned pool ``(P, ps, hkv, dh)`` WITHOUT materializing
the gathered ``(B, nblk*ps, hkv, dh)`` context in HBM — the win the
paged serving path needs once PTQ1.61 weights stop dominating decode
traffic (the KV cache does).

Mechanics (the scalar-prefetch contract):

* ``block_tables`` (flattened ``(B*nblk,)``) and ``context_lens``
  ``(B,)`` ride in as *scalar-prefetch* operands, so they are resident
  in SMEM before the grid starts and the K/V BlockSpec index maps can
  read them: grid step ``(b, hg, j)`` DMAs pool page
  ``block_tables[b, j]`` straight HBM→VMEM.  No XLA gather, no dense
  intermediate.
* The grid walks ``(B, hkv/bh, nblk)`` with the page dim innermost; a
  VMEM scratch triple ``(m, l, acc)`` carries the online-softmax state
  across a request's pages (flash-decode) and the normalized output is
  written once at the last page step.
* **Early exit / ragged lengths**: steps past a request's last live
  page (or before its sliding-window start) skip compute via
  ``pl.when`` AND clamp their index map into the live page range, so
  the Pallas pipeline re-addresses the previous block and issues no new
  DMA — per-token HBM traffic is proportional to the LIVE context, not
  to ``nblk*ps`` table capacity.  Unassigned / freed table entries
  (``-1``) are masked the same way (fetch clamped to page 0, compute
  skipped), matching the XLA reference's implied-position mask.
* GQA: queries are blocked ``(bh, rep, dh)`` per kv-head group and
  contracted against ``(ps, bh, dh)`` page tiles with a batched dot —
  the head-group broadcast never leaves VMEM.  ``bh`` (kv heads per
  block) comes from :func:`repro.kernels.autotune.choose_paged_blocks`.

Numerics mirror ``repro.models.layers._attend``: bf16 operands into the
MXU with f32 accumulation, f32 softmax (scores divided by sqrt(dh),
optional logit softcap), probabilities fed back at the V dtype.  Rows
with ``context_lens == 0`` (inactive slots) produce exact zeros rather
than the reference's uniform-softmax garbage — both are discarded by
the engine.

``repro.models.layers.attention_decode_paged`` dispatches here behind a
feasibility check (mirroring ``ops.mixed_matmul``) and keeps the XLA
gather as the fallback/reference path.

**Head-dim padding**: pools for archs whose ``dh`` is off the 128-lane
TPU tile are allocated at ``ops.padded_head_dim(dh)`` with zero-padded
tails, so the kernel serves them instead of punting to the dense
gather.  The wrapper zero-pads q into the pool tile (zero lanes add
nothing to q·k), keeps the softmax scale at 1/sqrt(dh_logical), and
slices the padded output columns off — exact by construction.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

NEG_INF = -1e30


def kv_block_index(bi, j, bt_flat, lens, *, ps: int, nblk: int,
                   window: Optional[int]):
    """Pool page the K/V BlockSpec addresses at grid step ``(bi, ·, j)``.

    THE fetch contract, shared by the kernel's index map and the
    instrumentation below: steps past the last live page, before the
    sliding-window start, or on inactive rows clamp onto an
    already-fetched live page — the Pallas pipeline sees an unchanged
    block index and issues no new DMA."""
    length = lens[bi]
    last = jnp.maximum((length - 1) // ps, 0)
    if window is None:
        first = 0
    else:
        first = jnp.minimum(jnp.maximum(length - window, 0) // ps, last)
    jj = jnp.clip(j, first, last)
    return jnp.maximum(bt_flat[bi * nblk + jj], 0)


def fetched_page_counts(block_tables, context_lens, ps: int, *,
                        window: Optional[int] = None):
    """Replay the kernel's ACTUAL K/V index map over one decode step's
    grid and count the page DMAs it issues per request row (consecutive
    equal block indices re-address the resident tile — no fetch).

    This is measurement, not a cost model: it walks the same
    :func:`kv_block_index` the BlockSpec uses, so a regression in the
    clamp (e.g. dead steps fetching fresh pages again) shows up here —
    serving_bench asserts these counts stay within one page of each
    row's live context.  Returns an int array (B,)."""
    import numpy as np
    b, nblk = np.asarray(block_tables).shape
    counts = _fetched_page_counts_dev(
        jnp.asarray(np.asarray(block_tables).reshape(-1)),
        jnp.asarray(np.asarray(context_lens)), ps=ps, nblk=nblk,
        window=window)
    return np.asarray(counts)


@functools.partial(jax.jit, static_argnames=("ps", "nblk", "window"))
def _fetched_page_counts_dev(bt_flat, lens, *, ps, nblk, window):
    b = lens.shape[0]
    pages = jax.vmap(lambda bi: jax.vmap(
        lambda j: kv_block_index(bi, j, bt_flat, lens, ps=ps, nblk=nblk,
                                 window=window))(jnp.arange(nblk)))(
        jnp.arange(b))                                   # (B, nblk)
    changed = jnp.concatenate(
        [jnp.ones((b, 1), bool), pages[:, 1:] != pages[:, :-1]], axis=1)
    return jnp.sum(changed, axis=1)


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, ps, nblk, sm_scale, window, softcap):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    page = bt_ref[b * nblk + j]
    length = len_ref[b]
    live = jnp.logical_and(page >= 0, j * ps < length)
    if window is not None:
        # skip pages wholly below the sliding-window start
        live = jnp.logical_and(live, (j + 1) * ps > length - window)

    @pl.when(live)
    def _page():
        q = q_ref[0]                       # (bh, rep, dh)
        k = k_ref[0]                       # (ps, bh, dh)
        v = v_ref[0]
        s = jax.lax.dot_general(            # (bh, rep, ps)
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        # sm_scale is 1/sqrt(dh_logical) — the LOGICAL head dim, not the
        # (possibly lane-padded) pool tile dim: padded lanes are zero in
        # q so they add nothing to the dot, but they must not inflate
        # the softmax temperature
        s = s.astype(jnp.float32) * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kp = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
        valid = kp < length
        if window is not None:
            valid = jnp.logical_and(valid, kp >= length - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nblk - 1)
    def _finalize():
        # inactive rows (length 0): l stays 0 -> exact zeros, never NaN
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "bh",
                                             "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array, *,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    bh: Optional[int] = None,
                    interpret: bool = True) -> jax.Array:
    """Flash-decode over pool pages.

    q (B, hq, dh); k_pool/v_pool (P, ps, hkv, dh_pool); block_tables
    (B, nblk) int32 page ids (-1 = unassigned); context_lens (B,) int32
    live tokens per request (0 = inactive row -> zero output).  Returns
    (B, hq, dh) f32.  ``bh`` (kv heads per block) defaults to the
    autotuner's pick.

    ``dh_pool`` may exceed q's logical ``dh`` (lane-padded pools for
    archs with ``dh`` off the 128-lane TPU tile —
    ``ops.padded_head_dim``): q is zero-padded into the pool tile, the
    softmax scale stays 1/sqrt(dh_logical), and the padded output
    columns are sliced off — exact, since zero q lanes contribute
    nothing to q·k and the padded V columns never survive the slice.
    """
    b, hq, dh = q.shape
    num_pages, ps, hkv, dh_pool = k_pool.shape
    nblk = block_tables.shape[1]
    rep = hq // hkv
    if hq % hkv:
        raise ValueError(f"hq={hq} not a multiple of hkv={hkv}")
    if dh_pool < dh:
        raise ValueError(f"pool head dim {dh_pool} < query head dim {dh}")
    sm_scale = 1.0 / math.sqrt(dh)
    if dh_pool > dh:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, dh_pool - dh)))
    if bh is None:
        choice = autotune.choose_paged_blocks(hkv, rep, dh_pool, ps)
        if choice is None:
            raise ValueError(
                f"no feasible paged-attention blocks for (hkv, rep, dh, ps)"
                f"=({hkv}, {rep}, {dh_pool}, {ps}); route through "
                f"repro.models.layers.attention_decode_paged for the XLA "
                f"fallback")
        bh = choice.bh
    if hkv % bh:
        raise ValueError(f"bh={bh} must divide hkv={hkv}")
    qg = q.reshape(b, hkv, rep, dh_pool)
    grid = (b, hkv // bh, nblk)

    def q_map(bi, hg, j, bt, lens):
        return (bi, hg, 0, 0)

    def kv_map(bi, hg, j, bt, lens):
        # the shared fetch contract (see kv_block_index): dead steps
        # clamp onto an already-fetched live page -> no new DMA
        return (kv_block_index(bi, j, bt, lens, ps=ps, nblk=nblk,
                               window=window), 0, hg, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, rep, dh_pool), q_map),
            pl.BlockSpec((1, ps, bh, dh_pool), kv_map),
            pl.BlockSpec((1, ps, bh, dh_pool), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bh, rep, dh_pool), q_map),
        scratch_shapes=[
            pltpu.VMEM((bh, rep, 1), jnp.float32),       # running max
            pltpu.VMEM((bh, rep, 1), jnp.float32),       # running denom
            pltpu.VMEM((bh, rep, dh_pool), jnp.float32),  # weighted-V acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ps=ps, nblk=nblk, sm_scale=sm_scale,
                          window=window, softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, dh_pool), jnp.float32),
        name="paged_attention", interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32),
      context_lens.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(b, hq, dh_pool)[..., :dh]
