"""Pallas TPU kernel: fused scatter+attend chunked prefill over the KV pool.

One pallas_call advances ONE request's prefill by a chunk of ``C`` prompt
tokens: it writes the chunk's K/V straight into the request's pool pages
(the block table rides in as a scalar-prefetch operand, exactly like the
flash-decode kernel in ``paged_attention.py``) and computes causal flash
attention of the chunk's queries against all previously-written context
pages plus the in-chunk causal prefix — WITHOUT ever materializing the
dense ``(B, bucket, hkv, dh)`` prefill cache the whole-prompt path
splices from.  Per-chunk HBM traffic is ∝ (live context pages read +
chunk pages written), which is what lets a long prompt advance a bounded
slice per engine tick instead of stalling every in-flight decode.

Mechanics (the scalar-prefetch contract):

* ``bt_read`` is the request's full block-table row: grid step ``(hg, j)``
  with ``j < nblk`` DMAs context page ``bt_read[j]`` HBM→VMEM through the
  K/V BlockSpec index map.  Steps past the live context (``j*ps >=
  start``), before the sliding-window start, or on unassigned entries
  clamp onto an already-fetched page — no new DMA, the index-map form
  of the decode kernel's ``paged_attention.page_fetched``.
* ``bt_write`` is the request's *writable* row
  (:meth:`repro.runtime.paged_cache.BlockTables.writable_row`): shared
  (prefix-attached / COW) blocks are masked to ``-1`` and their writes
  are routed to the pool's **dump page** (the physical page at index
  ``num_pages`` that :func:`repro.models.layers.make_paged_cache`
  over-allocates) — the fused scatter needs a real write target where
  the XLA path uses ``mode="drop"``.
* The grid walks ``(hkv/bh, nblk + C/ps)``: the first ``nblk`` steps
  stream context pages through the online-softmax scratch
  ``(m, l, acc)``; the last ``C/ps`` steps attend the chunk's own K
  tiles (causal, straight from VMEM — in-chunk keys never round-trip
  through HBM) AND write each chunk page tile into the pool through the
  aliased K/V outputs.  GQA head groups, sliding window and logit
  softcap follow the decode kernel exactly.
* ``start`` must be page-aligned and ``C`` a page-size multiple, so
  every chunk page holds only chunk tokens; the final (ragged) chunk
  carries ``length < C`` and masks its dead tail both in attention and
  in the write index map (fully-dead pages go to the dump page).

Numerics: K/V arrive already cast to the pool dtype (so in-chunk
attention sees exactly the bytes later chunks will read back), scores
and softmax are f32, probabilities feed back at the V dtype —
bit-compatible with :func:`paged_prefill_xla`, the dense-gather
reference below that ``repro.models.layers.attention_prefill_paged``
falls back to on infeasible shapes.  The reference accumulates over the
SAME page-tile sequence with the same dot_general calls, so kernel and
fallback agree bit-exactly in f32 (the oracle property the tests pin).
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


def ctx_block_index(j, bt_read, start, *, ps: int, nblk: int,
                    window: Optional[int]):
    """Context pool page the K/V BlockSpec addresses at grid step
    ``(·, j)`` — the prefill twin of ``paged_attention.page_fetched``:
    steps past the last context page (``j*ps >= start``), before the
    sliding-window start, or on dead entries clamp onto an
    already-fetched page so the pipeline issues no new DMA."""
    last = jnp.maximum(start // ps - 1, 0)
    if window is None:
        first = 0
    else:
        # oldest chunk query sits at position `start`: pages wholly
        # below start+1-window are invisible to every chunk query
        first = jnp.minimum(jnp.maximum(start + 1 - window, 0) // ps, last)
    jj = jnp.clip(j, first, last)
    return jnp.maximum(bt_read[jj], 0)


def _kernel(bt_r_ref, bt_w_ref, meta_ref, q_ref, kn_ref, vn_ref,
            kp_ref, vp_ref, o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref,
            *, ps, nblk, ncp, c, sm_scale, window, softcap):
    j = pl.program_id(1)
    start = meta_ref[0]
    length = meta_ref[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    is_chunk = j >= nblk
    cp = jnp.maximum(j - nblk, 0)

    # ---- liveness ----------------------------------------------------
    ctx_live = jnp.logical_and(
        jnp.logical_not(is_chunk),
        jnp.logical_and(bt_r_ref[jnp.minimum(j, nblk - 1)] >= 0,
                        j * ps < start))
    if window is not None:
        ctx_live = jnp.logical_and(ctx_live,
                                   (j + 1) * ps > start + 1 - window)
    chunk_live = jnp.logical_and(is_chunk, cp * ps < length)

    qp = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, c, 1), 2)

    def _tile(k, v, valid):
        """One online-softmax accumulation step over a (ps,) key tile."""
        q = q_ref[...]                       # (bh, rep, C, dhp)
        s = jax.lax.dot_general(             # (bh, rep, C, ps)
            q, k, (((3,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        s = s.astype(jnp.float32) * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((3,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ctx_live)
    def _context():
        kp = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, ps), 3)
        valid = kp < start                   # context is strictly pre-chunk
        if window is not None:
            valid = jnp.logical_and(valid, qp - kp < window)
        _tile(kp_ref[0, 0], vp_ref[0, 0], valid)

    @pl.when(chunk_live)
    def _chunk():
        kp = (start + cp * ps
              + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, ps), 3))
        valid = jnp.logical_and(kp <= qp, kp < start + length)
        if window is not None:
            valid = jnp.logical_and(valid, qp - kp < window)
        _tile(kn_ref[0], vn_ref[0], valid)

    # ---- fused scatter: chunk K/V tiles land in their pool pages -----
    # (context steps map to the dump page — see the write index map —
    # so the unconditional store never touches live pages there)
    ko_ref[0, 0] = kn_ref[0]
    vo_ref[0, 0] = vn_ref[0]

    @pl.when(j == nblk + ncp - 1)
    def _finalize():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("layer", "window", "softcap",
                                             "bh", "interpret"))
def paged_prefill(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                  k_pool: jax.Array, v_pool: jax.Array,
                  bt_read: jax.Array, bt_write: jax.Array,
                  start, length, *, layer: int,
                  window: Optional[int] = None,
                  softcap: Optional[float] = None,
                  bh: Optional[int] = None,
                  interpret: bool = True):
    """Fused chunk prefill: scatter + causal flash attention over pages.

    q (C, hq, dh); k_new/v_new (C, hkv, dh) ALREADY cast to the pool
    dtype; k_pool/v_pool (L, P+1, ps, hkv, dh_pool) — the last physical
    page is the dump page for masked writes; bt_read (nblk,) the
    request's block table; bt_write (nblk,) its writable row (shared
    blocks -1); start int32 page-aligned chunk origin; length int32 live
    tokens in the chunk (1..C).  Returns ``(o, k_pool', v_pool')`` with
    o (C, hq, dh) f32 — rows past ``length`` are garbage (masked
    queries) and must not be consumed.
    """
    c, hq, dh = q.shape
    nlayers, pp, ps, hkv, dhp = k_pool.shape
    nblk = bt_read.shape[0]
    rep = hq // hkv
    if hq % hkv:
        raise ValueError(f"hq={hq} not a multiple of hkv={hkv}")
    if c % ps:
        raise ValueError(f"chunk {c} not a multiple of page size {ps}")
    ncp = c // ps
    dump = pp - 1
    sm_scale = 1.0 / math.sqrt(dh)
    if dhp > dh:
        padw = ((0, 0), (0, 0), (0, dhp - dh))
        q = jnp.pad(q, padw)
        k_new, v_new = jnp.pad(k_new, padw), jnp.pad(v_new, padw)
    if bh is None:
        choice = autotune.choose_prefill_blocks(c, hkv, rep, dhp, ps)
        if choice is None:
            raise ValueError(
                f"no feasible paged-prefill blocks for (C, hkv, rep, dh, ps)"
                f"=({c}, {hkv}, {rep}, {dhp}, {ps}); route through "
                f"repro.models.layers.attention_prefill_paged for the XLA "
                f"fallback")
        bh = choice.bh
    if hkv % bh:
        raise ValueError(f"bh={bh} must divide hkv={hkv}")
    qg = q.reshape(c, hkv, rep, dhp).transpose(1, 2, 0, 3)  # (hkv,rep,C,dhp)
    knt = k_new.reshape(ncp, ps, hkv, dhp)
    vnt = v_new.reshape(ncp, ps, hkv, dhp)
    meta = jnp.asarray(
        jnp.stack([jnp.asarray(start, jnp.int32),
                   jnp.asarray(length, jnp.int32)]), jnp.int32)
    grid = (hkv // bh, nblk + ncp)
    start_page = jnp.asarray(start, jnp.int32) // ps

    def q_map(hg, j, bt_r, bt_w, m):
        return (hg, 0, 0, 0)

    def kn_map(hg, j, bt_r, bt_w, m):
        return (jnp.clip(j - nblk, 0, ncp - 1), 0, hg, 0)

    def kv_in_map(hg, j, bt_r, bt_w, m):
        # context fetch contract (see ctx_block_index): dead/chunk steps
        # clamp onto an already-fetched page -> no new DMA
        return (layer, ctx_block_index(j, bt_r, m[0], ps=ps, nblk=nblk,
                                       window=window), 0, hg, 0)

    def kv_out_map(hg, j, bt_r, bt_w, m):
        # chunk steps write their page (masked / dead pages and every
        # context step go to the dump page)
        cp = j - nblk
        page = bt_w[jnp.clip(m[0] // ps + cp, 0, nblk - 1)]
        live = jnp.logical_and(j >= nblk,
                               jnp.logical_and(cp * ps < m[1], page >= 0))
        return (layer, jnp.where(live, page, dump), 0, hg, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bh, rep, c, dhp), q_map),
            pl.BlockSpec((1, ps, bh, dhp), kn_map),
            pl.BlockSpec((1, ps, bh, dhp), kn_map),
            pl.BlockSpec((1, 1, ps, bh, dhp), kv_in_map),
            pl.BlockSpec((1, 1, ps, bh, dhp), kv_in_map),
        ],
        out_specs=[
            pl.BlockSpec((bh, rep, c, dhp), q_map),
            pl.BlockSpec((1, 1, ps, bh, dhp), kv_out_map),
            pl.BlockSpec((1, 1, ps, bh, dhp), kv_out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((bh, rep, c, 1), jnp.float32),     # running max
            pltpu.VMEM((bh, rep, c, 1), jnp.float32),     # running denom
            pltpu.VMEM((bh, rep, c, dhp), jnp.float32),   # weighted-V acc
        ],
    )
    o, k_pool, v_pool = pl.pallas_call(
        functools.partial(_kernel, ps=ps, nblk=nblk, ncp=ncp, c=c,
                          sm_scale=sm_scale, window=window, softcap=softcap),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hkv, rep, c, dhp), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operand numbering includes the scalar-prefetch args: the pools
        # (inputs 6/7) alias outputs 1/2 so chunk pages update in place
        input_output_aliases={6: 1, 7: 2},
        name="paged_prefill", interpret=interpret,
    )(bt_read.astype(jnp.int32), bt_write.astype(jnp.int32), meta,
      qg, knt, vnt, k_pool, v_pool)
    o = o.transpose(2, 0, 1, 3).reshape(c, hq, dhp)[..., :dh]
    return o, k_pool, v_pool


def paged_prefill_xla(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                      k_pool: jax.Array, v_pool: jax.Array,
                      bt_read: jax.Array, bt_write: jax.Array,
                      start, length, *, layer: int,
                      window: Optional[int] = None,
                      softcap: Optional[float] = None):
    """Dense-gather reference/fallback for :func:`paged_prefill`.

    Gathers every context page into a dense tile stack and accumulates
    the SAME online-softmax recurrence over the SAME page-tile order
    with the same dot_general calls, so in f32 it matches the kernel
    bit-exactly (the oracle the tests pin) while still writing the
    chunk's pages through the masked scatter.  The dense (nblk*ps)
    gather buffer is exactly the intermediate the kernel avoids.
    """
    c, hq, dh = q.shape
    nlayers, pp, ps, hkv, dhp = k_pool.shape
    nblk = bt_read.shape[0]
    rep = hq // hkv
    ncp = c // ps
    dump = pp - 1
    sm_scale = 1.0 / math.sqrt(dh)
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    if dhp > dh:
        padw = ((0, 0), (0, 0), (0, dhp - dh))
        q = jnp.pad(q, padw)
        k_new, v_new = jnp.pad(k_new, padw), jnp.pad(v_new, padw)

    # ---- fused-write mirror: full chunk-page tiles, dump for masked --
    idx = jnp.arange(c, dtype=jnp.int32)
    cp = idx // ps
    page = bt_write[jnp.clip(start // ps + cp, 0, nblk - 1)]
    live_w = jnp.logical_and(cp * ps < length, page >= 0)
    page = jnp.where(live_w, page, dump)
    slot = idx % ps
    k_pool = k_pool.at[layer, page, slot].set(k_new)
    v_pool = v_pool.at[layer, page, slot].set(v_new)

    # ---- attend: context page tiles then in-chunk tiles --------------
    ctx_pages = jnp.clip(bt_read, 0)
    kt = jnp.concatenate([k_pool[layer][ctx_pages],
                          k_new.reshape(ncp, ps, hkv, dhp)])
    vt = jnp.concatenate([v_pool[layer][ctx_pages],
                          v_new.reshape(ncp, ps, hkv, dhp)])
    qg = q.reshape(c, hkv, rep, dhp).transpose(1, 2, 0, 3)
    qp = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, c, 1), 2)

    def step(carry, xs):
        m_prev, l_prev, acc_prev = carry
        k, v, j = xs
        is_chunk = j >= nblk
        cpj = jnp.maximum(j - nblk, 0)
        live = jnp.where(
            is_chunk, cpj * ps < length,
            jnp.logical_and(bt_read[jnp.minimum(j, nblk - 1)] >= 0,
                            j * ps < start))
        base = jnp.where(is_chunk, start + cpj * ps, j * ps)
        if window is not None:
            live = jnp.logical_and(
                live, jnp.logical_or(is_chunk,
                                     (j + 1) * ps > start + 1 - window))
        kp = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, ps), 3)
        valid = jnp.where(is_chunk,
                          jnp.logical_and(kp <= qp, kp < start + length),
                          kp < start)
        if window is not None:
            valid = jnp.logical_and(valid, qp - kp < window)
        s = jax.lax.dot_general(qg, k, (((3,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32)
        s = s.astype(jnp.float32) * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, -1, keepdims=True)
        acc_new = acc_prev * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((3,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        keep = lambda new, old: jnp.where(live, new, old)
        return (keep(m_new, m_prev), keep(l_new, l_prev),
                keep(acc_new, acc_prev)), None

    m0 = jnp.full((hkv, rep, c, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((hkv, rep, c, 1), jnp.float32)
    a0 = jnp.zeros((hkv, rep, c, dhp), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kt, vt, jnp.arange(nblk + ncp, dtype=jnp.int32)))
    o = acc / jnp.maximum(l, 1e-30)
    o = o.transpose(2, 0, 1, 3).reshape(c, hq, dhp)[..., :dh]
    return o, k_pool, v_pool
