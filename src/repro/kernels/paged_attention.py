"""Pallas TPU kernel: paged flash-decode attention over the shared KV pool.

One pallas_call attends every decode slot's query against its own pages
of one layer of the stacked position-aligned pool ``(L, P, ps, hkv, dh)``
WITHOUT materializing the gathered ``(B, nblk*ps, hkv, dh)`` context in
HBM — the win the paged serving path needs once PTQ1.61 weights stop
dominating decode traffic (the KV cache does).

Mechanics:

* ``block_tables`` (flattened ``(B*nblk,)``) and ``context_lens``
  ``(B,)`` ride in as *scalar-prefetch* operands, resident in SMEM
  before the grid starts.  K and V stay in HBM (``memory_space=ANY``);
  the kernel gathers pages into VMEM itself by DMA.
* One **compute block** covers ``ppcb`` pool pages (``ppcb*ps``
  tokens).  The grid walks ``(B, ceil(nblk/ppcb))`` with the block dim
  innermost; a VMEM scratch triple ``(m, l, acc)`` carries the
  online-softmax state across a request's blocks (flash-decode) and the
  normalized output is written once at the last block step.  A grid
  step costs a fixed overhead whatever it does, so packing many pages
  into one step is what keeps short contexts in a wide table cheap.
* **The fetch contract** (:func:`page_fetched`): a page is DMA'd iff
  its table entry is assigned (``>= 0``), it lies inside the table and
  it holds a token the query sees (below the length, at or past the
  sliding-window start).  A live block (:func:`block_span`) starts one
  K and one V copy per fetched page; a block wholly past the length or
  below the window starts no DMA and no compute and costs one grid
  step.  So the DMAs equal the live pages, whatever ``ppcb`` is.
* **Double buffering across steps**: two buffer slots; while block
  ``i`` computes, the DMAs of the next live block in grid order — the
  next block, or the first live block of the next row with a non-zero
  length — are already in flight.
* Never-fetched pages of a live block (holes, past the length or the
  table, below the window) are masked: their scores to ``-inf`` and
  their V rows to zero, since stale VMEM times a zero probability can
  still be NaN.
* The stacked pool is the operand, and ``layer`` picks the pages to
  DMA (``k_hbm.at[layer, page]``): a custom call cannot take a view into
  a larger buffer, so handing it ``pool[layer]`` would make XLA copy that
  layer's K and V out of the pool at every layer of every decode step.
  ``layer`` rides in as a scalar-prefetch operand too, not as a static
  argument, so every layer of a step shares one lowering of the kernel
  (a static one lowers it once a layer, which multiplies set-up time).
* GQA without a relayout: the pool is viewed as ``(L, P, ps*hkv, dh)``
  (a bitcast of its tiled HBM layout), so a block lands in VMEM as
  ``(ppcb*ps*hkv, dh)`` rows, token-major and head-minor.  All ``hq``
  queries meet all rows in one pair of MXU dots, and a score whose row
  and query belong to different kv heads is masked like a dead token:
  ``hkv`` times the (small) decode FLOPs, in place of a per-head
  strided extraction that cost more than the DMAs.  ``ppcb`` comes
  from :func:`repro.kernels.autotune.choose_paged_blocks`.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

NEG_INF = -1e30


def block_span(length, *, tile: int, nb: int, window: Optional[int],
               xp=jnp):
    """Compute blocks ``[first, end)`` of a row of ``length`` tokens that
    hold a token the decode query attends (``tile = ppcb*ps`` tokens a
    block, ``nb`` blocks a row).  Empty (``first >= end``) for inactive
    rows.  ``xp`` is ``jnp`` in the kernel and ``np`` on the host."""
    end = xp.minimum((length + tile - 1) // tile, nb)
    if window is None:
        return 0, end
    return xp.maximum(length - window, 0) // tile, end


def page_fetched(j, page, length, *, ps: int, nblk: int,
                 window: Optional[int]):
    """THE fetch contract, shared by the kernel's DMA starts and
    :func:`fetched_page_counts`: table slot ``j`` holding ``page`` is
    fetched iff it is inside the table, assigned, below the row's
    length and not wholly below the sliding-window start."""
    ok = (j < nblk) & (page >= 0) & (j * ps < length)
    if window is not None:
        ok = ok & ((j + 1) * ps > length - window)
    return ok


def fetched_page_counts(block_tables, context_lens, ps: int, *,
                        window: Optional[int] = None):
    """Replay the kernel's fetch contract over one decode step and count
    the page DMAs it starts per request row (K and V count once).

    This is measurement, not a cost model: it evaluates the same
    :func:`page_fetched` the kernel's DMA loop runs, so a regression in
    it (e.g. dead pages fetched again) shows up here — serving_bench
    asserts these counts stay within one page of each row's live
    context.  Returns an int array (B,)."""
    bt = np.asarray(block_tables)
    lens = np.asarray(context_lens)[:, None]
    j = np.arange(bt.shape[1])[None, :]
    return page_fetched(j, bt, lens, ps=ps, nblk=bt.shape[1],
                        window=window).sum(axis=1)


def attn_block_counts(context_lens, ps: int, ppcb: int, nblk: int,
                      window: Optional[int] = None):
    """(live, all) compute blocks of one kernel call over host numpy
    ``context_lens``: live is each row's :func:`block_span`, all is
    rows x ceil(nblk/ppcb)."""
    nb = -(-nblk // ppcb)
    first, end = block_span(context_lens, tile=ppcb * ps, nb=nb,
                            window=window, xp=np)
    return int((end - first).sum()), len(context_lens) * nb


def _kernel(layer_ref, bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf,
            vbuf, sems, state, mask_ref, m_ref, l_ref, acc_ref, *, ps, ppcb,
            nblk, hkv, rep, sm_scale, window, softcap):
    b, i = pl.program_id(0), pl.program_id(1)
    nrows, nb = pl.num_programs(0), pl.num_programs(1)
    tile = ppcb * ps
    rows = ps * hkv                     # buffer rows a page holds
    span = functools.partial(block_span, tile=tile, nb=nb, window=window)
    fetched = functools.partial(page_fetched, ps=ps, nblk=nblk,
                                window=window)

    @pl.when((b == 0) & (i == 0))
    def _reset():
        state[0] = 0          # buffer slot of the current block
        state[1] = 0          # 1 once the current block's DMAs were started
        state[2] = 0          # the current block has a hole in its span

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def slot_page(bi, ii, t):
        j = ii * ppcb + t
        page = bt_ref[bi * nblk + jnp.minimum(j, nblk - 1)]
        return j, page, fetched(j, page, len_ref[bi])

    layer = layer_ref[0]

    def copies(page, t, slot):
        dst = pl.ds(t * rows, rows)
        page = jnp.maximum(page, 0)
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      kbuf.at[slot, dst], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      vbuf.at[slot, dst], sems.at[1, slot]))

    # the per-page loops are unrolled: a rolled loop's scalar overhead
    # costs about as much as the copies it starts
    def start(bi, ii, slot):
        def body(t, carry):
            _, page, ok = slot_page(bi, ii, t)

            @pl.when(ok)
            def _():
                for cp in copies(page, t, slot):
                    cp.start()
            return carry
        jax.lax.fori_loop(0, ppcb, body, 0, unroll=True)

    length = len_ref[b]
    first, end = span(length)

    @pl.when((i >= first) & (i < end))
    def _block():
        slot = state[0]

        @pl.when(state[1] == 0)
        def _first():
            start(b, i, slot)

        def next_row(_):
            def row_dead(r):
                f, e = span(len_ref[jnp.minimum(r, nrows - 1)])
                return (r < nrows) & (f >= e)
            r = jax.lax.while_loop(row_dead, lambda r: r + 1, b + 1)
            f, _ = span(len_ref[jnp.minimum(r, nrows - 1)])
            return r, f

        nxt_b, nxt_i = jax.lax.cond(i + 1 < end, lambda _: (b, i + 1),
                                    next_row, None)
        state[1] = (nxt_b < nrows).astype(jnp.int32)

        @pl.when(nxt_b < nrows)
        def _prefetch():
            start(nxt_b, nxt_i, 1 - slot)
            state[0] = 1 - slot

        state[2] = 0

        def wait(t, carry):
            j, page, ok = slot_page(b, i, t)

            @pl.when(ok)
            def _():
                for cp in copies(page, t, slot):
                    cp.wait()

            @pl.when(jnp.logical_not(ok))
            def _():
                # never fetched: stale VMEM must not reach p @ V
                vbuf[slot, pl.ds(t * rows, rows)] = jnp.zeros(
                    (rows, vbuf.shape[-1]), vbuf.dtype)
                hole = fetched(j, 0, length) & (page < 0)
                state[2] = jnp.where(hole, 1, state[2])
            return carry
        jax.lax.fori_loop(0, ppcb, wait, 0, unroll=True)

        col = jax.lax.broadcasted_iota(jnp.int32, (1, tile * hkv), 1)
        tok = col // hkv                                # buffer row's token
        kp = i * tile + tok
        valid = (kp < length) & (kp < nblk * ps)
        if window is not None:
            valid = valid & (kp >= length - window)
        mask_ref[...] = valid.astype(jnp.int32)

        @pl.when(state[2] == 1)
        def _holes():
            pidx = tok // ps

            def body(t, m):
                _, _, ok = slot_page(b, i, t)
                return jnp.where((pidx == t) & jnp.logical_not(ok), 0, m)
            mask_ref[...] = jax.lax.fori_loop(0, ppcb, body, mask_ref[...])

        valid = mask_ref[...] != 0                      # (1, T*hkv)
        if hkv > 1:
            # query head h*rep + r reads kv head h; row c holds head c % hkv
            shape = (hkv * rep, tile * hkv)
            qhead = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // rep
            rhead = jax.lax.broadcasted_iota(jnp.int32, shape, 1) % hkv
            valid = valid & (qhead == rhead)
        q = q_ref[0]                                    # (hq, dh)
        k = kbuf[slot]                                  # (T*hkv, dh)
        v = vbuf[slot]
        s = jax.lax.dot_general(                        # (hq, T*hkv)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # sm_scale is 1/sqrt(dh_logical) — the LOGICAL head dim, not the
        # (possibly lane-padded) pool tile dim: padded lanes are zero in
        # q so they add nothing to the dot, but they must not inflate
        # the softmax temperature
        s = s * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _finalize():
        # inactive rows (length 0): l stays 0 -> exact zeros, never NaN
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "ppcb",
                                             "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array, *,
                    layer,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    ppcb: Optional[int] = None,
                    interpret: bool = True) -> jax.Array:
    """Flash-decode over pool pages.

    q (B, hq, dh); k_pool/v_pool (L, P, ps, hkv, dh_pool), the stacked
    pool of which ``layer`` (an int or an int32 scalar) is read (a
    single layer's pool is passed as ``pool[None]`` with ``layer=0``);
    block_tables (B, nblk) int32 page ids (-1 = unassigned);
    context_lens (B,) int32 live tokens per request (0 = inactive row ->
    zero output).  Returns (B, hq, dh) f32.  ``ppcb`` (pool pages per
    compute block) defaults to the autotuner's pick.

    ``dh_pool`` may exceed q's logical ``dh`` (lane-padded pools for
    archs with ``dh`` off the 128-lane TPU tile —
    ``ops.padded_head_dim``): q is zero-padded into the pool tile, the
    softmax scale stays 1/sqrt(dh_logical), and the padded output
    columns are sliced off — exact, since zero q lanes contribute
    nothing to q·k and the padded V columns never survive the slice.
    """
    b, hq, dh = q.shape
    n_layers, num_pages, ps, hkv, dh_pool = k_pool.shape
    nblk = block_tables.shape[1]
    rep = hq // hkv
    if hq % hkv:
        raise ValueError(f"hq={hq} not a multiple of hkv={hkv}")
    if dh_pool < dh:
        raise ValueError(f"pool head dim {dh_pool} < query head dim {dh}")
    sm_scale = 1.0 / math.sqrt(dh)
    if dh_pool > dh:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, dh_pool - dh)))
    if ppcb is None:
        choice = autotune.choose_paged_blocks(hkv, rep, dh_pool, ps, nblk)
        if choice is None:
            raise ValueError(
                f"no feasible paged-attention blocks for (hkv, rep, dh, ps)"
                f"=({hkv}, {rep}, {dh_pool}, {ps}); route through "
                f"repro.models.layers.attention_decode_paged for the XLA "
                f"fallback")
        ppcb = choice.ppcb
    ppcb = min(ppcb, nblk)
    rows = ppcb * ps * hkv

    def q_map(bi, i, lay, bt, lens):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, -(-nblk // ppcb)),
        in_specs=[
            pl.BlockSpec((1, hq, dh_pool), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hq, dh_pool), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, rows, dh_pool), k_pool.dtype),
            pltpu.VMEM((2, rows, dh_pool), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),              # (K/V, slot)
            pltpu.SMEM((3,), jnp.int32),                  # see _reset
            pltpu.VMEM((1, rows), jnp.int32),             # row mask
            pltpu.VMEM((hq, 1), jnp.float32),             # running max
            pltpu.VMEM((hq, 1), jnp.float32),             # running denom
            pltpu.VMEM((hq, dh_pool), jnp.float32),       # weighted-V acc
        ],
    )
    # (L, P, ps, hkv, dh) -> (L, P, ps*hkv, dh): a bitcast of the tiled pool
    pool_rows = (n_layers, num_pages, ps * hkv, dh_pool)
    out = pl.pallas_call(
        functools.partial(_kernel, ps=ps, ppcb=ppcb, nblk=nblk, hkv=hkv,
                          rep=rep, sm_scale=sm_scale, window=window,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dh_pool), jnp.float32),
        # steps hand DMAs in flight to later steps: the grid is one
        # sequential walk
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2),
        name="paged_attention", interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.reshape(-1).astype(jnp.int32),
      context_lens.astype(jnp.int32), q, k_pool.reshape(pool_rows),
      v_pool.reshape(pool_rows))
    return out[..., :dh]
