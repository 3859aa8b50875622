"""Core transformer layers: norms, RoPE, GQA attention (full / sliding /
local / cross), gated MLP, and token-choice MoE with sort-based dispatch.

Conventions
-----------
* All linear weights are (in_features, out_features); every matmul routes
  through :func:`repro.models.linear.dense` so quantized weight pytrees
  (``repro.core.qlinear.QLinear``) drop in transparently.
* ``init_*`` functions return trees of :class:`repro.models.param.P`
  (shape + logical sharding axes); ``apply_*`` take the materialized (or
  quantized) tree.
* Attention decode caches are ring buffers of ``window`` slots holding a
  parallel int32 absolute-position array for mask construction, so full
  and sliding-window attention share one code path.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import Parallel, hint, in_mesh
from repro.models.linear import dense, expert_dense
from repro.models.param import P

Tree = Any
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(cfg: ArchConfig, d: Optional[int] = None) -> Tree:
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": P((d,), (None,), "ones")}
    return {"scale": P((d,), (None,), "ones"), "bias": P((d,), (None,), "zeros")}


def apply_norm(cfg: ArchConfig, p: Tree, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"].astype(jnp.float32)
        return y.astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, dh), positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def init_attention(cfg: ArchConfig, par: Parallel, cross: bool = False) -> Tree:
    """Parameters stay at the architecture's TRUE n_kv_heads (faithful
    param counts); Megatron-style KV replication to the TP degree happens
    at runtime in _project_qkv (a broadcast, not extra parameters)."""
    d, dh = cfg.d_model, cfg.head_dim_
    hq = cfg.n_heads
    hkv = cfg.n_kv_heads
    p = {
        "wq": P((d, hq * dh), ("embed", "heads"), "scaled"),
        "wk": P((d, hkv * dh), ("embed", "kv_heads"), "scaled"),
        "wv": P((d, hkv * dh), ("embed", "kv_heads"), "scaled"),
        "wo": P((hq * dh, d), ("heads", "embed"), "scaled"),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = P((hq * dh,), ("heads",), "zeros")
        p["bk"] = P((hkv * dh,), ("kv_heads",), "zeros")
        p["bv"] = P((hkv * dh,), ("kv_heads",), "zeros")
    if cfg.qk_norm and not cross:
        p["q_norm"] = P((dh,), (None,), "ones")
        p["k_norm"] = P((dh,), (None,), "ones")
    return p


def _qk_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _project_qkv(cfg: ArchConfig, par: Parallel, p: Tree, xq: jax.Array,
                 xkv: jax.Array, q_pos, kv_pos, use_rope: bool):
    dh = cfg.head_dim_
    hq = cfg.n_heads
    hkv = cfg.n_kv_heads
    hkv_run = par.kv_heads_run(hkv, hq)
    if "wqkv" in p and xq is xkv:
        # decode fast path: one fused matmul (and, when quantized, one
        # salient-channel gather) for all three projections
        g = p["wqkv"]
        q, k, v = g.split_out(dense(xq, g))
        if "bq" in p:
            q = q + p["bq"].astype(q.dtype)
            k = k + p["bk"].astype(k.dtype)
            v = v + p["bv"].astype(v.dtype)
    else:
        q = dense(xq, p["wq"], p.get("bq"))
        k = dense(xkv, p["wk"], p.get("bk"))
        v = dense(xkv, p["wv"], p.get("bv"))
    q = q.reshape(q.shape[:-1] + (hq, dh))
    k = k.reshape(k.shape[:-1] + (hkv, dh))
    v = v.reshape(v.shape[:-1] + (hkv, dh))
    if "q_norm" in p:
        q = _qk_norm(q, p["q_norm"])
        k = _qk_norm(k, p["k_norm"])
    if use_rope:
        q = rope(q, q_pos, cfg.rope_theta)
        k = rope(k, kv_pos, cfg.rope_theta)
    if hkv_run > hkv:
        # Megatron KV replication: repeat each true KV head f× so the KV
        # tensors/cache shard over the TP axis.  Consecutive repeats keep
        # the q-group ↔ kv-head mapping of _attend intact (group g's f
        # replicas serve q heads [g·rep0, (g+1)·rep0)).
        f = hkv_run // hkv
        k = jnp.repeat(k, f, axis=-2)
        v = jnp.repeat(v, f, axis=-2)
    return q, k, v


def _attend(q, k, v, mask, softcap: Optional[float]):
    """q:(B,Sq,Hq,dh) k,v:(B,Sk,Hkv,dh) mask:(B,Sq,Sk) or (1,Sq,Sk) bool.

    K/V stay in their storage dtype (bf16) with f32 MXU accumulation —
    converting a 32k-token cache to f32 before the QK/AV contractions
    doubles decode HBM traffic for no precision benefit (§Perf: scores
    and softmax are f32 regardless; P is fed back at bf16, the standard
    flash-attention practice)."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    qr = q.reshape(b, sq, hkv, rep, dh)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qr, k,
                   preferred_element_type=jnp.float32)
    s = s.astype(jnp.float32) / math.sqrt(dh)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhrqk,bkhd->bqhrd", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, hq, dh)


def _attend_chunked(q, k, v, q_pos, kv_pos, causal: bool,
                    window: Optional[int], softcap: Optional[float],
                    chunk: int):
    """Flash-style streaming softmax over KV chunks — O(Sq*chunk) memory.

    Positions are (B,Sq)/(B,Sk) int32; masking is positional so sliding
    windows and padding share the path.
    """
    b, sq, hq, dh = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    rep = hq // hkv
    n_chunks = sk // chunk
    assert sk % chunk == 0, (sk, chunk)
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, rep, dh) / math.sqrt(dh)
    kc = k.reshape(b, n_chunks, chunk, hkv, dh)
    vc = v.reshape(b, n_chunks, chunk, hkv, dh)
    pc = kv_pos.reshape(b, n_chunks, chunk)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs  # (B,chunk,Hkv,dh), (B,chunk)
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qf, kb.astype(jnp.float32))
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        valid = pb[:, None, :] <= q_pos[:, :, None] if causal else pb[:, None, :] >= 0
        valid = jnp.logical_and(valid, pb[:, None, :] >= 0)
        if window is not None:
            valid = jnp.logical_and(valid, q_pos[:, :, None] - pb[:, None, :] < window)
        s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhrqk,bkhd->bhrqd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, rep, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kc.swapaxes(0, 1), vc.swapaxes(0, 1), pc.swapaxes(0, 1)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dh)


def make_cache(cfg: ArchConfig, par: Parallel, batch: int, window: int,
               n_layers: int, dtype=jnp.bfloat16) -> Dict[str, P]:
    """KV ring-buffer declaration for one scanned stack of `n_layers`."""
    dh = cfg.head_dim_
    hkv = par.kv_heads_run(cfg.n_kv_heads, cfg.n_heads)
    return {
        "k": P((n_layers, batch, window, hkv, dh),
               ("layers", "batch", None, "kv_heads", None), "zeros", dtype),
        "v": P((n_layers, batch, window, hkv, dh),
               ("layers", "batch", None, "kv_heads", None), "zeros", dtype),
        "p": P((n_layers, batch, window), ("layers", "batch", None), "zeros",
               jnp.int32),
    }


def attention_full(cfg: ArchConfig, par: Parallel, p: Tree, x: jax.Array,
                   positions: jax.Array, *, causal: bool = True,
                   window: Optional[int] = None, use_rope: bool = True,
                   xkv: Optional[jax.Array] = None,
                   kv_positions: Optional[jax.Array] = None,
                   cache_window: Optional[int] = None):
    """Training / prefill attention over a whole sequence (optionally cross).

    When ``cache_window`` is given, also returns the decode ring cache built
    from the K/V already computed here (no re-projection).
    """
    xkv = x if xkv is None else xkv
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(cfg, par, p, x, xkv, positions, kv_positions, use_rope)
    sk = k.shape[1]
    if sk > par.attn_chunk and sk % par.attn_chunk == 0:
        o = _attend_chunked(q, k, v, positions, kv_positions, causal, window,
                            cfg.logit_softcap, par.attn_chunk)
    else:
        sq = q.shape[1]
        qp, kp = positions[:, :, None], kv_positions[:, None, :]
        mask = kp <= qp if causal else jnp.ones((1, sq, sk), bool)
        # position -1 marks padding (engine left-pad); never attended —
        # the chunked path below has always masked pb >= 0 the same way
        mask = jnp.logical_and(mask, kp >= 0)
        if window is not None:
            mask = jnp.logical_and(mask, qp - kp < window)
        o = _attend(q, k, v, mask, cfg.logit_softcap)
    o = o.astype(x.dtype).reshape(x.shape[:-1] + (-1,))
    out = dense(o, p["wo"])
    if cache_window is None:
        return out
    return out, ring_cache_from_kv(k, v, kv_positions, cache_window)


def ring_cache_from_kv(k: jax.Array, v: jax.Array, positions: jax.Array,
                       window: int):
    """Build the ring cache from prefill K/V: keep the last `window` slots."""
    s = k.shape[1]
    if s >= window:
        k_c, v_c, p_c = (k[:, -window:], v[:, -window:], positions[:, -window:])
    else:
        pad = window - s
        k_c = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_c = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        p_c = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    # ring-order the slots so slot = pos % window
    idx = p_c % window
    order = jnp.argsort(idx, axis=1)
    take = lambda a: jnp.take_along_axis(a, order[..., None, None], axis=1) \
        if a.ndim == 4 else jnp.take_along_axis(a, order, axis=1)
    return {"k": take(k_c), "v": take(v_c), "p": take(p_c)}


def attention_decode(cfg: ArchConfig, par: Parallel, p: Tree, x: jax.Array,
                     pos: jax.Array, cache: Tree, *, use_rope: bool = True,
                     window: Optional[int] = None,
                     layer: Optional[int] = None):
    """Single-token decode against a ring cache.

    x: (B,1,D); pos: (B,) absolute position of the new token;
    cache: {"k","v": (B,W,Hkv,dh), "p": (B,W)} — or, when ``layer`` is
    given (unrolled decode, §Perf), the STACKED (L,B,W,Hkv,dh) buffers:
    the new slot scatters directly into the stacked cache so the update
    writes B·Hkv·dh elements instead of round-tripping a whole (B,W,…)
    slice through the scan carry.
    """
    b = x.shape[0]
    q, k, v = _project_qkv(cfg, par, p, x, x, pos[:, None], pos[:, None], use_rope)
    bi = jnp.arange(b)
    if layer is None:
        w = cache["k"].shape[1]
        slot = pos % w
        ck = cache["k"].at[bi, slot].set(k[:, 0])
        cv = cache["v"].at[bi, slot].set(v[:, 0])
        cp = cache["p"].at[bi, slot].set(pos)
        new_cache = {"k": ck, "v": cv, "p": cp}
    else:
        w = cache["k"].shape[2]
        slot = pos % w
        ck_full = cache["k"].at[layer, bi, slot].set(k[:, 0])
        cv_full = cache["v"].at[layer, bi, slot].set(v[:, 0])
        cp_full = cache["p"].at[layer, bi, slot].set(pos)
        ck, cv, cp = ck_full[layer], cv_full[layer], cp_full[layer]
        new_cache = {"k": ck_full, "v": cv_full, "p": cp_full}
    qp = pos[:, None, None]
    kp = cp[:, None, :]
    mask = jnp.logical_and(kp <= qp, kp >= 0)
    if window is not None:
        mask = jnp.logical_and(mask, qp - kp < window)
    o = _attend(q, ck, cv, mask, cfg.logit_softcap)
    o = o.astype(x.dtype).reshape(b, 1, -1)
    return dense(o, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# Paged attention (serving runtime)
# ---------------------------------------------------------------------------
def make_paged_cache(cfg: ArchConfig, par: Parallel, num_pages: int,
                     page_size: int, n_layers: int,
                     dtype=jnp.bfloat16) -> Dict[str, P]:
    """KV *page pool* declaration for one scanned stack of ``n_layers``.

    Unlike :func:`make_cache` there is no per-slot position array: the
    layout is position-aligned (token ``t`` of a request lives at page
    ``block_table[t // page_size]``, slot ``t % page_size``), so the
    decode mask derives key positions from block/slot indices.  Reused
    pages therefore need no clearing — stale slots are masked out by the
    new owner's context length.

    The pool's head dim is ``ops.padded_head_dim(dh)``: on a real TPU,
    archs whose ``dh`` is off the 128-lane tile get zero-padded pool
    tiles so the flash-decode kernel can serve them instead of falling
    back to the XLA dense gather.  Writers pad K/V to the pool width;
    readers slice back to the logical ``dh`` (exact — see the kernel
    wrapper's docstring).

    One extra physical page beyond ``num_pages`` is allocated as the
    **dump page**: the chunked-prefill kernel's fused scatter needs a
    real write target for masked writes (shared/unassigned blocks,
    ragged chunk tails) where the XLA scatter uses ``mode="drop"``.  No
    block table ever references it (the allocator hands out ids
    ``[0, num_pages)``), so its garbage is unreachable, and the XLA
    paths' out-of-range sentinel ``num_pages + 1`` still drops.
    """
    from repro.kernels import ops
    dh = ops.padded_head_dim(cfg.head_dim_)
    hkv = par.kv_heads_run(cfg.n_kv_heads, cfg.n_heads)
    shape = (n_layers, num_pages + 1, page_size, hkv, dh)
    axes = ("layers", None, None, "kv_heads", None)
    return {"k": P(shape, axes, "zeros", dtype),
            "v": P(shape, axes, "zeros", dtype)}


def paged_key_positions(block_tables: jax.Array, page_size: int) -> jax.Array:
    """(B, nblk) block tables -> (B, nblk*page_size) implied key positions.

    Slot ``j`` of block ``i`` holds position ``i*page_size + j``;
    unassigned blocks (table entry < 0) yield position -1 (masked)."""
    b, nblk = block_tables.shape
    base = jnp.arange(nblk, dtype=jnp.int32)[:, None] * page_size
    kp = (base + jnp.arange(page_size, dtype=jnp.int32)[None, :])  # (nblk,ps)
    kp = jnp.broadcast_to(kp[None], (b, nblk, page_size))
    kp = jnp.where(block_tables[:, :, None] >= 0, kp, -1)
    return kp.reshape(b, nblk * page_size)


def scatter_pages(pool: Dict[str, jax.Array], k: jax.Array, v: jax.Array,
                  positions: jax.Array, bt_row: jax.Array) -> Dict[str, jax.Array]:
    """Scatter prefill K/V into pool pages (all layers at once).

    pool: {"k","v": (L, P, ps, hkv, dh)}; k/v: (L, S, hkv, dh) with the
    per-token absolute ``positions`` (S,) int32 (−1 = padding, dropped);
    ``bt_row`` (nblk,) is the owning request's block table.  Invalid
    tokens are routed to the out-of-range page id ``P`` and dropped by
    the scatter — no host-side compaction needed.
    """
    num_pages, ps = pool["k"].shape[1], pool["k"].shape[2]
    if k.shape[-1] < pool["k"].shape[-1]:    # lane-padded pool: pad tail
        padw = ((0, 0),) * (k.ndim - 1) + \
            ((0, pool["k"].shape[-1] - k.shape[-1]),)
        k, v = jnp.pad(k, padw), jnp.pad(v, padw)
    t = positions.astype(jnp.int32)
    tc = jnp.clip(t, 0)
    blk = jnp.clip(tc // ps, 0, bt_row.shape[0] - 1)
    # both invalid positions AND unassigned blocks (bt_row entry -1)
    # route out of range — a -1 page id would wrap to the last pool page
    # and corrupt another request's KV
    valid = jnp.logical_and(t >= 0, bt_row[blk] >= 0)
    page = jnp.where(valid, bt_row[blk], num_pages)      # OOR -> dropped
    slot = tc % ps
    return {"k": pool["k"].at[:, page, slot].set(k, mode="drop"),
            "v": pool["v"].at[:, page, slot].set(v, mode="drop")}


def attention_decode_paged(cfg: ArchConfig, par: Parallel, p: Tree,
                           x: jax.Array, pos: jax.Array, cache: Tree,
                           block_tables: jax.Array, *, layer: int,
                           lengths: Optional[jax.Array] = None,
                           use_rope: bool = True,
                           window: Optional[int] = None,
                           use_kernel: bool = True):
    """Single-token decode against the shared page pool.

    x: (B,1,D); pos: (B,) absolute positions; cache: {"k","v"} page pools
    of shape (L, P, ps, hkv, dh); block_tables: (B, nblk) int32 page ids,
    -1 = unassigned; lengths: (B,) int32 live context per request
    (pos+1 for active rows, 0 for inactive — the engine plumbs them from
    ``BlockTables.context_lens``).  The new K/V scatter-writes into the
    owner's page (requests with no page for ``pos`` — inactive slots —
    scatter to the out-of-range sentinel and are dropped).

    The read has two paths, mirroring ``ops.mixed_matmul``:

    * **Pallas flash-decode kernel** (default on feasible shapes, needs
      ``lengths``): walks each request's pages of ``layer`` straight out
      of the stacked pool with scalar-prefetched block tables — per-token
      KV traffic scales with the LIVE context, and neither a (B,
      nblk*ps, hkv, dh) gather buffer nor a copy of the layer's pool
      ever exists in HBM (``repro.kernels.paged_attention``).
    * **XLA gather reference/fallback**: gathers the request's pages
      into a dense context and masks by the implied positions — the
      oracle the kernel is tested against, and the path taken when the
      shape is infeasible or ``use_kernel=False``.
    """
    b = x.shape[0]
    q, k, v = _project_qkv(cfg, par, p, x, x, pos[:, None], pos[:, None],
                           use_rope)
    num_pages, ps = cache["k"].shape[1], cache["k"].shape[2]
    nblk = block_tables.shape[1]
    dh = k.shape[-1]
    dh_pool = cache["k"].shape[-1]
    kw, vw = k[:, 0], v[:, 0]
    if dh_pool > dh:        # lane-padded pool (ops.padded_head_dim)
        padw = ((0, 0), (0, 0), (0, dh_pool - dh))
        kw, vw = jnp.pad(kw, padw), jnp.pad(vw, padw)
    # -- write the new token's K/V into its page ------------------------
    blk = jnp.clip(pos // ps, 0, nblk - 1)
    bi = jnp.arange(b)
    page = block_tables[bi, blk]                         # (B,)
    page = jnp.where(page >= 0, page, num_pages)         # OOR -> dropped
    slot = pos % ps
    ck = cache["k"].at[layer, page, slot].set(kw, mode="drop")
    cv = cache["v"].at[layer, page, slot].set(vw, mode="drop")
    new_cache = {"k": ck, "v": cv}
    # -- attend over this request's pages -------------------------------
    from repro.kernels import ops
    hkv = k.shape[2]
    hq = q.shape[2]
    choice = (ops.paged_attention_blocks(ps, hkv, hq // hkv, dh, nblk,
                                         pool_dh=dh_pool)
              if use_kernel and lengths is not None else None)
    if choice is not None:
        o = ops.paged_attention(q[:, 0], ck, cv, block_tables, lengths,
                                layer=layer, window=window,
                                softcap=cfg.logit_softcap, ppcb=choice.ppcb)
        o = o[:, None]                                   # (B, 1, hq, dh)
    else:
        bt = jnp.clip(block_tables, 0)                   # (B, nblk)
        k_ctx = ck[layer][bt].reshape(b, nblk * ps, -1,
                                      dh_pool)[..., :dh]
        v_ctx = cv[layer][bt].reshape(b, nblk * ps, -1,
                                      dh_pool)[..., :dh]
        kp = paged_key_positions(block_tables, ps)       # (B, nblk*ps)
        qp = pos[:, None, None]
        mask = jnp.logical_and(kp[:, None, :] <= qp, kp[:, None, :] >= 0)
        if window is not None:
            mask = jnp.logical_and(mask, qp - kp[:, None, :] < window)
        o = _attend(q, k_ctx, v_ctx, mask, cfg.logit_softcap)
    o = o.astype(x.dtype).reshape(b, 1, -1)
    return dense(o, p["wo"]), new_cache


def attention_prefill_paged(cfg: ArchConfig, par: Parallel, p: Tree,
                            x: jax.Array, positions: jax.Array,
                            cache: Tree, bt_read: jax.Array,
                            bt_write: jax.Array, start, length, *,
                            layer: int, window: Optional[int] = None,
                            use_kernel: bool = True):
    """One CHUNK of paged prefill for one request: project the chunk's
    Q/K/V, write K/V straight into the request's pool pages and attend
    the chunk queries against all previously-written context pages plus
    the in-chunk causal prefix — fused in one kernel call, no dense
    per-request prefill cache.

    x: (1, C, D) the chunk's hidden states (rows past ``length`` are
    padding); positions: (1, C) absolute positions ``start + i``;
    cache: {"k","v"} page pools (L, P+1, ps, hkv, dh) — the last
    physical page is the masked-write dump page; bt_read: (nblk,) the
    request's block table; bt_write: (nblk,) its writable row (shared
    blocks -1, so prefix-attached pages are never rewritten); start:
    page-aligned chunk origin; length: live tokens in the chunk.

    K/V are cast to the pool dtype BEFORE both the write and the
    in-chunk attention, so the chunk attends exactly the bytes later
    chunks and decode steps will read back — which is what makes
    chunked and whole-prompt prefill agree in f32 pools.

    Dispatches the Pallas fused scatter+attend kernel on feasible
    shapes (mirroring ``attention_decode_paged``) and falls back to
    ``ops.paged_prefill_xla``, the bit-compatible dense-gather
    reference.
    """
    c = x.shape[1]
    q, k, v = _project_qkv(cfg, par, p, x, x, positions, positions, True)
    kw = k[0].astype(cache["k"].dtype)
    vw = v[0].astype(cache["v"].dtype)
    from repro.kernels import ops
    hkv = k.shape[2]
    hq = q.shape[2]
    dh = k.shape[-1]
    dh_pool = cache["k"].shape[-1]
    ps = cache["k"].shape[2]
    choice = (ops.paged_prefill_blocks(c, ps, hkv, hq // hkv, dh,
                                       pool_dh=dh_pool)
              if use_kernel else None)
    if choice is not None:
        o, kp, vp = ops.paged_prefill(
            q[0], kw, vw, cache["k"], cache["v"], bt_read, bt_write,
            start, length, layer=layer, window=window,
            softcap=cfg.logit_softcap, bh=choice.bh)
    else:
        o, kp, vp = ops.paged_prefill_xla(
            q[0], kw, vw, cache["k"], cache["v"], bt_read, bt_write,
            start, length, layer=layer, window=window,
            softcap=cfg.logit_softcap)
    o = o.astype(x.dtype).reshape(1, c, -1)
    return dense(o, p["wo"]), {"k": kp, "v": vp}


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------
def init_mlp(cfg: ArchConfig, d_ff: Optional[int] = None) -> Tree:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wg": P((d, f), ("embed", "ffn"), "scaled"),
        "wu": P((d, f), ("embed", "ffn"), "scaled"),
        "wd": P((f, d), ("ffn", "embed"), "scaled"),
    }


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(name)


def apply_mlp(cfg: ArchConfig, p: Tree, x: jax.Array) -> jax.Array:
    if "wgu" in p:
        # decode fast path: fused gate+up (one matmul / one gather)
        gu = p["wgu"]
        g, u = gu.split_out(dense(x, gu))
        g = _act(cfg.act, g)
    else:
        g = _act(cfg.act, dense(x, p["wg"]))
        u = dense(x, p["wu"])
    return dense(g * u, p["wd"])


# ---------------------------------------------------------------------------
# Mixture of Experts — token-choice top-k, sort-free capacity dispatch.
# ---------------------------------------------------------------------------
def init_moe(cfg: ArchConfig) -> Tree:
    assert cfg.moe is not None
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    # router stays fp16/fp32 and replicated — tiny and saliency-critical
    # (same exemption class as norms; see DESIGN.md §4).
    return {
        "router": P((d, e), ("embed", None), "scaled", jnp.float32),
        "wg": P((e, d, f), ("experts", "embed", "ffn"), "scaled"),
        "wu": P((e, d, f), ("experts", "embed", "ffn"), "scaled"),
        "wd": P((e, f, d), ("experts", "ffn", "embed"), "scaled"),
    }


def moe_capacity(cfg: ArchConfig, n_tokens: int) -> int:
    m = cfg.moe
    cap = int(math.ceil(m.top_k * m.capacity_factor * n_tokens / m.n_experts))
    return max(8, ((cap + 7) // 8) * 8)


def apply_moe(cfg: ArchConfig, p: Tree, x: jax.Array,
              par: Optional[Parallel] = None) -> jax.Array:
    """Capacity-bound token-choice MoE.

    Dispatch is scatter-based (cumsum position-in-expert + one scatter),
    not the GShard O(T·E·C·D) one-hot einsum — the einsum dispatch FLOPs
    would exceed the expert FLOPs ~20× at Mixtral scale (see DESIGN.md).
    Overflowing tokens past capacity are dropped (standard token-choice
    semantics); their residual path passes through unchanged.

    Under a multi-device mesh the dispatch runs GROUP-LOCAL inside
    shard_map (GShard local-group capacity): plain-GSPMD scatter dispatch
    all-gathers every token to every device (measured 51GB/layer on
    mixtral prefill_32k — §Perf); with shard_map each device routes only
    its own tokens and the only cross-device traffic is the wd partial-sum
    (train) or the g·u feature gather (quantized serving).
    """
    m = cfg.moe
    b, s, d = x.shape
    if par is not None and _moe_shardable(par, b, s):
        return _apply_moe_shard_map(cfg, p, x, par)
    t = b * s
    xt = x.reshape(t, d)
    cap = moe_capacity(cfg, t)

    logits = xt.astype(jnp.float32) @ p["router"]          # (T,E)
    gate_w, gate_e = jax.lax.top_k(logits, m.top_k)        # (T,k)
    gate_w = jax.nn.softmax(gate_w, axis=-1).astype(x.dtype)

    flat_e = gate_e.reshape(-1)                            # (T*k,)
    onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1              # (T*k,E)
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    dest_e = jnp.where(keep, flat_e, m.n_experts)          # overflow -> ghost
    dest_c = jnp.where(keep, pos, 0)

    src = jnp.repeat(jnp.arange(t), m.top_k)
    buf = jnp.zeros((m.n_experts + 1, cap, d), x.dtype)
    buf = buf.at[dest_e, dest_c].set(xt[src])
    buf = buf[: m.n_experts]

    if "wgu" in p:
        # fused expert gate+up: one batched matmul (and one per-expert
        # salient-channel gather when quantized) for both projections
        g, u = p["wgu"].split_out(expert_dense(buf, p["wgu"]))
        g = _act(cfg.act, g)
    else:
        g = _act(cfg.act, expert_dense(buf, p["wg"]))
        u = expert_dense(buf, p["wu"])
    y = expert_dense(g * u, p["wd"])                       # (E,cap,D)

    gathered = y[dest_e.clip(0, m.n_experts - 1), dest_c]  # (T*k,D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    w = gate_w.reshape(-1)[:, None].astype(gathered.dtype)
    out = jnp.zeros((t, d), gathered.dtype).at[src].add(gathered * w)
    return out.reshape(b, s, d)


def _moe_shardable(par: Parallel, b: int, s: int) -> bool:
    from repro.models.common import current_mesh
    mesh = current_mesh()
    if mesh is None or not hasattr(mesh, "devices"):
        return False
    if mesh.devices.size <= 1 or not par.shard_batch:
        return False
    return b % max(par.dp, 1) == 0 and s > 1


def _moe_dispatch_local(cfg: ArchConfig, router: jax.Array, xt: jax.Array):
    """Token-choice routing + capacity dispatch over LOCAL tokens.
    Returns (buf (E,cap,D), src, dest_e, dest_c, keep, gate_w)."""
    m = cfg.moe
    t, d = xt.shape
    cap = moe_capacity(cfg, t)
    logits = xt.astype(jnp.float32) @ router               # (T,E)
    gate_w, gate_e = jax.lax.top_k(logits, m.top_k)
    gate_w = jax.nn.softmax(gate_w, axis=-1).astype(xt.dtype)
    flat_e = gate_e.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    dest_e = jnp.where(keep, flat_e, m.n_experts)
    dest_c = jnp.where(keep, pos, 0)
    src = jnp.repeat(jnp.arange(t), m.top_k)
    buf = jnp.zeros((m.n_experts + 1, cap, d), xt.dtype)
    buf = buf.at[dest_e, dest_c].set(xt[src])
    return buf[: m.n_experts], src, dest_e, dest_c, keep, gate_w


def _moe_combine_local(cfg: ArchConfig, y: jax.Array, t: int, src, dest_e,
                       dest_c, keep, gate_w) -> jax.Array:
    m = cfg.moe
    gathered = y[dest_e.clip(0, m.n_experts - 1), dest_c]
    gathered = jnp.where(keep[:, None], gathered, 0)
    w = gate_w.reshape(-1)[:, None].astype(gathered.dtype)
    return jnp.zeros((t, y.shape[-1]), gathered.dtype).at[src].add(
        gathered * w)


def _apply_moe_shard_map(cfg: ArchConfig, p: Tree, x: jax.Array,
                         par: Parallel) -> jax.Array:
    from jax.sharding import PartitionSpec as PS
    from repro.models.common import _batch_axes, current_mesh
    if "wgu" in p:
        # the shard-map path's specs are per-projection: serve it from
        # the group's unfused member views (same packed bytes, exact)
        wg, wu = p["wgu"].members()
        p = {**{k: v for k, v in p.items() if k != "wgu"},
             "wg": wg, "wu": wu}
    mesh = current_mesh()
    baxes = _batch_axes()
    quantized = hasattr(p["wg"], "__expert_matmul__")

    def leaf_spec_out_sharded(q, leaf_is=None):
        """Specs for wg/wu: output (N=d_ff) dim over 'model'."""
        if not quantized:
            return PS(None, None, "model")
        n = q.n
        return jax.tree.map(
            lambda a: PS(*([None] * (a.ndim - 1)), "model")
            if a.shape[-1] == n else PS(*([None] * a.ndim)), q)

    if quantized:
        wg_spec = leaf_spec_out_sharded(p["wg"])
        wu_spec = leaf_spec_out_sharded(p["wu"])
        # wd keeps its (permuted, packed) K intact: replicate it and
        # all-gather the g·u features inside (see module docstring)
        wd_spec = jax.tree.map(lambda a: PS(*([None] * a.ndim)), p["wd"])
    else:
        wg_spec = wu_spec = PS(None, None, "model")
        wd_spec = PS(None, "model", None)       # contracting dim sharded

    def local(router, wg, wu, wd, xs):
        # tokens are data-sharded and REPLICATED across the model axis
        # (deterministic dispatch → every model rank routes identically);
        # expert features are model-sharded.  The token-level partial is
        # psum'd once AFTER combine — combine is linear in y, and the
        # token layout is ~2.5× smaller than the capacity buffers.
        bl, sl, d = xs.shape
        xt = xs.reshape(bl * sl, d)
        buf, src, dest_e, dest_c, keep, gate_w = _moe_dispatch_local(
            cfg, router, xt)
        g = _act(cfg.act, expert_dense(buf, wg))
        u = expert_dense(buf, wu)
        gu = g * u                                   # (E,cap,F_loc)
        if quantized:
            gu = jax.lax.all_gather(gu, "model", axis=2, tiled=True)
            y = expert_dense(gu, wd)                 # full K, exact
            out = _moe_combine_local(cfg, y, xt.shape[0], src, dest_e,
                                     dest_c, keep, gate_w)
        else:
            y = expert_dense(gu, wd)                 # partial over F_loc
            out = _moe_combine_local(cfg, y, xt.shape[0], src, dest_e,
                                     dest_c, keep, gate_w)
            out = jax.lax.psum(out, "model")
        return out.reshape(bl, sl, -1)

    # replication checking off: the dispatch body's collectives are untyped
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(None, None), wg_spec, wu_spec, wd_spec,
                  PS(baxes, None, None)),
        out_specs=PS(baxes, None, None), check_vma=False)
    return fn(p["router"], p["wg"], p["wu"], p["wd"], x)


def moe_aux_loss(cfg: ArchConfig, x: jax.Array, router: jax.Array) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    m = cfg.moe
    t = x.shape[0] * x.shape[1]
    logits = x.reshape(t, -1).astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, -1)
    _, top1 = jax.lax.top_k(logits, 1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1[:, 0], m.n_experts), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return m.n_experts * jnp.sum(frac_tokens * frac_probs)
