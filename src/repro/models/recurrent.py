"""Recurrent blocks: Griffin RG-LRU (RecurrentGemma) and xLSTM cells.

Training-time forms
-------------------
* RG-LRU: elementwise linear recurrence ``h_t = a_t*h_{t-1} + b_t`` runs as
  a log-depth ``jax.lax.associative_scan`` over the sequence.
* mLSTM: chunkwise gated-linear-attention form — O(S·L) intra-chunk
  attention + O(S/L) recurrent chunk scan carrying the (d_k × d_v) matrix
  state.  Matches the step recurrence (tested against it).
* sLSTM: strictly sequential scalar-memory cell (block-diagonal recurrent
  matrices per head) via ``lax.scan`` — inherently serial, as in the paper.

Decode-time forms are single-step state updates; the dry-run decode cells
lower these.  All weight matmuls route through ``dense`` so they quantize.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import Parallel
from repro.models.linear import dense
from repro.models.param import P

Tree = Any


# ---------------------------------------------------------------------------
# RG-LRU (Griffin recurrent block): in-proj -> [conv -> RG-LRU] * gelu gate
# ---------------------------------------------------------------------------
RG_HEADS = 8  # block-diagonal gate heads (Griffin appendix)


def init_rglru(cfg: ArchConfig) -> Tree:
    d = cfg.d_model
    r = cfg.rnn_width or d
    hd = r // RG_HEADS
    return {
        "w_x": P((d, r), ("embed", "rnn"), "scaled"),
        "w_gate": P((d, r), ("embed", "rnn"), "scaled"),
        "conv_w": P((cfg.conv_width, r), (None, "rnn"), "scaled"),
        "conv_b": P((r,), ("rnn",), "zeros"),
        # block-diagonal input/recurrence gates (heads, hd, hd)
        "w_inp": P((RG_HEADS, hd, hd), (None, None, None), "scaled"),
        "w_rec": P((RG_HEADS, hd, hd), (None, None, None), "scaled"),
        "lam": P((r,), ("rnn",), "ones", jnp.float32),   # Λ (via softplus map)
        "w_out": P((r, d), ("rnn", "embed"), "scaled"),
    }


def _rg_gates(p: Tree, x: jax.Array):
    """x: (..., R) -> input gate i_t, recurrence gate r_t (block-diag heads)."""
    shp = x.shape[:-1]
    xh = x.reshape(shp + (RG_HEADS, -1)).astype(jnp.float32)
    gi = jnp.einsum("...hd,hde->...he", xh, p["w_inp"].astype(jnp.float32))
    gr = jnp.einsum("...hd,hde->...he", xh, p["w_rec"].astype(jnp.float32))
    i_t = jax.nn.sigmoid(gi.reshape(shp + (-1,)))
    r_t = jax.nn.sigmoid(gr.reshape(shp + (-1,)))
    return i_t, r_t


_RG_C = 8.0  # Griffin's fixed exponent scale


def _rg_decay(p: Tree, r_t: jax.Array) -> jax.Array:
    # a = sigmoid(lam); a_t = a ** (c * r_t)  computed in log space
    log_a = -jax.nn.softplus(-p["lam"].astype(jnp.float32))  # log sigmoid(lam)
    return jnp.exp(_RG_C * r_t * log_a)


def _causal_conv(p: Tree, x: jax.Array, state: Optional[jax.Array]):
    """Depthwise causal conv, width cw. x:(B,S,R). state:(B,cw-1,R) or None."""
    cw = p["conv_w"].shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * p["conv_w"][i].astype(x.dtype)
              for i in range(cw))
    new_state = xp[:, -(cw - 1):]
    return out + p["conv_b"].astype(x.dtype), new_state


def rglru_seq(cfg: ArchConfig, p: Tree, x: jax.Array,
              h0: Optional[jax.Array] = None,
              conv0: Optional[jax.Array] = None):
    """Full-sequence RG-LRU block. x: (B,S,D) -> (B,S,D), final states."""
    gate = jax.nn.gelu(dense(x, p["w_gate"]))
    u = dense(x, p["w_x"])
    u, conv_state = _causal_conv(p, u, conv0)
    i_t, r_t = _rg_gates(p, u)
    a_t = _rg_decay(p, r_t)                               # (B,S,R) f32
    b_t = jnp.sqrt(jnp.maximum(1.0 - a_t * a_t, 1e-8)) * (
        i_t * u.astype(jnp.float32))
    if h0 is not None:
        # fold carry-in into the first step:  h_1 = a_1 h_0 + b_1
        b_t = b_t.at[:, 0].add(a_t[:, 0] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    a_s, h = jax.lax.associative_scan(combine, (a_t, b_t), axis=1)
    out = dense((h.astype(x.dtype) * gate), p["w_out"])
    return out, h[:, -1], conv_state


def rglru_step(cfg: ArchConfig, p: Tree, x: jax.Array, h: jax.Array,
               conv_state: jax.Array):
    """Single decode step. x: (B,1,D); h: (B,R); conv_state: (B,cw-1,R)."""
    gate = jax.nn.gelu(dense(x, p["w_gate"]))
    u = dense(x, p["w_x"])
    u, conv_state = _causal_conv(p, u, conv_state)
    i_t, r_t = _rg_gates(p, u)
    a_t = _rg_decay(p, r_t)[:, 0]
    b_t = jnp.sqrt(jnp.maximum(1.0 - a_t * a_t, 1e-8)) * (
        i_t[:, 0] * u[:, 0].astype(jnp.float32))
    h = a_t * h.astype(jnp.float32) + b_t
    out = dense(h[:, None].astype(x.dtype) * gate, p["w_out"])
    return out, h, conv_state


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix-memory cell) — chunkwise GLA formulation
# ---------------------------------------------------------------------------
def init_mlstm(cfg: ArchConfig) -> Tree:
    d = cfg.d_model
    m = int(cfg.mlstm_proj_factor * d)     # value/gate width
    h = cfg.n_heads
    return {
        "w_q": P((d, d), ("embed", "heads"), "scaled"),
        "w_k": P((d, d), ("embed", "heads"), "scaled"),
        "w_v": P((d, m), ("embed", "heads"), "scaled"),
        "w_gate": P((d, m), ("embed", "heads"), "scaled"),
        "w_if": P((d, 2 * h), ("embed", None), "scaled", jnp.float32),
        "w_out": P((m, d), ("heads", "embed"), "scaled"),
    }


def _mlstm_qkvg(cfg: ArchConfig, p: Tree, x: jax.Array):
    h = cfg.n_heads
    q = dense(x, p["w_q"])
    k = dense(x, p["w_k"])
    v = dense(x, p["w_v"])
    g = jax.nn.silu(dense(x, p["w_gate"]))
    shp = x.shape[:-1]
    q = q.reshape(shp + (h, -1)).astype(jnp.float32)
    k = k.reshape(shp + (h, -1)).astype(jnp.float32) / math.sqrt(q.shape[-1])
    v = v.reshape(shp + (h, -1)).astype(jnp.float32)
    gates = (x.astype(jnp.float32) @ p["w_if"].astype(jnp.float32))
    i_raw, f_raw = jnp.split(gates.reshape(shp + (2, h)), 2, axis=-2)
    log_i = -jax.nn.softplus(-i_raw[..., 0, :])   # log sigmoid — stabilized
    log_f = -jax.nn.softplus(-f_raw[..., 0, :])
    return q, k, v, g, log_i, log_f


def mlstm_seq(cfg: ArchConfig, p: Tree, x: jax.Array,
              state: Optional[Tree] = None, chunk: int = 256):
    """Chunkwise-parallel mLSTM. x: (B,S,D).

    State: C (B,H,dk,dv), n (B,H,dk), carried across chunks via lax.scan.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    q, k, v, g, log_i, log_f = _mlstm_qkvg(cfg, p, x)
    dk, dv = q.shape[-1], v.shape[-1]
    l = min(chunk, s)
    assert s % l == 0, (s, l)
    nc = s // l
    # (B,nc,L,...) views
    rs = lambda a: a.reshape((b, nc, l) + a.shape[2:])
    q_, k_, v_ = rs(q), rs(k), rs(v)
    li_, lf_ = rs(log_i), rs(log_f)

    if state is None:
        c0 = jnp.zeros((b, h, dk, dv), jnp.float32)
        n0 = jnp.zeros((b, h, dk), jnp.float32)
    else:
        c0, n0 = state["c"].astype(jnp.float32), state["n"].astype(jnp.float32)

    def chunk_step(carry, xs):
        c, n = carry
        qc, kc, vc, lic, lfc = xs          # (B,L,H,*) / (B,L,H)
        cum_f = jnp.cumsum(lfc, axis=1)    # (B,L,H) inclusive
        # intra-chunk decay matrix  A[t,s] = exp(cum_f[t]-cum_f[s]+log_i[s])
        decay = cum_f[:, :, None, :] - cum_f[:, None, :, :] + lic[:, None, :, :]
        causal = jnp.tril(jnp.ones((l, l), bool))
        a = jnp.where(causal[None, :, :, None], jnp.exp(decay), 0.0)
        scores = jnp.einsum("blhd,bmhd->blmh", qc, kc) * a
        o_intra = jnp.einsum("blmh,bmhv->blhv", scores, vc)
        n_intra = jnp.einsum("blmh,bmhd->blhd", a, kc)
        # inter-chunk: state contribution decayed to each position
        dec_t = jnp.exp(cum_f)             # (B,L,H)
        o_inter = jnp.einsum("blhd,bhdv->blhv", qc, c) * dec_t[..., None]
        n_inter = jnp.einsum("blhd,bhd->blh", qc, n) * dec_t
        num = o_intra + o_inter
        den = jnp.abs(jnp.einsum("blhd,blhd->blh", qc, n_intra) + n_inter)
        out = num / jnp.maximum(den, 1.0)[..., None]
        # update state to end of chunk
        tail = jnp.exp(cum_f[:, -1:, :] - cum_f + lic)     # (B,L,H)
        c = c * jnp.exp(cum_f[:, -1])[:, :, None, None] + jnp.einsum(
            "blhd,blhv,blh->bhdv", kc, vc, tail)
        n = n * jnp.exp(cum_f[:, -1])[:, :, None] + jnp.einsum(
            "blhd,blh->bhd", kc, tail)
        return (c, n), out

    xs = tuple(a.swapaxes(0, 1) for a in (q_, k_, v_, li_, lf_))
    (c, n), outs = jax.lax.scan(chunk_step, (c0, n0), xs)
    o = outs.swapaxes(0, 1).reshape(b, s, h * dv).astype(x.dtype)
    y = dense(o * g, p["w_out"])
    return y, {"c": c, "n": n}


def mlstm_step(cfg: ArchConfig, p: Tree, x: jax.Array, state: Tree):
    """Single decode step. x:(B,1,D); state {c:(B,H,dk,dv), n:(B,H,dk)}."""
    q, k, v, g, log_i, log_f = _mlstm_qkvg(cfg, p, x)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    i_t = jnp.exp(log_i[:, 0])[..., None, None]
    f_t = jnp.exp(log_f[:, 0])[..., None, None]
    c = state["c"].astype(jnp.float32) * f_t + i_t * jnp.einsum(
        "bhd,bhv->bhdv", k, v)
    n = state["n"].astype(jnp.float32) * f_t[..., 0] + i_t[..., 0] * k
    num = jnp.einsum("bhd,bhdv->bhv", q, c)
    den = jnp.abs(jnp.einsum("bhd,bhd->bh", q, n))
    o = (num / jnp.maximum(den, 1.0)[..., None]).reshape(x.shape[0], 1, -1)
    y = dense(o.astype(x.dtype) * g, p["w_out"])
    return y, {"c": c, "n": n}


# ---------------------------------------------------------------------------
# sLSTM (scalar-memory cell, block-diagonal recurrence) + gated FFN
# ---------------------------------------------------------------------------
def init_slstm(cfg: ArchConfig) -> Tree:
    d = cfg.d_model
    h = cfg.n_heads
    hd = d // h
    f = int(round(cfg.slstm_ff_factor * d / 128) * 128)
    return {
        "w_gates": P((d, 4 * d), ("embed", "heads"), "scaled"),
        "r_gates": P((4, h, hd, hd), (None, None, None, None), "scaled"),
        "b_gates": P((4 * d,), (None,), "zeros", jnp.float32),
        "w_up": P((d, f), ("embed", "ffn"), "scaled"),
        "w_gate": P((d, f), ("embed", "ffn"), "scaled"),
        "w_down": P((f, d), ("ffn", "embed"), "scaled"),
    }


def _slstm_cell(cfg: ArchConfig, p: Tree, zx: jax.Array, st: Tree):
    """One timestep. zx: (B,4D) pre-computed input contribution."""
    h = cfg.n_heads
    b = zx.shape[0]
    d = zx.shape[1] // 4
    hprev = st["h"]                                        # (B,D) f32
    hh = hprev.reshape(b, h, -1)
    rec = jnp.einsum("bhd,ghde->bghe", hh, p["r_gates"].astype(jnp.float32))
    rec = rec.reshape(b, 4 * d)
    pre = zx.astype(jnp.float32) + rec + p["b_gates"]
    zi, ii, fi, oi = jnp.split(pre, 4, axis=-1)
    z = jnp.tanh(zi)
    o = jax.nn.sigmoid(oi)
    log_i = -jax.nn.softplus(-ii)
    log_f = -jax.nn.softplus(-fi)
    m_new = jnp.maximum(log_f + st["m"], log_i)
    i_s = jnp.exp(log_i - m_new)
    f_s = jnp.exp(log_f + st["m"] - m_new)
    c = f_s * st["c"] + i_s * z
    n = jnp.maximum(f_s * st["n"] + i_s, 1e-6)
    h_new = o * (c / n)
    return {"h": h_new, "c": c, "n": n, "m": m_new}


def _slstm_scan_ref(cfg: ArchConfig, p_rec: Tree, zx: jax.Array,
                    state: Tree):
    """Plain autodiff reference (oracle for the custom-VJP fast path)."""
    def step(st, zt):
        st = _slstm_cell(cfg, p_rec, zt, st)
        return st, st["h"]

    state, hs = jax.lax.scan(step, state, zx.swapaxes(0, 1))
    return state, hs.swapaxes(0, 1)


# ---------------------------------------------------------------------------
# sLSTM scan with deferred weight gradient.
#
# Autodiff of a scan whose body CONTAINS a weight matmul accumulates the
# weight gradient per timestep: each backward step materializes a full
# r_gates-sized outer product and read-modify-writes the accumulator
# (~100MB of HBM traffic per step — measured to dominate the xlstm
# train_4k roofline, §Perf).  The classical RNN fix: the backward scan
# only produces the per-step pre-activation cotangents dpre_t (cheap,
# B×4D), stacked; the weight gradient is ONE einsum contracting (T, B)
# at the end:   dR = Σ_t  h_{t-1} ⊗ dpre_t,   db = Σ_t dpre_t.
# ---------------------------------------------------------------------------
def _cell_nopar(cfg: ArchConfig, pre: jax.Array, st: Tree) -> Tree:
    """_slstm_cell with the affine part (zx + R·h + b) precomputed —
    weight-free, so its VJP has no weight cotangents."""
    b = pre.shape[0]
    d = pre.shape[1] // 4
    zi, ii, fi, oi = jnp.split(pre.astype(jnp.float32), 4, axis=-1)
    z = jnp.tanh(zi)
    o = jax.nn.sigmoid(oi)
    log_i = -jax.nn.softplus(-ii)
    log_f = -jax.nn.softplus(-fi)
    m_new = jnp.maximum(log_f + st["m"], log_i)
    i_s = jnp.exp(log_i - m_new)
    f_s = jnp.exp(log_f + st["m"] - m_new)
    c = f_s * st["c"] + i_s * z
    n = jnp.maximum(f_s * st["n"] + i_s, 1e-6)
    h_new = o * (c / n)
    return {"h": h_new, "c": c, "n": n, "m": m_new}


def _rec_term(cfg: ArchConfig, rgF: jax.Array, h: jax.Array):
    """R·h for the block-diagonal recurrent matrices, with the weight
    PRE-TRANSPOSED outside the scan (rgF: (h, hd, 4·hd)) so the per-step
    op is a clean invariant-operand batched matmul — XLA otherwise
    re-materializes a transposed 16MB copy of r_gates every timestep
    (measured; §Perf).  h: (B,D) -> (B,4D) in (g,h,e) layout."""
    b = h.shape[0]
    nh = rgF.shape[0]
    hh = h.reshape(b, nh, -1)
    rec = jnp.einsum("bhd,hdk->bhk", hh, rgF)        # (B,h,4·hd)
    g4 = rec.shape[-1] // (h.shape[-1] // nh)
    rec = rec.reshape(b, nh, g4, -1).transpose(0, 2, 1, 3)
    return rec.reshape(b, -1)


def _rg_fwd_layout(r_gates: jax.Array) -> jax.Array:
    """(g,h,hd,he) -> (h, hd, g·he), hoisted out of the scan."""
    g, h, d, e = r_gates.shape
    return (r_gates.astype(jnp.float32)
            .transpose(1, 2, 0, 3).reshape(h, d, g * e))


def _rg_bwd_layout(r_gates: jax.Array) -> jax.Array:
    """(g,h,hd,he) -> (h, g·he, hd) for the dh_rec contraction."""
    g, h, d, e = r_gates.shape
    return (r_gates.astype(jnp.float32)
            .transpose(1, 0, 3, 2).reshape(h, g * e, d))


def _slstm_scan(cfg: ArchConfig, p_rec: Tree, zx: jax.Array, state: Tree):
    """Public entry: f32-cast wrapper around the custom-VJP core (the
    casts' transposes restore the storage dtypes of the cotangents)."""
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p_rec)
    return _slstm_scan_f32(cfg, p32, zx.astype(jnp.float32), state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _slstm_scan_f32(cfg: ArchConfig, p_rec: Tree, zx: jax.Array,
                    state: Tree):
    (state, hs), _ = _slstm_scan_fwd(cfg, p_rec, zx, state)
    return state, hs


def _slstm_scan_fwd(cfg, p_rec, zx, state):
    rg = p_rec["r_gates"].astype(jnp.float32)
    rgF = _rg_fwd_layout(rg)                               # hoisted
    bg = p_rec["b_gates"].astype(jnp.float32)
    zxt = zx.swapaxes(0, 1).astype(jnp.float32)            # (T,B,4D)

    def step(st, zt):
        pre = zt + _rec_term(cfg, rgF, st["h"]) + bg
        st2 = _cell_nopar(cfg, pre, st)
        return st2, (st2, pre)

    stateN, (sts, pres) = jax.lax.scan(step, state, zxt)
    hs = sts["h"].swapaxes(0, 1)
    # residuals: per-step states shifted by one (st_{t-1} enters step t)
    prev = jax.tree.map(
        lambda s0, ss: jnp.concatenate([s0[None], ss[:-1]], 0),
        state, sts)
    return (stateN, hs), (rg, pres, prev)


def _slstm_scan_bwd(cfg, res, cots):
    rg, pres, prev = res
    rgB = _rg_bwd_layout(rg)                               # hoisted
    d_stateN, d_hs = cots
    t, b = pres.shape[0], pres.shape[1]
    g4, nh = rg.shape[0], rg.shape[1]
    d_hs_t = d_hs.swapaxes(0, 1).astype(jnp.float32)       # (T,B,D)

    def back(carry, xs):
        dst = carry                     # cotangent of st AFTER step t
        pre_t, prev_t, dh_out = xs
        dst = dict(dst)
        dst["h"] = dst["h"] + dh_out    # h_t also feeds the block output
        _, vjp = jax.vjp(lambda p, s: _cell_nopar(cfg, p, s), pre_t, prev_t)
        dpre, dprev = vjp(dst)
        # dpre also reaches h_{t-1} through the recurrent term; the
        # (h, g·e, d) weight layout is invariant (hoisted above)
        dp_h = (dpre.reshape(b, g4, nh, -1).transpose(0, 2, 1, 3)
                .reshape(b, nh, -1))                       # (B,h,g·e)
        dh_rec = jnp.einsum("bhk,hkd->bhd", dp_h, rgB).reshape(b, -1)
        dprev = dict(dprev)
        dprev["h"] = dprev["h"] + dh_rec
        return dprev, dpre

    zero_h = {k: jnp.asarray(v, jnp.float32)
              for k, v in d_stateN.items()}
    d_state0, dpres = jax.lax.scan(
        back, zero_h, (pres, prev, d_hs_t), reverse=True)

    # deferred weight gradients: ONE contraction over (T, B)
    hh_prev = prev["h"].reshape(t, b, rg.shape[1], -1)      # (T,B,h,hd)
    dp = dpres.reshape(t, b, rg.shape[0], rg.shape[1], -1)  # (T,B,g,h,hd)
    d_rg = jnp.einsum("tbhd,tbghe->ghde", hh_prev, dp)
    d_bg = jnp.sum(dpres, axis=(0, 1))
    d_zx = dpres.swapaxes(0, 1)                             # (B,T,4D)
    return {"r_gates": d_rg, "b_gates": d_bg}, d_zx, d_state0


_slstm_scan_f32.defvjp(_slstm_scan_fwd, _slstm_scan_bwd)


def slstm_seq(cfg: ArchConfig, p: Tree, x: jax.Array,
              state: Optional[Tree] = None,
              par: Optional[Parallel] = None):
    b, s, d = x.shape
    zx = dense(x, p["w_gates"])                            # (B,S,4D)
    if state is None:
        z = jnp.zeros((b, d), jnp.float32)
        state = {"h": z, "c": z, "n": z + 1e-6, "m": z}
    p_rec = {"r_gates": p["r_gates"], "b_gates": p["b_gates"]}

    # Run the sequential recurrence under shard_map: under plain GSPMD the
    # backward scan all-reduces the r_gates weight-gradient partial EVERY
    # TIMESTEP (measured: 98k × 16MB collectives dominating the xlstm
    # train roofline — §Perf).  shard_map keeps the accumulation local to
    # each device and psums ONCE at the boundary; batch stays
    # data-parallel, the recurrence itself is replicated across the model
    # axis (its FLOPs are negligible next to the TP'd matmuls around it).
    from repro.models.common import _batch_axes, current_mesh
    mesh = current_mesh()
    use_sm = (mesh is not None and hasattr(mesh, "devices")
              and (par is None or par.shard_batch) and b > 1)
    if use_sm:
        from jax.sharding import PartitionSpec as PS
        baxes = _batch_axes()
        st_spec = jax.tree.map(lambda _: PS(baxes, None), state)
        fn = jax.shard_map(
            functools.partial(_slstm_scan, cfg),
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: PS(), p_rec),
                      PS(baxes, None, None), st_spec),
            out_specs=(st_spec, PS(baxes, None, None)), check_vma=False)
        state, hs = fn(p_rec, zx, state)
    else:
        state, hs = _slstm_scan(cfg, p_rec, zx, state)
    hs = hs.astype(x.dtype)                                # (B,S,D)
    up = jax.nn.gelu(dense(hs, p["w_up"])) * dense(hs, p["w_gate"])
    return dense(up, p["w_down"]), state


def slstm_step(cfg: ArchConfig, p: Tree, x: jax.Array, state: Tree):
    zx = dense(x, p["w_gates"])[:, 0]
    state = _slstm_cell(cfg, p, zx, state)
    hs = state["h"][:, None].astype(x.dtype)
    up = jax.nn.gelu(dense(hs, p["w_up"])) * dense(hs, p["w_gate"])
    return dense(up, p["w_down"]), state


def init_recurrent_state(cfg: ArchConfig, kind: str, batch: int) -> Dict[str, P]:
    """Abstract decode-state declaration for one layer of `kind`."""
    d = cfg.d_model
    if kind == "rglru":
        r = cfg.rnn_width or d
        return {"h": P((batch, r), ("batch", "rnn"), "zeros", jnp.float32),
                "conv": P((batch, cfg.conv_width - 1, r),
                          ("batch", None, "rnn"), "zeros")}
    if kind == "mlstm":
        h = cfg.n_heads
        dk = d // h
        dv = int(cfg.mlstm_proj_factor * d) // h
        return {"c": P((batch, h, dk, dv), ("batch", None, None, None),
                       "zeros", jnp.float32),
                "n": P((batch, h, dk), ("batch", None, None), "zeros",
                       jnp.float32)}
    if kind == "slstm":
        return {k: P((batch, d), ("batch", None), "zeros", jnp.float32)
                for k in ("h", "c", "n", "m")}
    raise ValueError(kind)
