"""Plain float32 reference of a dense Qwen-style decoder (Qwen2 / Qwen3)
served with PTQ1.61 weights.

It imports nothing of the program.  It draws the same bf16 weights from
the seed through its family's draw (``bench/families/qwen_dense.py``,
which the program's weights are built with too), quantizes them itself
by the recipe the configuration states — per weight (QKV and gate+up
concatenated along the outputs): rank input channels by mean |w|, keep
the top ``quant_ratio`` (rounded to ``salient_multiple``) as per-channel
asymmetric int4 (min/max, 15 levels), binarize the rest as sign(w)
times the mean |w| of that output column over the binary rows — and
runs the whole forward in float32 at ``Precision.HIGHEST``: RMSNorm,
q/k/v with optional bias, optional per-head q/k RMSNorm, rotate-half
RoPE, causal GQA softmax attention, SiLU-gated MLP, tied head over the
real vocabulary.

``lowp`` computes the same forward in a lower precision by rounding at
every point where a served model stores or feeds a value: each
activation entering a matrix product, K, V, the attention weights, the
residual stream after each add, and the dequantized weights.
``"f8"`` rounds to float8 e4m3, the precision one step below the
configuration's bfloat16: the check's control.  ``"f8act"`` rounds only
the activations entering products, K, V and the attention weights (a
float8 activation path over float32 residual and weights).  ``"bf16"``
rounds everywhere to bfloat16, as the configuration serves, and is a
witness for how far bfloat16 alone moves this model from float32.
"""
from __future__ import annotations

import functools
import math
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import draw
from harness.cells import load_module
from harness.spec import salient_channels

FAMILY = load_module(Path(__file__).resolve().parents[1], "families",
                     "qwen_dense")
Dims, dims = FAMILY.Dims, FAMILY.dims

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block
HEAD_ROWS = 512        # rows per block of the vocabulary projection


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


_LOW = {"f8": jnp.float8_e4m3fn, "f8act": jnp.float8_e4m3fn,
        "bf16": jnp.bfloat16}


def _f8(x, lowp):
    """Round an activation (or K, V, attention weights) to ``lowp``."""
    return x.astype(_LOW[lowp]).astype(jnp.float32) if lowp else x


def _store(x, lowp):
    """Round a stored value (residual stream, dequantized weight), which
    only the full-precision modes keep in float32."""
    return _f8(x, lowp) if lowp in ("f8", "bf16") else x


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def dequantize(w: jax.Array, ratio: float, multiple: int) -> jax.Array:
    """PTQ1.61 data-free quantization of one (K, N) weight, returned
    dequantized in float32 and in the original channel order."""
    w = w.astype(jnp.float32)
    k = w.shape[0]
    k_s = salient_channels(k, ratio, multiple)
    stat = jnp.mean(jnp.abs(w), axis=1)
    order = jnp.argsort(-stat, stable=True)
    salient = jnp.zeros((k,), bool).at[order[:k_s]].set(True)
    wmin, wmax = jnp.min(w, axis=1), jnp.max(w, axis=1)
    s = jnp.maximum((wmax - wmin) / 15.0, 1e-8)
    z = jnp.clip(jnp.round(-wmin / s), 0, 15)
    q = jnp.clip(jnp.round(w / s[:, None]) + z[:, None], 0, 15)
    w4 = (q - z[:, None]) * s[:, None]
    binary = (~salient).astype(jnp.float32)[:, None]
    alpha = jnp.sum(jnp.abs(w) * binary, axis=0) / (k - k_s)
    w1 = jnp.where(w >= 0, 1.0, -1.0) * alpha[None, :]
    return jnp.where(salient[:, None], w4, w1)


def _rope(x, pos, theta):
    """x (S, T, H, dh), pos (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, lowp):
    """Causal GQA attention, q (S, T, hq, dh), k/v (S, T, hkv, dh)."""
    s_, t, hq, dh = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    nb = t // Q_BLOCK
    qb = q.reshape(s_, nb, Q_BLOCK, hkv, rep, dh).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(t)

    def block(args):
        i, qq = args
        sc = jnp.einsum("sqhrd,skhd->shrqk", qq, k, precision=HI)
        sc = sc / math.sqrt(dh)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
        p = _f8(jax.nn.softmax(sc, axis=-1), lowp)
        return jnp.einsum("shrqk,skhd->sqhrd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(nb), qb))
    return o.transpose(1, 0, 2, 3, 4, 5).reshape(s_, t, hq * dh)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 5))
def _layer(key, dm: Dims, init_items: Tuple, recipe: Tuple, x, lowp):
    init = dict(init_items)
    ratio, multiple = recipe
    w = FAMILY.draw_layer(key, dm, init, recipe)
    f32 = lambda a: a.astype(jnp.float32)
    qd, kvd = dm.hq * dm.dh, dm.hkv * dm.dh
    wqkv = dequantize(jnp.concatenate(
        [w["q_proj"], w["k_proj"], w["v_proj"]], axis=1), ratio, multiple)
    wo = dequantize(w["o_proj"], ratio, multiple)
    wgu = dequantize(jnp.concatenate([w["gate_proj"], w["up_proj"]], 1),
                     ratio, multiple)
    wd = dequantize(w["down_proj"], ratio, multiple)
    wqkv, wo, wgu, wd = (_store(a, lowp) for a in (wqkv, wo, wgu, wd))
    s_, t, _ = x.shape
    pos = jnp.arange(t)

    h = _f8(_rms(x, f32(w["input_norm"]), dm.eps), lowp)
    qkv = _mm(h, wqkv)
    q, k, v = qkv[..., :qd], qkv[..., qd:qd + kvd], qkv[..., qd + kvd:]
    if dm.qkv_bias:
        q, k, v = (q + f32(w["q_bias"]), k + f32(w["k_bias"]),
                   v + f32(w["v_bias"]))
    q = q.reshape(s_, t, dm.hq, dm.dh)
    k = k.reshape(s_, t, dm.hkv, dm.dh)
    v = v.reshape(s_, t, dm.hkv, dm.dh)
    if dm.qk_norm:
        q = _rms(q, f32(w["q_norm"]), dm.eps)
        k = _rms(k, f32(w["k_norm"]), dm.eps)
    q, k = _rope(q, pos, dm.rope_theta), _rope(k, pos, dm.rope_theta)
    q, k, v = _f8(q, lowp), _f8(k, lowp), _f8(v, lowp)
    o = _f8(_attention(q, k, v, lowp), lowp)
    x = _store(x + _mm(o, wo), lowp)
    h = _f8(_rms(x, f32(w["post_attention_norm"]), dm.eps), lowp)
    gu = _mm(h, wgu)
    a = _f8(jax.nn.silu(gu[..., :dm.ff]) * gu[..., dm.ff:], lowp)
    return _store(x + _mm(a, wd), lowp)


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def _embed(seed_k, dm: Dims, init_items: Tuple, tokens, lowp):
    e = FAMILY.draw_embed(seed_k, dm, dict(init_items))
    return _store(jnp.take(e, tokens, axis=0).astype(jnp.float32), lowp)


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def _head(seed_k, dm: Dims, init_items: Tuple, rows, lowp):
    """rows (R, d) final hidden states -> (R, vocab) f32 logits."""
    init = dict(init_items)
    e = _store(FAMILY.draw_embed(seed_k, dm, init).astype(jnp.float32),
               lowp)
    fn = FAMILY.draw_final_norm(seed_k, dm, init).astype(jnp.float32)
    h = _f8(_rms(rows, fn, dm.eps), lowp)
    return _mm(h, e.T)


def served_logits(spec: Dict, seed: int,
                  requests: Sequence[Tuple[np.ndarray, Sequence[int]]],
                  lowp=None) -> List[np.ndarray]:
    """Logits (n_i, vocab) at each position where request i was served
    a token: the prompt's last position and each served token but the
    last.  Layer by layer over all requests at once (padded at the end,
    which causal attention never sees)."""
    dm = dims(spec)
    init = tuple(sorted(spec["init"].items()))
    dep = spec["deployment"]
    recipe = (float(dep["quant_ratio"]), int(dep["salient_multiple"]))
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(s[:-1], np.int32)])
            for p, s in requests]
    t = max(len(s) for s in seqs)
    t = -(-t // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    seed_k = draw.seed_key(seed)
    x = _embed(seed_k, dm, init, jnp.asarray(toks), lowp)
    for layer in range(dm.layers):
        x = _layer(draw.layer_key(seed_k, layer), dm, init, recipe, x, lowp)
    out = []
    for i, (p, s) in enumerate(requests):
        lo = len(p) - 1
        rows = x[i, lo:lo + len(s)]
        blocks = []
        for b in range(0, rows.shape[0], HEAD_ROWS):
            chunk = rows[b:b + HEAD_ROWS]
            pad = HEAD_ROWS - chunk.shape[0]
            lg = _head(seed_k, dm, init, jnp.pad(chunk, ((0, pad), (0, 0))),
                       lowp)
            blocks.append(np.asarray(lg[:chunk.shape[0]]))
        out.append(np.concatenate(blocks))
    return out
