"""Transformer assembly: superblock stage scans for all six block kinds,
with full-sequence (train/prefill), cache-prefill and single-step decode
paths, encoder–decoder support, and frontend stubs (vision/audio).

Depth is always `jax.lax.scan` over stacked per-layer parameters so the
lowered HLO is depth-independent (critical for the 512-device dry-run).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Stage
from repro.models import layers as L
from repro.models import recurrent as R
from repro.models.common import Parallel, hint_act
from repro.models.linear import dense
from repro.models.param import P, is_leaf, tree_map_params

Tree = Any


# ---------------------------------------------------------------------------
# Per-block parameter declarations
# ---------------------------------------------------------------------------
def init_block(cfg: ArchConfig, par: Parallel, kind: str,
               cross: bool = False) -> Tree:
    p: Dict[str, Tree] = {}
    if kind in ("dense", "moe", "local"):
        p["ln1"] = L.init_norm(cfg)
        p["attn"] = L.init_attention(cfg, par)
        p["ln2"] = L.init_norm(cfg)
        p["mlp"] = L.init_moe(cfg) if kind == "moe" else L.init_mlp(cfg)
    elif kind == "rglru":
        p["ln1"] = L.init_norm(cfg)
        p["rec"] = R.init_rglru(cfg)
        p["ln2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(cfg)
    elif kind == "mlstm":
        p["ln1"] = L.init_norm(cfg)
        p["cell"] = R.init_mlstm(cfg)
    elif kind == "slstm":
        p["ln1"] = L.init_norm(cfg)
        p["cell"] = R.init_slstm(cfg)
    else:
        raise ValueError(kind)
    if cross:
        p["ln_x"] = L.init_norm(cfg)
        p["xattn"] = L.init_attention(cfg, par, cross=True)
    return p


def stack_p(tree: Tree, n: int) -> Tree:
    """Prepend a scanned `layers` dim to every P leaf."""
    return tree_map_params(
        lambda p: P((n,) + p.shape, ("layers",) + p.axes, p.init, p.dtype), tree)


# ---------------------------------------------------------------------------
# Decode fast path: N-fused projection layouts (QKV, gate+up)
# ---------------------------------------------------------------------------
def _fusable(d, names) -> bool:
    return d is not None and all(isinstance(d.get(k), jax.Array)
                                 for k in names)


def fuse_block_params(p: Tree) -> Tree:
    """Fuse one block's same-input projections along N for decode.

    ``wq``/``wk``/``wv`` become one ``wqkv`` :class:`QLinearGroup` and an
    MLP's ``wg``/``wu`` become ``wgu`` — each transformer block then
    issues 2 projection matmuls instead of 5.  MoE expert weights fuse
    the same way along their last (N) axis: the stacked ``(E, K, F)``
    gate/up pair becomes one ``(E, K, 2F)`` group served by a single
    ``expert_dense`` batched matmul (and, quantized, one per-expert
    activation gather).  Concatenating fp arrays is mathematically
    exact; already-quantized (QLinear) leaves are left unfused because
    post-hoc fusion cannot reconcile their per-projection permutations —
    quantize with ``quantize_params_data_free(..., fuse=True)`` to get
    fused packed layouts.  Cross-attention keeps the per-projection
    path.
    """
    from repro.core.qlinear import QLinearGroup
    p = dict(p)
    attn = p.get("attn")
    if _fusable(attn, ("wq", "wk", "wv")):
        attn = dict(attn)
        ws = [attn.pop(k) for k in ("wq", "wk", "wv")]
        attn["wqkv"] = QLinearGroup(jnp.concatenate(ws, axis=-1),
                                    tuple(int(w.shape[-1]) for w in ws))
        p["attn"] = attn
    mlp = p.get("mlp")
    if mlp is not None and _fusable(mlp, ("wg", "wu")):
        mlp = dict(mlp)
        ws = [mlp.pop(k) for k in ("wg", "wu")]
        mlp["wgu"] = QLinearGroup(jnp.concatenate(ws, axis=-1),
                                  tuple(int(w.shape[-1]) for w in ws))
        p["mlp"] = mlp
    return p


def unfuse_block_params(p: Tree) -> Tree:
    """Inverse of :func:`fuse_block_params`: rebuild per-projection
    weights as unfused VIEWS over the same (fp or packed) data — the
    oracle the fused path is tested against."""
    p = dict(p)
    attn = p.get("attn")
    if attn is not None and "wqkv" in attn:
        attn = dict(attn)
        g = attn.pop("wqkv")
        attn["wq"], attn["wk"], attn["wv"] = g.members()
        p["attn"] = attn
    mlp = p.get("mlp")
    if mlp is not None and "wgu" in mlp:
        mlp = dict(mlp)
        g = mlp.pop("wgu")
        mlp["wg"], mlp["wu"] = g.members()
        p["mlp"] = mlp
    return p


def fuse_params_for_decode(params: Tree) -> Tree:
    """Apply :func:`fuse_block_params` across every stage's (stacked)
    block trees.  Stacked (L, K, N) leaves concatenate along N exactly
    like 2-D ones, so the fused groups slice cleanly under scan."""
    new = dict(params)
    new["stages"] = [tuple(fuse_block_params(bp) for bp in sp)
                     for sp in params["stages"]]
    return new


def unfuse_params_for_oracle(params: Tree) -> Tree:
    new = dict(params)
    new["stages"] = [tuple(unfuse_block_params(bp) for bp in sp)
                     for sp in params["stages"]]
    return new


def init_stage(cfg: ArchConfig, par: Parallel, stage: Stage,
               cross: bool = False) -> Tuple[Tree, ...]:
    return tuple(stack_p(init_block(cfg, par, k, cross), stage.repeats)
                 for k in stage.pattern)


def _kind_window(cfg: ArchConfig, kind: str, max_seq: int) -> Optional[int]:
    if kind == "local":
        return cfg.local_window
    if kind in ("dense", "moe"):
        return cfg.attn_window
    return None


def _cache_window(cfg: ArchConfig, kind: str, max_seq: int) -> int:
    w = _kind_window(cfg, kind, max_seq)
    return min(w, max_seq) if w is not None else max_seq


# ---------------------------------------------------------------------------
# Block applications — full sequence
# ---------------------------------------------------------------------------
def block_full(cfg: ArchConfig, par: Parallel, kind: str, p: Tree,
               x: jax.Array, positions: jax.Array, *, causal: bool,
               enc_out: Optional[jax.Array] = None,
               enc_pos: Optional[jax.Array] = None,
               aux: Optional[jax.Array] = None):
    """One block over a whole sequence. Returns (x, aux)."""
    if kind in ("dense", "moe", "local"):
        w = _kind_window(cfg, kind, x.shape[1])
        h = L.attention_full(cfg, par, p["attn"], L.apply_norm(cfg, p["ln1"], x),
                             positions, causal=causal, window=w)
        x = x + h
        if "xattn" in p:
            h = L.attention_full(cfg, par, p["xattn"],
                                 L.apply_norm(cfg, p["ln_x"], x), positions,
                                 causal=False, use_rope=False, xkv=enc_out,
                                 kv_positions=enc_pos)
            x = x + h
        z = L.apply_norm(cfg, p["ln2"], x)
        if kind == "moe":
            h = L.apply_moe(cfg, p["mlp"], z, par)
            if aux is not None:
                aux = aux + L.moe_aux_loss(cfg, z, p["mlp"]["router"])
        else:
            h = L.apply_mlp(cfg, p["mlp"], z)
        x = x + h
    elif kind == "rglru":
        h, _, _ = R.rglru_seq(cfg, p["rec"], L.apply_norm(cfg, p["ln1"], x))
        x = x + h
        x = x + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], x))
    elif kind == "mlstm":
        h, _ = R.mlstm_seq(cfg, p["cell"], L.apply_norm(cfg, p["ln1"], x))
        x = x + h
    elif kind == "slstm":
        h, _ = R.slstm_seq(cfg, p["cell"], L.apply_norm(cfg, p["ln1"], x),
                           par=par)
        x = x + h
    else:
        raise ValueError(kind)
    return hint_act(x, par), aux


def stage_full(cfg: ArchConfig, par: Parallel, stage: Stage, sparams: Tree,
               x: jax.Array, positions: jax.Array, *, causal: bool,
               enc_out=None, enc_pos=None, remat: bool = False):
    """Scan a stage over its superblocks (training / eval forward)."""

    def body(carry, lp):
        x, aux = carry
        for i, kind in enumerate(stage.pattern):
            x, aux = block_full(cfg, par, kind, lp[i], x, positions,
                                causal=causal, enc_out=enc_out,
                                enc_pos=enc_pos, aux=aux)
        return (x, aux), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), sparams)
    return x, aux


# ---------------------------------------------------------------------------
# Prefill: full sequence + build decode caches
# ---------------------------------------------------------------------------
def block_prefill(cfg: ArchConfig, par: Parallel, kind: str, p: Tree,
                  x: jax.Array, positions: jax.Array, max_seq: int,
                  enc_out=None, enc_pos=None):
    """Returns (x, cache) for one block."""
    if kind in ("dense", "moe", "local"):
        w = _kind_window(cfg, kind, x.shape[1])
        z = L.apply_norm(cfg, p["ln1"], x)
        h, cache = L.attention_full(cfg, par, p["attn"], z, positions,
                                    causal=True, window=w,
                                    cache_window=_cache_window(cfg, kind, max_seq))
        x = x + h
        if "xattn" in p:
            zx = L.apply_norm(cfg, p["ln_x"], x)
            h = L.attention_full(cfg, par, p["xattn"], zx, positions,
                                 causal=False, use_rope=False, xkv=enc_out,
                                 kv_positions=enc_pos)
            x = x + h
            # cross-attn K/V are static over decode: cache them once
            q, k, v = L._project_qkv(cfg, par, p["xattn"], zx, enc_out,
                                     positions, enc_pos, False)
            cache = {"self": cache, "xk": k, "xv": v}
        z = L.apply_norm(cfg, p["ln2"], x)
        h = L.apply_moe(cfg, p["mlp"], z, par) if kind == "moe" else \
            L.apply_mlp(cfg, p["mlp"], z)
        x = x + h
    elif kind == "rglru":
        h, hN, conv = R.rglru_seq(cfg, p["rec"], L.apply_norm(cfg, p["ln1"], x))
        cache = {"h": hN, "conv": conv}
        x = x + h
        x = x + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], x))
    elif kind == "mlstm":
        h, cache = R.mlstm_seq(cfg, p["cell"], L.apply_norm(cfg, p["ln1"], x))
        x = x + h
    elif kind == "slstm":
        h, cache = R.slstm_seq(cfg, p["cell"], L.apply_norm(cfg, p["ln1"], x),
                               par=par)
        x = x + h
    else:
        raise ValueError(kind)
    return hint_act(x, par), cache


def stage_prefill(cfg: ArchConfig, par: Parallel, stage: Stage, sparams: Tree,
                  x: jax.Array, positions: jax.Array, max_seq: int,
                  enc_out=None, enc_pos=None):
    def body(x, lp):
        caches = []
        for i, kind in enumerate(stage.pattern):
            x, c = block_prefill(cfg, par, kind, lp[i], x, positions, max_seq,
                                 enc_out, enc_pos)
            caches.append(c)
        return x, tuple(caches)

    x, caches = jax.lax.scan(body, x, sparams)
    return x, caches          # caches: tuple per position, stacked (repeats,)


# ---------------------------------------------------------------------------
# Decode: single step, carry per-layer state
# ---------------------------------------------------------------------------
def block_step(cfg: ArchConfig, par: Parallel, kind: str, p: Tree,
               x: jax.Array, pos: jax.Array, cache: Tree, max_seq: int,
               layer=None):
    if kind in ("dense", "moe", "local"):
        w = _kind_window(cfg, kind, max_seq)
        self_cache = cache["self"] if "xattn" in p else cache
        h, new_self = L.attention_decode(
            cfg, par, p["attn"], L.apply_norm(cfg, p["ln1"], x), pos,
            self_cache, window=w, layer=layer)
        x = x + h
        if "xattn" in p:
            zx = L.apply_norm(cfg, p["ln_x"], x)
            hq = cfg.n_heads
            dh = cfg.head_dim_
            q = dense(zx, p["xattn"]["wq"]).reshape(x.shape[0], 1, hq, dh)
            xk = cache["xk"] if layer is None else cache["xk"][layer]
            xv = cache["xv"] if layer is None else cache["xv"][layer]
            mask = jnp.ones((x.shape[0], 1, xk.shape[1]), bool)
            o = L._attend(q, xk, xv, mask, cfg.logit_softcap)
            x = x + dense(o.astype(x.dtype).reshape(x.shape[0], 1, -1),
                          p["xattn"]["wo"])
            new_cache = {"self": new_self, "xk": cache["xk"],
                         "xv": cache["xv"]}
        else:
            new_cache = new_self
        z = L.apply_norm(cfg, p["ln2"], x)
        h = L.apply_moe(cfg, p["mlp"], z, par) if kind == "moe" else \
            L.apply_mlp(cfg, p["mlp"], z)
        x = x + h
    elif kind == "rglru":
        c = cache if layer is None else jax.tree.map(lambda a: a[layer], cache)
        h, hN, conv = R.rglru_step(cfg, p["rec"], L.apply_norm(cfg, p["ln1"], x),
                                   c["h"], c["conv"])
        new_cache = {"h": hN, "conv": conv}
        if layer is not None:
            new_cache = jax.tree.map(lambda full, new: full.at[layer].set(new),
                                     cache, new_cache)
        x = x + h
        x = x + L.apply_mlp(cfg, p["mlp"], L.apply_norm(cfg, p["ln2"], x))
    elif kind == "mlstm":
        c = cache if layer is None else jax.tree.map(lambda a: a[layer], cache)
        h, new_cache = R.mlstm_step(cfg, p["cell"], L.apply_norm(cfg, p["ln1"], x),
                                    c)
        if layer is not None:
            new_cache = jax.tree.map(lambda full, new: full.at[layer].set(new),
                                     cache, new_cache)
        x = x + h
    elif kind == "slstm":
        c = cache if layer is None else jax.tree.map(lambda a: a[layer], cache)
        h, new_cache = R.slstm_step(cfg, p["cell"], L.apply_norm(cfg, p["ln1"], x),
                                    c)
        if layer is not None:
            new_cache = jax.tree.map(lambda full, new: full.at[layer].set(new),
                                     cache, new_cache)
        x = x + h
    else:
        raise ValueError(kind)
    return hint_act(x, par), new_cache


def stage_step(cfg: ArchConfig, par: Parallel, stage: Stage, sparams: Tree,
               x: jax.Array, pos: jax.Array, caches: Tree, max_seq: int):
    if par.decode_unroll:
        # Unrolled decode: each layer's cache is addressed directly in the
        # stacked buffer, so the update is an in-place slot write instead
        # of a scan-carry dynamic-slice/update round trip over the whole
        # (B, W, H, dh) window — ~2× less decode HBM traffic (§Perf).
        cur = list(caches)          # per-pattern-position stacked trees
        for layer in range(stage.repeats):
            lp = jax.tree.map(lambda a: a[layer], sparams)
            for i, kind in enumerate(stage.pattern):
                x, cur[i] = block_step(cfg, par, kind, lp[i], x, pos,
                                       cur[i], max_seq, layer=layer)
        return x, tuple(cur)

    def body(x, xs):
        lp, cs = xs
        new = []
        for i, kind in enumerate(stage.pattern):
            x, c = block_step(cfg, par, kind, lp[i], x, pos, cs[i], max_seq)
            new.append(c)
        return x, tuple(new)

    x, new_caches = jax.lax.scan(body, x, (sparams, caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# Paged decode: block-table addressed KV pages (serving runtime)
# ---------------------------------------------------------------------------
ATTN_KINDS = ("dense", "moe", "local")


def block_step_paged(cfg: ArchConfig, par: Parallel, kind: str, p: Tree,
                     x: jax.Array, pos: jax.Array, cache: Tree,
                     block_tables: jax.Array, context_lens, max_seq: int,
                     layer: int, use_kernel: bool = True):
    """Paged variant of :func:`block_step` for attention blocks; recurrent
    blocks carry O(1) per-slot state and keep the dense (unrolled) path."""
    if kind in ATTN_KINDS:
        w = _kind_window(cfg, kind, max_seq)
        h, new_cache = L.attention_decode_paged(
            cfg, par, p["attn"], L.apply_norm(cfg, p["ln1"], x), pos,
            cache, block_tables, lengths=context_lens, window=w,
            layer=layer, use_kernel=use_kernel)
        x = x + h
        z = L.apply_norm(cfg, p["ln2"], x)
        h = L.apply_moe(cfg, p["mlp"], z, par) if kind == "moe" else \
            L.apply_mlp(cfg, p["mlp"], z)
        return hint_act(x + h, par), new_cache
    return block_step(cfg, par, kind, p, x, pos, cache, max_seq, layer=layer)


def stage_step_paged(cfg: ArchConfig, par: Parallel, stage: Stage,
                     sparams: Tree, x: jax.Array, pos: jax.Array,
                     caches: Tree, block_tables: jax.Array,
                     context_lens=None, max_seq: int = 0,
                     use_kernel: bool = True):
    """Always unrolled over layers: each layer's page writes are slot
    scatters addressed into the stacked pool, and the kernel reads its
    layer out of the stacked pool too; a scan would round-trip the whole
    (L, P, ps, H, dh) pool through the carry every layer.  The writes are
    in place only where the caller donates the pool
    (``runtime.engine.paged_steps``); otherwise XLA copies it whole into
    the output buffer first.

    Fully-inactive ticks (every block-table row -1, i.e. no slot owns a
    page) short-circuit via ``lax.cond``: the whole layer walk — QKV
    projections, page scatters, attention, MLPs — is skipped on device
    and x/caches pass through untouched (with the pool donated, the
    conditional aliases it and copies nothing).  Per-row inactivity
    inside a live batch is handled downstream (the kernel zero-fills
    rows with ``context_lens == 0``; the XLA path masks their pages)."""

    def walk(args):
        x, caches = args
        cur = list(caches)
        for layer in range(stage.repeats):
            lp = jax.tree.map(lambda a: a[layer], sparams)
            for i, kind in enumerate(stage.pattern):
                x, cur[i] = block_step_paged(cfg, par, kind, lp[i], x, pos,
                                             cur[i], block_tables,
                                             context_lens, max_seq, layer,
                                             use_kernel)
        return x, tuple(cur)

    return jax.lax.cond(jnp.any(block_tables >= 0), walk,
                        lambda args: args, (x, caches))


def block_prefill_step_paged(cfg: ArchConfig, par: Parallel, kind: str,
                             p: Tree, x: jax.Array, positions: jax.Array,
                             cache: Tree, bt_read: jax.Array,
                             bt_write: jax.Array, start, length,
                             max_seq: int, layer: int,
                             use_kernel: bool = True):
    """One block of one CHUNK of paged prefill (attention kinds only —
    recurrent blocks carry sequential state across chunks, which the
    chunked path does not thread; the engine keeps whole-prompt prefill
    for hybrid stages)."""
    if kind not in ATTN_KINDS:
        raise NotImplementedError(
            f"chunked paged prefill supports attention blocks only, "
            f"got {kind!r} — serve hybrid/recurrent stages with the "
            f"whole-prompt prefill path")
    w = _kind_window(cfg, kind, max_seq)
    h, new_cache = L.attention_prefill_paged(
        cfg, par, p["attn"], L.apply_norm(cfg, p["ln1"], x), positions,
        cache, bt_read, bt_write, start, length, layer=layer, window=w,
        use_kernel=use_kernel)
    x = x + h
    z = L.apply_norm(cfg, p["ln2"], x)
    h = L.apply_moe(cfg, p["mlp"], z, par) if kind == "moe" else \
        L.apply_mlp(cfg, p["mlp"], z)
    return hint_act(x + h, par), new_cache


def stage_prefill_step_paged(cfg: ArchConfig, par: Parallel, stage: Stage,
                             sparams: Tree, x: jax.Array,
                             positions: jax.Array, caches: Tree,
                             bt_read: jax.Array, bt_write: jax.Array,
                             start, length, max_seq: int = 0,
                             use_kernel: bool = True):
    """Chunk-prefill walk over a stage: unrolled over layers exactly
    like :func:`stage_step_paged`, so each layer's fused scatter+attend
    writes into the stacked pool instead of round-tripping it through a
    scan carry — in place where the caller donates the pool, as
    :func:`stage_step_paged` says."""
    cur = list(caches)
    for layer in range(stage.repeats):
        lp = jax.tree.map(lambda a: a[layer], sparams)
        for i, kind in enumerate(stage.pattern):
            x, cur[i] = block_prefill_step_paged(
                cfg, par, kind, lp[i], x, positions, cur[i], bt_read,
                bt_write, start, length, max_seq, layer, use_kernel)
    return x, tuple(cur)


def stage_splice_paged(cfg: ArchConfig, stage: Stage, pool_stage: Tree,
                       cache1_stage: Tree, slot, bt_row: jax.Array) -> Tree:
    """Splice one request's prefill caches into the paged pools.

    Attention caches scatter by absolute token position into the pages of
    ``bt_row``; recurrent states splice into decode-batch slot ``slot``
    exactly as the contiguous path does."""
    out = []
    for i, kind in enumerate(stage.pattern):
        pool_i, c1 = pool_stage[i], cache1_stage[i]
        if kind in ATTN_KINDS:
            out.append(L.scatter_pages(pool_i, c1["k"][:, 0], c1["v"][:, 0],
                                       c1["p"][0, 0], bt_row))
        else:
            out.append(jax.tree.map(
                lambda full, new: full.at[:, slot].set(new[:, 0]),
                pool_i, c1))
    return tuple(out)


def stage_copy_pages(cfg: ArchConfig, stage: Stage, pool_stage: Tree,
                     src, dst) -> Tree:
    """COW page copies for one stage: attention pools copy ``src`` page
    rows onto ``dst`` across all layers at once; recurrent per-slot
    state passes through untouched (it owns no pages)."""
    out = []
    for i, kind in enumerate(stage.pattern):
        pool_i = pool_stage[i]
        if kind in ATTN_KINDS:
            out.append({"k": pool_i["k"].at[:, dst].set(pool_i["k"][:, src]),
                        "v": pool_i["v"].at[:, dst].set(pool_i["v"][:, src])})
        else:
            out.append(pool_i)
    return tuple(out)


def init_stage_cache_paged(cfg: ArchConfig, par: Parallel, stage: Stage,
                           n_slots: int, num_pages: int,
                           page_size: int, dtype=None) -> Tree:
    """Paged mirror of :func:`init_stage_cache`: attention blocks share
    the (num_pages, page_size) pool; recurrent blocks keep per-slot
    state at the decode batch size."""
    per_pos = []
    for kind in stage.pattern:
        if kind in ATTN_KINDS:
            c = L.make_paged_cache(cfg, par, num_pages, page_size,
                                   stage.repeats,
                                   **({} if dtype is None
                                      else {"dtype": dtype}))
        else:
            c = stack_p(R.init_recurrent_state(cfg, kind, n_slots),
                        stage.repeats)
        per_pos.append(c)
    return tuple(per_pos)


# ---------------------------------------------------------------------------
# Decode-cache declarations (abstract P trees, mirror stage_prefill output)
# ---------------------------------------------------------------------------
def init_stage_cache(cfg: ArchConfig, par: Parallel, stage: Stage,
                     batch: int, max_seq: int, enc_len: int = 0) -> Tree:
    per_pos = []
    for kind in stage.pattern:
        if kind in ("dense", "moe", "local"):
            w = _cache_window(cfg, kind, max_seq)
            hkv = par.kv_heads_run(cfg.n_kv_heads, cfg.n_heads)
            # KV heads shard over "model" when they fill/divide it evenly;
            # otherwise shard the context window instead (pjit boundary
            # shardings must divide exactly — phi4 24H / llava 56H / rg 10H)
            tp = max(par.tp, 1)
            if hkv % tp == 0:
                kv_axes = ("batch", None, "kv_heads", None)
            elif w % tp == 0:
                kv_axes = ("batch", "ctx", "kv_heads", None)
            else:
                kv_axes = ("batch", None, None, None)   # replicate (tiny)
            c = {
                "k": P((batch, w, hkv, cfg.head_dim_), kv_axes, "zeros"),
                "v": P((batch, w, hkv, cfg.head_dim_), kv_axes, "zeros"),
                "p": P((batch, w), ("batch", None), "neg_ones", jnp.int32),
            }
            if cfg.enc_dec and enc_len:
                xa = (("batch", None, "kv_heads", None)
                      if hkv % tp == 0 else
                      (("batch", "ctx", "kv_heads", None)
                       if enc_len % tp == 0 else
                       ("batch", None, None, None)))
                c = {"self": c,
                     "xk": P((batch, enc_len, hkv, cfg.head_dim_), xa,
                             "zeros"),
                     "xv": P((batch, enc_len, hkv, cfg.head_dim_), xa,
                             "zeros")}
        else:
            c = R.init_recurrent_state(cfg, kind, batch)
        per_pos.append(stack_p(c, stage.repeats))
    return tuple(per_pos)
