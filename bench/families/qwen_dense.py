"""The dense Qwen-style decoder block (Qwen2 / Qwen3): what the harness
knows of one model family.

A configuration file names its family under ``"family"``; the harness
loads ``bench/families/<family>.py`` by that name
(``cells.Benchmark.family``) and takes from it:

* ``dims(spec)``: the sizes, with ``vocab`` and ``kv_bytes_per_token()``
  (the driver sizes the page pool and draws prompt tokens from them);
* ``build_params(spec, seed)``: ``(ArchConfig, packed params)`` on the
  default device, made from the seed in one jitted call;
* ``KERNELS``: the Pallas kernels each compiled step must hold;
* the work of the window's traced steps, for the readers of
  ``bench/metrics``: ``decode_matmul_work``, ``decode_attention_work``,
  ``decode_step_flops`` and ``prefill_attention_work``.  Each takes the
  reader's ``Context``, so a family may count from what the window saw
  (the engine's counters), not only from the rows.

The plain reference of the family (``bench/references/qwen_dense.py``)
draws its weights through this module's ``draw_layer``, ``draw_embed``
and ``draw_final_norm``, so program and reference start from the same
bf16 numbers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from harness import draw
from harness.spec import salient_channels
from harness.work import ACT, mixed_matmul

# the kernels each compiled step must hold as a tpu_custom_call
KERNELS = {"decode": ("mixed_matmul", "paged_attention"),
           "prefill_chunk": ("mixed_matmul", "paged_prefill")}


# ---------------------------------------------------------------- sizes
@dataclass(frozen=True)
class Dims:
    d: int            # hidden size
    hq: int           # query heads
    hkv: int          # KV heads
    dh: int           # head size
    ff: int           # MLP width
    layers: int
    vocab: int
    rope_theta: float
    eps: float
    qkv_bias: bool
    qk_norm: bool
    tied: bool

    @property
    def vocab_padded(self) -> int:
        """Embedding rows the program keeps: the vocabulary rounded up to
        256 (the pad rows are zero and their logits masked)."""
        return -(-self.vocab // 256) * 256

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        """K and V of one token over all layers."""
        return 2 * self.hkv * self.dh * itemsize * self.layers

    def projections(self) -> Dict[str, Tuple[int, int]]:
        """(K, N) of each packed projection of one layer, QKV and
        gate+up fused as the deployment quantizes them."""
        return {
            "qkv": (self.d, (self.hq + 2 * self.hkv) * self.dh),
            "o": (self.hq * self.dh, self.d),
            "gate_up": (self.d, 2 * self.ff),
            "down": (self.ff, self.d),
        }


def dims(spec: Dict[str, Any]) -> Dims:
    pub, arch = spec["published"], spec["architecture"]
    hq = int(pub["num_attention_heads"])
    return Dims(
        d=int(pub["hidden_size"]), hq=hq,
        hkv=int(pub["num_key_value_heads"]),
        dh=int(arch.get("head_dim") or pub["hidden_size"] // hq),
        ff=int(pub["intermediate_size"]),
        layers=int(pub["num_hidden_layers"]),
        vocab=int(pub["vocab_size"]),
        rope_theta=float(pub["rope_theta"]),
        eps=float(pub["rms_norm_eps"]),
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        tied=bool(pub["tie_word_embeddings"]))


# ----------------------------------------------------------------- draw
# which input each weight reads: weights of one input share their
# salient channels (QKV and gate+up are quantized fused)
INPUT_OF = {"q_proj": "attn_in", "k_proj": "attn_in", "v_proj": "attn_in",
            "o_proj": "o_in", "gate_proj": "mlp_in", "up_proj": "mlp_in",
            "down_proj": "down_in"}


def layer_leaves(dm: Dims) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Name -> (shape, kind) of one decoder layer, weights (in, out)."""
    qd, kvd = dm.hq * dm.dh, dm.hkv * dm.dh
    out = {
        "input_norm": ((dm.d,), "norm"),
        "q_proj": ((dm.d, qd), "matrix"),
        "k_proj": ((dm.d, kvd), "matrix"),
        "v_proj": ((dm.d, kvd), "matrix"),
        "o_proj": ((qd, dm.d), "matrix"),
        "post_attention_norm": ((dm.d,), "norm"),
        "gate_proj": ((dm.d, dm.ff), "matrix"),
        "up_proj": ((dm.d, dm.ff), "matrix"),
        "down_proj": ((dm.ff, dm.d), "matrix"),
    }
    if dm.qkv_bias:
        out.update({"q_bias": ((qd,), "bias"), "k_bias": ((kvd,), "bias"),
                    "v_bias": ((kvd,), "bias")})
    if dm.qk_norm:
        out.update({"q_norm": ((dm.dh,), "norm"),
                    "k_norm": ((dm.dh,), "norm")})
    return out


def draw_layer(key, dm: Dims, init: Dict[str, float],
               recipe: Tuple[float, int]) -> Dict[str, jax.Array]:
    """One layer's bf16 leaves under ``key`` (from ``draw.layer_key``);
    ``recipe`` is (salient ratio, multiple), which sizes the outlier
    channels."""
    ratio, multiple = recipe
    rows: Dict[str, jax.Array] = {}
    out = {}
    for name, (shape, kind) in layer_leaves(dm).items():
        r = None
        if kind == "matrix":
            group = INPUT_OF[name]
            if group not in rows:
                rows[group] = draw._row_scales(
                    draw._leaf_key(key, group), shape[0],
                    salient_channels(shape[0], ratio, multiple),
                    float(init["salient_scale"]))
            r = rows[group]
        out[name] = draw._draw(draw._leaf_key(key, name), shape, kind, init,
                               r)
    return out


def draw_embed(seed_k, dm: Dims, init: Dict[str, float]) -> jax.Array:
    """(vocab, d) bf16 token embedding (also the tied head)."""
    return draw._draw(jax.random.fold_in(seed_k, 0), (dm.vocab, dm.d),
                      "matrix", init)


def draw_final_norm(seed_k, dm: Dims, init: Dict[str, float]) -> jax.Array:
    return draw._draw(jax.random.fold_in(seed_k, 1), (dm.d,), "norm", init)


# -------------------------------------------------- the program's weights
def arch_config(spec: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file.  Refuses
    what the program cannot state (another norm epsilon, untied head)."""
    from repro.configs.base import ArchConfig, Stage

    dm = dims(spec)
    pub = spec["published"]
    if dm.eps != 1e-6:
        raise ValueError(f"rms_norm_eps {dm.eps}: the program's RMSNorm "
                         f"uses 1e-6")
    if pub["hidden_act"] != "silu" or not dm.tied:
        raise ValueError("only silu MLPs with tied embeddings are built")
    return ArchConfig(
        name=spec["name"], family="dense", d_model=dm.d, n_heads=dm.hq,
        n_kv_heads=dm.hkv, head_dim=dm.dh, d_ff=dm.ff, vocab=dm.vocab,
        stages=(Stage(("dense",), dm.layers),), act="silu",
        qkv_bias=dm.qkv_bias, qk_norm=dm.qk_norm, tied_embeddings=True,
        rope_theta=dm.rope_theta, source=spec["source"])


def _program_block(raw: Dict[str, jax.Array], dm: Dims) -> Dict[str, Any]:
    attn = {"wq": raw["q_proj"], "wk": raw["k_proj"], "wv": raw["v_proj"],
            "wo": raw["o_proj"]}
    if dm.qkv_bias:
        attn.update(bq=raw["q_bias"], bk=raw["k_bias"], bv=raw["v_bias"])
    if dm.qk_norm:
        attn.update(q_norm=raw["q_norm"], k_norm=raw["k_norm"])
    return {"ln1": {"scale": raw["input_norm"]}, "attn": attn,
            "ln2": {"scale": raw["post_attention_norm"]},
            "mlp": {"wg": raw["gate_proj"], "wu": raw["up_proj"],
                    "wd": raw["down_proj"]}}


def _check_structure(cfg, block) -> None:
    """The block drawn here has the program's own decoder-block leaves."""
    from repro.models import transformer as T
    from repro.models.common import Parallel
    from repro.models.param import is_leaf

    want = {jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_leaves_with_path(
                T.init_block(cfg, Parallel(), "dense"), is_leaf=is_leaf)}
    got = {jax.tree_util.keystr(p) for p, _ in
           jax.tree_util.tree_leaves_with_path(block)}
    if want != got:
        raise ValueError(f"drawn block leaves {sorted(got)} differ from the "
                         f"program's {sorted(want)}")


def build_params(spec: Dict[str, Any], seed: int) -> Tuple[Any, Any]:
    """(ArchConfig, packed params) on the default device.

    Each layer is drawn in bf16, arranged as the program's decoder block,
    and quantized by the program's own data-free PTQ1.61
    (``quantize_params_data_free`` with fused QKV and gate+up) inside a
    ``lax.scan`` over the layers, so the f32 working set is one layer's
    and the whole bf16 model never exists.  Only the program's public
    functions are called; the packed layout is never read here."""
    from repro.core.pipeline import quantize_params_data_free
    from repro.core.qlinear import QuantConfig

    cfg = arch_config(spec)
    dm = dims(spec)
    init = spec["init"]
    dep = spec["deployment"]
    if not dep["fused_projections"]:
        raise ValueError("the benchmark serves fused projections only")
    qcfg = QuantConfig(ratio=dep["quant_ratio"],
                       multiple=dep["salient_multiple"], use_kernel=True)
    recipe = (float(dep["quant_ratio"]), int(dep["salient_multiple"]))
    _check_structure(cfg, jax.eval_shape(
        lambda k: _program_block(draw_layer(k, dm, init, recipe), dm),
        jax.random.PRNGKey(0)))

    def build(seed_k):
        def layer(_, i):
            raw = draw_layer(draw.layer_key(seed_k, i), dm, init, recipe)
            tree = {"stages": [(_program_block(raw, dm),)]}
            q = quantize_params_data_free(tree, qcfg, fuse=True)
            return None, q["stages"][0][0]

        _, stacked = jax.lax.scan(layer, None,
                                  jnp.arange(dm.layers, dtype=jnp.int32))
        embed = draw_embed(seed_k, dm, init)
        embed = jnp.pad(embed, ((0, dm.vocab_padded - dm.vocab), (0, 0)))
        return {"embed": embed, "stages": [(stacked,)],
                "final_norm": {"scale": draw_final_norm(seed_k, dm, init)}}

    params = jax.jit(build)(draw.seed_key(seed))
    return cfg, params


# ----------------------------------------------------------------- work
# Operations and bytes each kernel call needs, from the configuration's
# shapes and the quantization recipe (``harness/work.py`` says how they
# are counted).
def decode_matmuls(dm: Dims, rows: int, ratio: float,
                   multiple: int) -> Tuple[float, float]:
    """All mixed_matmul calls of one decode step (every layer)."""
    f = b = 0.0
    for k, n in dm.projections().values():
        ff, bb = mixed_matmul(rows, k, n, ratio, multiple)
        f, b = f + ff, b + bb
    return f * dm.layers, b * dm.layers


def paged_attention(dm: Dims, contexts: Iterable[int]) -> Tuple[float, float]:
    """All paged_attention calls of one decode step: one query per row
    against its live context, every layer."""
    kv_tok = 2 * dm.hkv * dm.dh * ACT
    f = b = 0.0
    for ctx in contexts:
        f += 4.0 * dm.hq * dm.dh * ctx
        b += ctx * kv_tok + 2 * ACT * dm.hq * dm.dh
    return f * dm.layers, b * dm.layers


def paged_prefill(dm: Dims, start: int, length: int) -> Tuple[float, float]:
    """All paged_prefill calls of one chunk (every layer): ``length``
    causal queries at positions ``start..``, reading ``start`` tokens of
    context and writing the chunk's K/V."""
    kv_tok = 2 * dm.hkv * dm.dh * ACT
    keys = length * start + length * (length + 1) / 2
    f = 4.0 * dm.hq * dm.dh * keys
    b = (start + 2 * length) * kv_tok + 2 * ACT * length * dm.hq * dm.dh
    return f * dm.layers, b * dm.layers


def decode_token_flops(dm: Dims, context: int) -> float:
    """Model FLOPs of one decoded token: every projection and the head
    at 2 per weight, plus attention over its context."""
    lin = sum(k * n for k, n in dm.projections().values()) * dm.layers
    head = dm.d * dm.vocab
    return 2.0 * (lin + head) + 4.0 * dm.hq * dm.dh * context * dm.layers


# ---------------------------------------- the window's work, for readers
def decode_matmul_work(ctx, steps: int,
                       rows: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of the mixed_matmul calls of ``steps`` decode steps
    that decoded ``rows`` (one context length per row): every step reads
    every layer's packed weights once, every row its activations."""
    dep = ctx.spec["deployment"]
    ratio, mult = float(dep["quant_ratio"]), int(dep["salient_multiple"])
    f1, b1 = decode_matmuls(ctx.dims, 1, ratio, mult)
    f0, b0 = decode_matmuls(ctx.dims, 0, ratio, mult)
    return f1 * len(rows), b0 * steps + (b1 - b0) * len(rows)


def decode_attention_work(ctx, rows: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of the paged_attention calls over ``rows``."""
    return paged_attention(ctx.dims, rows)


def decode_step_flops(ctx, rows: Sequence[int]) -> float:
    """Model FLOPs of the decode steps that decoded ``rows``."""
    return sum(decode_token_flops(ctx.dims, c) for c in rows)


def prefill_attention_work(ctx, chunks: List[Tuple[int, int]]
                           ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the paged_prefill calls of ``chunks``, each
    (start, length)."""
    flops = nbytes = 0.0
    for start, length in chunks:
        f, b = paged_prefill(ctx.dims, start, length)
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes
