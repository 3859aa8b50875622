"""The program's packed weights for a configuration, made on the device
from ``--seed`` in one jitted call.

Each layer is drawn in bf16 (``draw.py``), arranged as the program's
decoder block, and quantized by the program's own data-free PTQ1.61
(``quantize_params_data_free`` with fused QKV and gate+up) inside a
``lax.scan`` over the layers, so the f32 working set is one layer's and
the whole bf16 model never exists.  Only the program's public functions
are called; the packed layout is never read here.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from harness import draw
from harness.spec import Dims, dims


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
    """(ArchConfig, packed params) on the default device."""
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
        lambda k: _program_block(draw.draw_layer(k, dm, init, recipe), dm),
        jax.random.PRNGKey(0)))

    def build(seed_k):
        def layer(_, i):
            raw = draw.draw_layer(draw.layer_key(seed_k, i), dm, init,
                                  recipe)
            tree = {"stages": [(_program_block(raw, dm),)]}
            q = quantize_params_data_free(tree, qcfg, fuse=True)
            return None, q["stages"][0][0]

        _, stacked = jax.lax.scan(layer, None,
                                  jnp.arange(dm.layers, dtype=jnp.int32))
        embed = draw.draw_embed(seed_k, dm, init)
        embed = jnp.pad(embed, ((0, dm.vocab_padded - dm.vocab), (0, 0)))
        return {"embed": embed, "stages": [(stacked,)],
                "final_norm": {"scale": draw.draw_final_norm(seed_k, dm,
                                                             init)}}

    params = jax.jit(build)(draw.seed_key(seed))
    return cfg, params
