"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e, at Qwen2.5-3B's published widths and the shapes ``chip_smoke.py``
serves (decode M = 4 slots, prefill chunk M = 64, page 16), and of the
kernels and whole decode and chunk steps at the benchmark cells' widths
and shapes (Qwen2.5-3B and Qwen3-4B).

No chip is needed: the TPU compiler installed with JAX compiles for a
described ``v5e:2x2`` topology.  Each compile must hold its kernel as a
``tpu_custom_call``, so what Mosaic refuses — block tiling, scoped VMEM —
fails here instead of on the chip, and a kernel that quietly fell back
to XLA fails too.  The topology is described inside the module fixture
(never at import): only the worker that runs this file loads libtpu.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.configs.base import Stage
from repro.core.pipeline import quantize_params_data_free
from repro.core.qlinear import QuantConfig
from repro.core.saliency import round_salient
from repro.kernels import autotune, ops
from repro.kernels.mixed_matmul import mixed_matmul
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_prefill import paged_prefill
from repro.launch.hlo_analysis import tpu_kernels
from repro.models import model as M
from repro.models.common import Parallel
from repro.models.param import abstractify
from repro.runtime.engine import paged_steps

CFG = registry.get("qwen2.5-3b")
DECODE_M = 4                  # chip_smoke's decode slots
CHUNK = 64                    # chip_smoke's prefill chunk
PAGE = 16
NBLK = 512 // PAGE            # block-table width at max_seq 512
POOL_PAGES = DECODE_M * NBLK
HQ, HKV, DH = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim_

# (K, N) of the four packed projections per layer (QKV and gate+up fused)
PROJECTIONS = {
    "qkv": (CFG.d_model, (HQ + 2 * HKV) * DH),
    "wo": (HQ * DH, CFG.d_model),
    "gate_up": (CFG.d_model, 2 * CFG.d_ff),
    "wd": (CFG.d_ff, CFG.d_model),
}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compile
    cache off (a TPU entry written here could not be read back without
    a chip) and libtpu's log files off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compiled_kernels(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return tpu_kernels(jax.jit(fn).lower(*args).compile().as_text())


@pytest.mark.parametrize("m", [DECODE_M, CHUNK], ids=["decode", "chunk"])
@pytest.mark.parametrize("proj", list(PROJECTIONS))
def test_mixed_matmul_compiles(one_chip, proj, m):
    k, n = PROJECTIONS[proj]
    k_s = round_salient(k, QuantConfig.ratio, QuantConfig.multiple)
    k_b = k - k_s
    f32, u8 = jnp.float32, jnp.uint8
    shapes = [((m, k), jnp.bfloat16), ((k_s // 2, n), u8), ((k_s,), f32),
              ((k_s,), f32), ((k_b // 8, n), u8), ((n,), f32), ((k_b,), f32)]
    found = _compiled_kernels(
        lambda *a: mixed_matmul(*a, interpret=False), shapes, one_chip)
    assert found == {"mixed_matmul": 1}


def test_paged_attention_compiles(one_chip):
    shapes = [((DECODE_M, HQ, DH), jnp.bfloat16),
              ((1, POOL_PAGES, PAGE, HKV, DH), jnp.bfloat16),
              ((1, POOL_PAGES, PAGE, HKV, DH), jnp.bfloat16),
              ((DECODE_M, NBLK), jnp.int32), ((DECODE_M,), jnp.int32)]
    found = _compiled_kernels(
        lambda *a: paged_attention(*a, layer=0, interpret=False), shapes,
        one_chip)
    assert found == {"paged_attention": 1}


# the paged decode kernel at serving shapes, with the autotuner's ppcb:
# (config, decode rows, table width, pool pages)
SERVING_DECODE = {
    "qwen2.5-3b.batch_decode": ("qwen2.5-3b", 64, 160, 2432),
    "qwen3-4b": ("qwen3-4b", 8, 288, 2304),
}


@pytest.mark.parametrize("cell", list(SERVING_DECODE))
def test_paged_attention_compiles_serving(one_chip, cell):
    arch, b, nblk, pages = SERVING_DECODE[cell]
    cfg = registry.get(arch)
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    choice = autotune.choose_paged_blocks(hkv, hq // hkv, dh, PAGE, nblk)
    assert choice is not None
    pool = ((1, pages, PAGE, hkv, dh), jnp.bfloat16)
    shapes = [((b, hq, dh), jnp.bfloat16), pool, pool,
              ((b, nblk), jnp.int32), ((b,), jnp.int32)]
    found = _compiled_kernels(
        lambda *a: paged_attention(*a, layer=0, ppcb=choice.ppcb,
                                   interpret=False),
        shapes, one_chip)
    assert found == {"paged_attention": 1}


def _prefill_shapes(cfg, chunk, nblk, pages, n_layers):
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    pool = ((n_layers, pages + 1, PAGE, hkv, dh), jnp.bfloat16)
    return [((chunk, hq, dh), jnp.bfloat16), ((chunk, hkv, dh), jnp.bfloat16),
            ((chunk, hkv, dh), jnp.bfloat16), pool, pool,
            ((nblk,), jnp.int32), ((nblk,), jnp.int32),
            ((), jnp.int32), ((), jnp.int32)]


def test_paged_prefill_compiles(one_chip):
    shapes = _prefill_shapes(CFG, CHUNK, NBLK, POOL_PAGES, CFG.n_layers)
    found = _compiled_kernels(
        lambda *a: paged_prefill(*a, layer=CFG.n_layers - 1,
                                 interpret=False), shapes, one_chip)
    assert found == {"paged_prefill": 1}


# the benchmark cells' serving shapes: (config, slots, max_seq, pool
# pages, prefill chunk), as bench/traffic/*.json and bench/configs/*.json
# state them
SERVING = {
    "qwen2.5-3b.batch_decode": ("qwen2.5-3b", 64, 2560, 2432, 256),
    "qwen3-4b.long_decode": ("qwen3-4b", 8, 4608, 2304, 128),
}


@pytest.mark.parametrize("cell", list(SERVING))
def test_paged_prefill_compiles_serving(one_chip, cell):
    """The chunk kernel at a cell's chunk, pool and table width, with
    the tile the autotuner picks (all kv heads a grid step in both)."""
    arch, _, max_seq, pages, chunk = SERVING[cell]
    cfg = registry.get(arch)
    hkv = cfg.n_kv_heads
    choice = autotune.choose_prefill_blocks(chunk, hkv, cfg.n_heads // hkv,
                                            cfg.head_dim_, PAGE)
    assert choice is not None and choice.bh == hkv
    shapes = _prefill_shapes(cfg, chunk, max_seq // PAGE, pages,
                             cfg.n_layers)
    found = _compiled_kernels(
        lambda *a: paged_prefill(*a, layer=cfg.n_layers - 1, bh=choice.bh,
                                 interpret=False), shapes, one_chip)
    assert found == {"paged_prefill": 1}


# (kv heads a grid step, query heads per kv head, chunk, head dim): both
# cells' tiles, a smaller chunk and a tiny tile at dh 128; RecurrentGemma-
# 2B's one kv head of 256, one head of xLSTM-1.3B's 512, and Granite's 8
# kv heads of 64 (lanes half full): the lane and sublane padding counted
VMEM_SHAPES = [(2, 8, 256, 128), (8, 4, 128, 128), (8, 4, 64, 128),
               (2, 1, 64, 128), (1, 10, 128, 256), (1, 1, 64, 512),
               (8, 2, 128, 64)]


@pytest.mark.parametrize(
    "bh,rep,chunk,dh", VMEM_SHAPES,
    ids=[f"{b}-{r}-{c}" + ("" if d == 128 else f"-dh{d}")
         for b, r, c, d in VMEM_SHAPES])
def test_paged_prefill_vmem_model_bounds_mosaic(one_chip, monkeypatch, bh,
                                                rep, chunk, dh):
    """Mosaic compiles the chunk kernel with its scoped VMEM limited to
    what ``paged_prefill_vmem_bytes`` counts: the count is an upper
    bound of what Mosaic allocates, so the chooser never picks a tile
    that runs out of VMEM."""
    limit = autotune.paged_prefill_vmem_bytes(bh, rep, dh, PAGE, chunk)
    call = pl.pallas_call

    def limited(*a, **kw):
        kw["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=limit)
        return call(*a, **kw)

    monkeypatch.setattr(pl, "pallas_call", limited)
    cfg = dataclasses.replace(CFG, n_heads=bh * rep, n_kv_heads=bh,
                              head_dim=dh)
    shapes = _prefill_shapes(cfg, chunk, NBLK, POOL_PAGES, 2)
    found = _compiled_kernels(
        lambda *a: paged_prefill.__wrapped__(*a, layer=1, bh=bh,
                                             interpret=False),
        shapes, one_chip)
    assert found == {"paged_prefill": 1}


def _abstract_serving(cfg, slots, pages, sharding):
    """Packed parameters (data-free PTQ1.61, QKV and gate+up fused, as
    the benchmark builds them) and the paged KV pool, as shapes."""
    par = Parallel()
    qcfg = QuantConfig(ratio=0.2, multiple=128, use_kernel=True)

    def pack(p):
        q = quantize_params_data_free({"stages": p["stages"]}, qcfg,
                                      fuse=True)
        return {**p, "stages": q["stages"]}

    params = jax.eval_shape(pack, M.abstract_params(cfg, par))
    caches = abstractify(M.init_paged_caches(cfg, par, slots, pages, PAGE))
    put = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)
    return par, put(params), put(caches)


# a v5e core's VMEM: XLA may stage a smaller pool there whole
VMEM_BYTES = 128 * 2**20

# what may produce a pool-shaped value without copying the pool: views,
# plumbing and the conditional that passes the pool through (besides the
# kernels and the scatters, let through by the test below)
IN_PLACE = {"parameter", "get-tuple-element", "tuple", "bitcast",
            "conditional"}
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s"
                   r"([a-z][\w\-]*)\(")
ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _instructions(hlo):
    """(result shapes, opcode, line) of every instruction in ``hlo``."""
    for line in hlo.splitlines():
        m = INSTR.match(line)
        if m:
            shapes = {tuple(int(d) for d in a.split(",") if d)
                      for a in ARRAY.findall(m.group(2))}
            yield shapes, m.group(3), line


def _serving_depth(cfg, pages):
    """The fewest layers, two at least, at which one pool leaf outgrows
    VMEM, as it does at every cell's full depth."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim_
    layer = (pages + 1) * PAGE * hkv * dh * 2               # bf16
    return max(2, VMEM_BYTES // layer + 1)


def _compile_serving_steps(cell, sharding):
    """A cell's decode and chunk steps as the engine jits them
    (``paged_steps``, the pool donated), compiled at its published
    widths, slots, table width, pool and chunk: (layers, pool shape,
    {step: compiled HLO text})."""
    arch, slots, max_seq, pages, chunk = SERVING[cell]
    base = registry.get(arch)
    n = _serving_depth(base, pages)
    cfg = dataclasses.replace(base, stages=(Stage(("dense",), n),))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "INTERPRET", False)
        par, params, caches = _abstract_serving(cfg, slots, pages, sharding)
        nblk = max_seq // PAGE
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                  sharding=sharding)
        decode, chunk_step = paged_steps(cfg, par, max_seq=max_seq,
                                         use_kernel=True)
        lowered = {
            "decode": decode.lower(params, i32(slots), i32(slots), caches,
                                   i32(slots, nblk), i32(slots)),
            "prefill_chunk": chunk_step.lower(
                params, i32(1, chunk), caches, i32(nblk), i32(nblk), i32(),
                i32())}
        hlo = {k: v.compile().as_text() for k, v in lowered.items()}
    return n, caches[0][0]["k"].shape, hlo


@pytest.fixture(scope="module")
def serving_steps(one_chip):
    """Each cell's compiled steps, compiled once for the tests below."""
    compiled = {}

    def get(cell):
        if cell not in compiled:
            compiled[cell] = _compile_serving_steps(cell, one_chip)
        return compiled[cell]
    return get


@pytest.mark.parametrize("cell", list(SERVING))
def test_serving_steps_compile(serving_steps, cell):
    """The whole decode and chunk steps of a cell, at its published widths
    (as many of its layers as outgrow VMEM), slots, table width, pool and
    chunk: each holds its kernels as ``tpu_custom_call``s, one a
    projection or layer."""
    n, _, hlo = serving_steps(cell)
    assert tpu_kernels(hlo["decode"]) == {"mixed_matmul": 4 * n,
                                          "paged_attention": n}
    assert tpu_kernels(hlo["prefill_chunk"]) == {"mixed_matmul": 4 * n,
                                                 "paged_prefill": n}
    # the layer is an operand of the attention kernel, so every layer's
    # call is one lowering of it (a static layer lowers it once a layer)
    bodies = {re.search(r"backend_config=(\{.*?\})(?:,|$)", line).group(1)
              for _, op, line in _instructions(hlo["decode"])
              if line.lstrip().startswith("%paged_attention")}
    assert len(bodies) == 1


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
@pytest.mark.parametrize("cell", list(SERVING))
def test_serving_steps_update_the_pool_in_place(serving_steps, cell, step):
    """The served steps take the pool donated and never copy it: its K
    and V parameters alias outputs, and nothing but views, the
    conditional, scatters and the kernels yields a value of the stacked
    pool's shape, one layer's, or the kernel's per-layer row view — no
    ``copy`` and no ``slice`` of the pool, whole or by layer."""
    _, pool, hlo = serving_steps(cell)
    hlo = hlo[step]
    _, p, ps, hkv, dh = pool
    pooled = {pool, (1, p, ps, hkv, dh), (p, ps, hkv, dh), (p, ps * hkv, dh)}
    params = [re.search(r"parameter\((\d+)\)", line).group(1)
              for shapes, op, line in _instructions(hlo)
              if op == "parameter" and pool in shapes and "caches" in line]
    assert len(params) == 2                               # K and V
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
    aliased = set(re.findall(r"\((\d+), \{\}, may-alias\)",
                             alias.group(1) if alias else ""))
    assert set(params) <= aliased
    copies = [line.strip()[:160] for shapes, op, line in _instructions(hlo)
              if shapes & pooled and op not in IN_PLACE
              and 'custom_call_target="tpu_custom_call"' not in line
              and not (op == "scatter" or (op == "fusion"
                                           and '/scatter"' in line))]
    assert not copies
    assert tpu_kernels(hlo).get(
        "paged_attention" if step == "decode" else "paged_prefill")
