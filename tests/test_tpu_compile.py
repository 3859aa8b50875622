"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e, at Qwen2.5-3B's published widths and the shapes ``chip_smoke.py``
serves (decode M = 4 slots, prefill chunk M = 64, page 16).

No chip is needed: the TPU compiler installed with JAX compiles for a
described ``v5e:2x2`` topology.  Each compile must hold its kernel as a
``tpu_custom_call``, so what Mosaic refuses — block tiling, scoped VMEM —
fails here instead of on the chip, and a kernel that quietly fell back
to XLA fails too.  The topology is described inside the module fixture
(never at import): only the worker that runs this file loads libtpu.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core.qlinear import QuantConfig
from repro.core.saliency import round_salient
from repro.kernels import autotune
from repro.kernels.mixed_matmul import mixed_matmul
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_prefill import paged_prefill
from repro.launch.hlo_analysis import tpu_kernels

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
              ((POOL_PAGES, PAGE, HKV, DH), jnp.bfloat16),
              ((POOL_PAGES, PAGE, HKV, DH), jnp.bfloat16),
              ((DECODE_M, NBLK), jnp.int32), ((DECODE_M,), jnp.int32)]
    found = _compiled_kernels(
        lambda *a: paged_attention(*a, interpret=False), shapes, one_chip)
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
    pool = ((pages, PAGE, hkv, dh), jnp.bfloat16)
    shapes = [((b, hq, dh), jnp.bfloat16), pool, pool,
              ((b, nblk), jnp.int32), ((b,), jnp.int32)]
    found = _compiled_kernels(
        lambda *a: paged_attention(*a, ppcb=choice.ppcb, interpret=False),
        shapes, one_chip)
    assert found == {"paged_attention": 1}


def test_paged_prefill_compiles(one_chip):
    pool = ((CFG.n_layers, POOL_PAGES + 1, PAGE, HKV, DH), jnp.bfloat16)
    shapes = [((CHUNK, HQ, DH), jnp.bfloat16),
              ((CHUNK, HKV, DH), jnp.bfloat16),
              ((CHUNK, HKV, DH), jnp.bfloat16), pool, pool,
              ((NBLK,), jnp.int32), ((NBLK,), jnp.int32),
              ((), jnp.int32), ((), jnp.int32)]
    found = _compiled_kernels(
        lambda *a: paged_prefill(*a, layer=CFG.n_layers - 1,
                                 interpret=False), shapes, one_chip)
    assert found == {"paged_prefill": 1}
