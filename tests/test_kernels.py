"""Pallas kernel sweeps: every kernel × shapes × dtypes against the
pure-jnp oracle in repro.kernels.ref (interpret mode on CPU).

Tolerances: the kernels feed bf16 operands to the MXU (jax.lax.dot with
f32 accumulation) while the oracle contracts in f32, so per-element
relative error scales like 2^-8·sqrt(K); assertions use an explicit
K-scaled atol on top of 2% rtol.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import pack
from repro.kernels import ref
from repro.kernels.binary_matmul import binary_matmul
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.mixed_matmul import mixed_matmul


def _tol(k, scale=1.0):
    return {"rtol": 2e-2, "atol": 0.06 * np.sqrt(k) * scale}


def make_binary(rng, k, n):
    signs = rng.choice([-1.0, 1.0], size=(k, n)).astype(np.float32)
    bits = pack.pack_bits(jnp.asarray(signs), axis=-2)
    a_out = jnp.asarray(rng.uniform(0.5, 2.0, n), jnp.float32)
    a_in = jnp.asarray(rng.uniform(0.5, 2.0, k), jnp.float32)
    return bits, a_out, a_in


def make_int4(rng, k, n):
    q = jnp.asarray(rng.integers(0, 16, size=(k, n)), jnp.uint8)
    w4 = pack.pack_nibbles(q, axis=-2)
    s4 = jnp.asarray(rng.uniform(0.01, 0.1, k), jnp.float32)
    z4 = jnp.asarray(rng.integers(0, 16, k).astype(np.float32))
    return w4, s4, z4


@pytest.mark.parametrize("m,k,n", [
    (8, 128, 128), (128, 256, 256), (64, 512, 384),
    (256, 1024, 512), (32, 2048, 128),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_binary_matmul(rng, m, k, n, dtype):
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    bits, a_out, a_in = make_binary(rng, k, n)
    y_ref = ref.binary_matmul_ref(x, bits, a_out, a_in).astype(np.float32)
    y = binary_matmul(x, bits, a_out, a_in, interpret=True).astype(np.float32)
    np.testing.assert_allclose(y, y_ref, **_tol(k, 2.0))


@pytest.mark.parametrize("m,k,n", [
    (8, 128, 128), (128, 256, 256), (64, 512, 384), (16, 1024, 256),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_int4_matmul(rng, m, k, n, dtype):
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w4, s4, z4 = make_int4(rng, k, n)
    y_ref = ref.int4_matmul_ref(x, w4, s4, z4).astype(np.float32)
    y = int4_matmul(x, w4, s4, z4, interpret=True).astype(np.float32)
    np.testing.assert_allclose(y, y_ref, **_tol(k))


@pytest.mark.parametrize("m,k_s,k_b,n", [
    (8, 128, 384, 128), (64, 128, 512, 256),
    (128, 256, 1024, 256), (32, 512, 512, 384),
])
def test_mixed_matmul(rng, m, k_s, k_b, n):
    k = k_s + k_b
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w4, s4, z4 = make_int4(rng, k_s, n)
    bits, a_out, a_in = make_binary(rng, k_b, n)
    y_ref = ref.mixed_matmul_ref(x, w4, s4, z4, bits, a_out, a_in)
    y = mixed_matmul(x, w4, s4, z4, bits, a_out, a_in, interpret=True)
    np.testing.assert_allclose(y.astype(np.float32),
                               y_ref.astype(np.float32), **_tol(k, 2.0))


def test_mixed_matches_qlinear_forward(rng):
    """ops.mixed_matmul(x, qlinear) == the XLA dequant forward."""
    from repro.core.qlinear import QuantConfig, quantize_linear
    from repro.kernels import ops
    import dataclasses

    k, n = 640, 256
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    stat = jnp.asarray(rng.uniform(0.1, 10.0, k), jnp.float32)
    q = quantize_linear(w, stat, QuantConfig(ratio=0.2, multiple=128,
                                             use_kernel=False))
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.bfloat16)
    y_xla = q.__matmul_x__(x).astype(np.float32)
    y_ker = ops.mixed_matmul(x, q).astype(np.float32)
    np.testing.assert_allclose(y_ker, y_xla, rtol=2e-2,
                               atol=0.06 * np.sqrt(k))


def test_mixed_matmul_gather_in_kernel_bit_identical(rng):
    """The scalar-prefetched perm path (gather inside the kernel, full-K
    x tile) is pure data movement: results must be BIT-identical to
    pre-gathering the activation on the host."""
    m, k_s, k_b, n = 8, 128, 384, 128
    k = k_s + k_b
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w4, s4, z4 = make_int4(rng, k_s, n)
    bits, a_out, a_in = make_binary(rng, k_b, n)
    perm = jnp.asarray(rng.permutation(k), jnp.int32)
    xp = jnp.take(x, perm, axis=-1)
    y_pre = mixed_matmul(xp, w4, s4, z4, bits, a_out, a_in, interpret=True)
    y_ker = mixed_matmul(x, w4, s4, z4, bits, a_out, a_in, perm,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(y_ker, np.float32),
                                  np.asarray(y_pre, np.float32))


def test_ops_mixed_matmul_uses_in_kernel_gather(rng):
    """ops.mixed_matmul routes the decode-shaped QLinear forward through
    the in-kernel gather (no host-side permuted copy of x) and still
    matches the XLA dequant oracle."""
    from repro.core.qlinear import QuantConfig, quantize_linear
    from repro.kernels import autotune, ops

    k, n = 640, 256
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    stat = jnp.asarray(rng.uniform(0.1, 10.0, k), jnp.float32)
    q = quantize_linear(w, stat, QuantConfig(ratio=0.2, multiple=128))
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.bfloat16)
    choice = autotune.choose_blocks(4, q.k_s, q.k_b, q.n)
    assert autotune.gather_in_kernel_ok(choice, 4, k)   # decode M: fits
    y_ker = ops.mixed_matmul(x, q).astype(np.float32)
    y_xla = q.__matmul_x__(x).astype(np.float32)
    np.testing.assert_allclose(y_ker, y_xla, rtol=2e-2,
                               atol=0.06 * np.sqrt(k))
    # huge-K prefill shapes that overflow the full-K tile budget fall
    # back to the host-side gather, never to a wrong answer
    assert not autotune.gather_in_kernel_ok(choice, 4, k,
                                            vmem_budget=1 << 12)


def test_mixed_matmul_mismatched_k_spans(rng):
    """k_s=128, k_b=192: no single bk ≤ 128 divides both spans at the old
    default — the kernel must repair bk to the common divisor (64), not
    assert mid-trace."""
    m, k_s, k_b, n = 8, 128, 192, 128
    k = k_s + k_b
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w4, s4, z4 = make_int4(rng, k_s, n)
    bits, a_out, a_in = make_binary(rng, k_b, n)
    y_ref = ref.mixed_matmul_ref(x, w4, s4, z4, bits, a_out, a_in)
    for blocks in ({}, {"bk": 128}):       # autotuned and explicit-cap
        y = mixed_matmul(x, w4, s4, z4, bits, a_out, a_in,
                         interpret=True, **blocks)
        np.testing.assert_allclose(y.astype(np.float32),
                                   y_ref.astype(np.float32), **_tol(k, 2.0))


# ---------------------------------------------------------------------------
# Block-size autotuner
# ---------------------------------------------------------------------------
def test_autotune_common_bk():
    from repro.kernels import autotune
    assert autotune.common_bk(128, 192) == 64
    assert autotune.common_bk(128, 136) == 8
    assert autotune.common_bk(512, 512) == 512
    assert autotune.common_bk(0, 384) == 384      # empty span: unconstrained
    assert autotune.common_bk(768, 3328, cap=128) == 128
    assert autotune.common_bk(24, 36) is None     # gcd 12: no ×8 divisor
    assert autotune.common_bk(0, 0) is None


@pytest.mark.parametrize("m,k_s,k_b,n", [
    (1, 768, 3328, 12288),     # llama-7b fused QKV at decode batch 1
    (4, 768, 3328, 22016),     # fused gate+up
    (16, 128, 512, 384),
    (256, 768, 3328, 4096),    # prefill-shaped
])
def test_autotune_choice_feasible(m, k_s, k_b, n):
    from repro.kernels import autotune
    c = autotune.choose_blocks(m, k_s, k_b, n)
    assert c is not None
    assert m % c.bm == 0 and n % c.bn == 0
    assert k_s % c.bk == 0 and k_b % c.bk == 0 and c.bk % 8 == 0
    assert c.vmem_bytes <= autotune.VMEM_BUDGET
    # decode shapes must stream the activation once: whole-M row block
    if m <= 16:
        assert c.bm == m


def test_autotune_decode_beats_legacy_blocks():
    """The picked tiling must not model MORE traffic than the legacy
    hard-coded (256, 512, 128) blocks on a decode shape."""
    from repro.kernels import autotune
    m, k_s, k_b, n = 4, 768, 3328, 12288
    c = autotune.choose_blocks(m, k_s, k_b, n)
    legacy = autotune.modeled_hbm_bytes(m, k_s, k_b, n,
                                        bm=min(256, m), bn=min(512, n))
    assert c.hbm_bytes <= legacy
    # one x read per call at decode shapes (bn covers all of N)
    assert c.bn == n


def test_autotune_knobs_are_live():
    """Reassigning the module knobs must take effect immediately, even
    for shapes already in the dispatch cache (knobs are cache keys)."""
    from repro.kernels import autotune
    shape = (4, 768, 3328, 12288)
    full = autotune.choose_blocks(*shape)
    assert full.bn == 12288
    old = autotune.BN_CAP
    try:
        autotune.BN_CAP = 512
        capped = autotune.choose_blocks(*shape)
        assert capped.bn <= 512
    finally:
        autotune.BN_CAP = old
    assert autotune.choose_blocks(*shape).bn == 12288
    # explicit budget overrides the module default
    tight = autotune.choose_blocks(*shape, vmem_budget=1 << 20)
    assert tight is None or tight.vmem_bytes <= 1 << 20


@pytest.mark.parametrize("m", [4, 64], ids=["decode", "chunk"])
@pytest.mark.parametrize("k,n", [(2048, 2560), (2048, 2048), (2048, 22016),
                                 (11008, 2048)])
def test_autotune_tpu_tiling_floors(m, k, n):
    """Qwen2.5-3B's packed projections at 128-channel salient spans:
    the Mosaic-side pick keeps bk on the 128-lane tiling, bm at all of M
    or a multiple of 8, and the counted VMEM inside the budget."""
    from repro.core.saliency import round_salient
    from repro.kernels import autotune
    k_s = round_salient(k, 0.2, 128)
    c = autotune.choose_blocks(m, k_s, k - k_s, n, tpu_tiling=True)
    assert c is not None
    assert c.bk % autotune.LANE == 0
    assert k_s % c.bk == 0 and (k - k_s) % c.bk == 0
    assert c.bm == m or c.bm % autotune.SUBLANE == 0
    assert n % c.bn == 0 and c.bn % autotune.LANE == 0
    assert c.vmem_bytes <= autotune.VMEM_BUDGET


def test_autotune_counts_unpack_temporaries():
    """A whole-N gate+up block (bn=22016, bk=128) needs ~41 MB of scoped
    VMEM under Mosaic — the footprint model must price it out of a
    16 MiB limit, not pass it for its ~4 MB of pipeline buffers."""
    from repro.kernels import autotune
    assert autotune.kernel_vmem_bytes(4, 22016, 128) > 16 * 1024 * 1024
    # spans with no 128-multiple common divisor cannot tile for Mosaic
    assert autotune.choose_blocks(4, 128, 192, 256, tpu_tiling=True) is None
    assert autotune.choose_blocks(4, 128, 192, 256) is not None


def test_autotune_unfeasible_shapes():
    from repro.kernels import autotune
    assert autotune.choose_blocks(4, 128, 512, 200) is None   # N % 128
    assert autotune.choose_blocks(4, 24, 36, 256) is None     # no common bk
    assert autotune.choose_blocks(0, 128, 512, 256) is None


def test_kernel_block_shape_sweep(rng):
    """Block-shape sweep: results must be block-size independent."""
    m, k, n = 128, 512, 256
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    bits, a_out, a_in = make_binary(rng, k, n)
    base = binary_matmul(x, bits, a_out, a_in, bm=128, bn=128, bk=128,
                         interpret=True)
    for bm, bn, bk in [(64, 64, 64), (128, 256, 512), (32, 128, 256)]:
        y = binary_matmul(x, bits, a_out, a_in, bm=bm, bn=bn, bk=bk,
                          interpret=True)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(base, np.float32),
                                   rtol=1e-2, atol=0.5)
