"""Equivalence tests for the §Perf optimized execution paths against
their plain-JAX oracles (the optimizations must not change the math)."""
import dataclasses
import os
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType

from repro.configs import registry
from repro.core import pipeline
from repro.core.qlinear import (QLinearGroup, QuantConfig, quantize_linear,
                                quantize_linear_group)
from repro.kernels import ops
from repro.models import layers as L
from repro.models import model as M
from repro.models import recurrent as R
from repro.models import transformer as T
from repro.models.common import Parallel
from repro.models.param import materialize


# ---------------------------------------------------------------------------
# sLSTM deferred-weight-gradient custom VJP == autodiff reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 3])
def test_slstm_custom_vjp_matches_autodiff(seed):
    cfg = registry.get("xlstm-1.3b").reduced()
    p = materialize(R.init_slstm(cfg), jax.random.PRNGKey(1))
    p_rec = {"r_gates": p["r_gates"].astype(jnp.float32),
             "b_gates": p["b_gates"]}
    rng = np.random.default_rng(seed)
    b, t, d = 2, 7, cfg.d_model
    zx = jnp.asarray(rng.normal(size=(b, t, 4 * d)) * 0.4, jnp.float32)
    z = jnp.zeros((b, d), jnp.float32)
    st = {"h": z, "c": z, "n": z + 1e-6, "m": z}

    def mk(fn):
        def loss(pr, zx):
            stN, hs = fn(cfg, pr, zx, st)
            return (jnp.sum(hs ** 2) + jnp.sum(stN["c"] ** 2) * 0.3
                    + jnp.sum(stN["h"]) * 0.1)
        return loss

    v1 = mk(R._slstm_scan)(p_rec, zx)
    v2 = mk(R._slstm_scan_ref)(p_rec, zx)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    g1 = jax.grad(mk(R._slstm_scan), argnums=(0, 1))(p_rec, zx)
    g2 = jax.grad(mk(R._slstm_scan_ref), argnums=(0, 1))(p_rec, zx)
    for a, b2 in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        a = np.asarray(a, np.float32)
        b2 = np.asarray(b2, np.float32)
        den = np.abs(b2).max() + 1e-9
        assert np.abs(a - b2).max() / den < 1e-4, a.shape


# ---------------------------------------------------------------------------
# shard_map MoE == plain dispatch (fwd and grad), multi-device
# ---------------------------------------------------------------------------
def test_moe_shard_map_matches_fallback():
    if jax.device_count() < 4:
        pytest.skip("needs ≥4 devices (run under the dryrun env)")
    import dataclasses
    cfg = registry.get("mixtral-8x22b").reduced()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    p = materialize(L.init_moe(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)) * 0.3,
                    jnp.float32)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    par = Parallel(tp=2, dp=2, remat=False, attn_chunk=32)

    def loss(p, use_par):
        return jnp.sum(L.apply_moe(cfg, p, x,
                                   par if use_par else None) ** 2)

    v1, g1 = jax.value_and_grad(lambda p: loss(p, False))(p)
    with mesh:
        v4, g4 = jax.jit(jax.value_and_grad(lambda p: loss(p, True)))(p)
    np.testing.assert_allclose(float(v1), float(v4), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# decode_unroll knob (kept despite being slower — must stay correct)
# ---------------------------------------------------------------------------
def test_decode_unroll_matches_scan():
    from repro.models import model as M
    cfg = registry.get("qwen3-4b").reduced()
    par_scan = Parallel(remat=False, attn_chunk=32, decode_unroll=False)
    par_unr = Parallel(remat=False, attn_chunk=32, decode_unroll=True)
    params = M.init_params(cfg, par_scan, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    b, s, max_seq = 2, 12, 32
    toks = jnp.asarray(rng.integers(1, cfg.vocab - 1, (b, s + 1)),
                       jnp.int32)
    batch = {"tokens": toks[:, :s]}
    _, caches = M.prefill(cfg, par_scan, params, batch, max_seq)
    pos = jnp.full((b,), s, jnp.int32)
    l1, c1 = M.decode_step(cfg, par_scan, params, toks[:, s], pos, caches,
                           max_seq)
    l2, c2 = M.decode_step(cfg, par_unr, params, toks[:, s], pos, caches,
                           max_seq)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32),
                               rtol=5e-2, atol=5e-2)
    for a, b2 in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b2, np.float32),
                                   rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# Decode fast path: N-fused QKV / gate+up vs the unfused oracle
# ---------------------------------------------------------------------------
def test_fused_fp_model_matches_unfused():
    """fp fusion is pure concatenation — prefill and decode logits must
    match the per-projection model exactly (same contractions)."""
    cfg = registry.get("tiny-lm").reduced()
    par = Parallel(remat=False, attn_chunk=32)
    params = M.init_params(cfg, par, jax.random.PRNGKey(0))
    fused = T.fuse_params_for_decode(params)
    rng = np.random.default_rng(0)
    b, s, max_seq = 2, 12, 32
    toks = jnp.asarray(rng.integers(1, cfg.vocab - 1, (b, s + 1)), jnp.int32)
    l1, c1 = M.prefill(cfg, par, params, {"tokens": toks[:, :s]}, max_seq)
    l2, c2 = M.prefill(cfg, par, fused, {"tokens": toks[:, :s]}, max_seq)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32),
                               rtol=1e-5, atol=1e-5)
    pos = jnp.full((b,), s, jnp.int32)
    d1, _ = M.decode_step(cfg, par, params, toks[:, s], pos, c1, max_seq)
    d2, _ = M.decode_step(cfg, par, fused, toks[:, s], pos, c2, max_seq)
    np.testing.assert_allclose(np.asarray(d1, np.float32),
                               np.asarray(d2, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_fused_group_qlinear_matches_member_oracle(rng):
    """A fused QLinearGroup (one quantization over concat(ws)) must give
    the SAME outputs as running its sliced per-member QLinears — the
    members are views over identical packed bytes."""
    k = 640
    ws = [jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
          for n in (128, 256, 128)]
    stat = jnp.asarray(rng.uniform(0.1, 10.0, k), jnp.float32)
    g = quantize_linear_group(ws, stat, QuantConfig(ratio=0.2, multiple=128))
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.float32)
    ys = g.forward_split(x)
    for y, member in zip(ys, g.members()):
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(member.__matmul_x__(x),
                                              np.float32),
                                   rtol=1e-5, atol=1e-5)
    # kernel path over the fused layout vs the unfused XLA oracle
    gk = dataclasses.replace(
        g, inner=dataclasses.replace(g.inner, use_kernel=True))
    xb = x.astype(jnp.bfloat16)
    for y, member in zip(gk.forward_split(xb), g.members()):
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(member.__matmul_x__(xb),
                                              np.float32),
                                   rtol=2e-2, atol=0.06 * np.sqrt(k))


def test_fused_quantized_model_matches_unfused_oracle_scan():
    """Whole-model equivalence THROUGH the scanned stage stack: a
    data-free fused quantization vs the same packed data consumed
    unfused (fused groups sliced back into wq/wk/wv, wg/wu)."""
    cfg = registry.get("tiny-lm").reduced()
    par = Parallel(remat=False, attn_chunk=32)
    params = M.init_params(cfg, par, jax.random.PRNGKey(0))
    qcfg = QuantConfig(ratio=0.2, multiple=16)
    qp = pipeline.quantize_params_data_free(params, qcfg, fuse=True)
    # the transform must have produced stacked fused groups
    groups = [l for l in jax.tree.leaves(
        qp["stages"], is_leaf=lambda x: isinstance(x, QLinearGroup))
        if isinstance(l, QLinearGroup)]
    assert groups, "no QLinearGroup produced by fuse=True"
    oracle = T.unfuse_params_for_oracle(qp)
    rng = np.random.default_rng(1)
    b, s, max_seq = 2, 8, 32
    toks = jnp.asarray(rng.integers(1, cfg.vocab - 1, (b, s + 1)), jnp.int32)
    lq, cq = M.prefill(cfg, par, qp, {"tokens": toks[:, :s]}, max_seq)
    lu, cu = M.prefill(cfg, par, oracle, {"tokens": toks[:, :s]}, max_seq)
    np.testing.assert_allclose(np.asarray(lq, np.float32),
                               np.asarray(lu, np.float32),
                               rtol=1e-5, atol=1e-5)
    pos = jnp.full((b,), s, jnp.int32)
    dq, _ = M.decode_step(cfg, par, qp, toks[:, s], pos, cq, max_seq)
    du, _ = M.decode_step(cfg, par, oracle, toks[:, s], pos, cu, max_seq)
    np.testing.assert_allclose(np.asarray(dq, np.float32),
                               np.asarray(du, np.float32),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Pre-permuted vs stored-perm forwards (kernel path AND odd-shape fallback)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k_s,k_b,n,kernel_feasible", [
    (128, 512, 384, True),    # aligned: Pallas kernel path
    (128, 192, 256, True),    # bk must drop to the common divisor 64
    (128, 136, 192, False),   # N % 128 != 0: XLA fallback path
])
def test_pre_permuted_matches_stored_perm(rng, k_s, k_b, n, kernel_feasible):
    from repro.kernels import autotune
    k = k_s + k_b
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    stat = jnp.asarray(rng.uniform(0.1, 10.0, k), jnp.float32)
    q = quantize_linear(w, stat, QuantConfig(ratio=k_s / k, multiple=8,
                                             use_kernel=True))
    assert (q.k_s, q.k_b) == (k_s, k_b)
    assert (autotune.choose_blocks(4, q.k_s, q.k_b, n) is not None) \
        == kernel_feasible
    x = jnp.asarray(rng.normal(size=(4, k)), jnp.bfloat16)
    xp = jnp.take(x, q.perm, axis=-1)
    y_stored = ops.mixed_matmul(x, q)
    y_pre = ops.mixed_matmul(xp, q, pre_permuted=True)
    np.testing.assert_array_equal(np.asarray(y_stored, np.float32),
                                  np.asarray(y_pre, np.float32))
    # both must agree with the XLA dequant oracle
    oracle = dataclasses.replace(q, use_kernel=False).__matmul_x__(x)
    np.testing.assert_allclose(np.asarray(y_stored, np.float32),
                               np.asarray(oracle, np.float32),
                               rtol=2e-2, atol=0.06 * np.sqrt(k) * 2)


# ---------------------------------------------------------------------------
# bf16 attention == f32 oracle within accumulation tolerance
# ---------------------------------------------------------------------------
def test_bf16_attention_close_to_f32_oracle(rng):
    b, sq, sk, hq, hkv, dh = 2, 4, 16, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(b, sq, hq, dh)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, sk, hkv, dh)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, sk, hkv, dh)), jnp.bfloat16)
    mask = jnp.tril(jnp.ones((1, sq, sk), bool), k=sk - sq)
    o = L._attend(q, k, v, mask, None)

    import math
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, hq // hkv, dh)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qf, k.astype(jnp.float32))
    s = s / math.sqrt(dh)
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    w = jax.nn.softmax(s, -1)
    o_ref = jnp.einsum("bhrqk,bkhd->bqhrd", w, v.astype(jnp.float32))
    o_ref = o_ref.reshape(b, sq, hq, dh)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# MoE expert gate+up fusion == per-projection oracle (expert_dense path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "mixtral-8x22b"])
def test_moe_expert_fusion_matches_unfused(arch):
    """Fusing the stacked expert wg/wu along N (one expert_dense batched
    matmul for both projections) must be exact for fp weights and
    bit-identical to the group's unfused member views when quantized."""
    cfg = registry.get(arch).reduced()
    par = Parallel(remat=False, attn_chunk=32)
    params = M.init_params(cfg, par, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "targets": jnp.ones((2, 16), jnp.int32)}
    base = M.forward_loss(cfg, par, params, batch)

    fused = T.fuse_params_for_decode(params)
    assert any("wgu" in bp.get("mlp", {}) and "router" in bp.get("mlp", {})
               for sp in fused["stages"] for bp in sp), \
        "MoE expert wg/wu must fuse into a QLinearGroup"
    lf = M.forward_loss(cfg, par, fused, batch)
    lu = M.forward_loss(cfg, par, T.unfuse_params_for_oracle(fused), batch)
    assert float(base) == float(lf) == float(lu), \
        "fp expert fusion is pure concatenation — must be exact"

    qp = pipeline.quantize_params_data_free(
        params, QuantConfig(ratio=0.25, multiple=16), min_dim=32,
        fuse=True)
    lq = M.forward_loss(cfg, par, qp, batch)
    lqu = M.forward_loss(cfg, par, T.unfuse_params_for_oracle(qp), batch)
    assert np.isfinite(float(lq))
    assert float(lq) == float(lqu), \
        "fused packed layout must match its unfused member views exactly"
