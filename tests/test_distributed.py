"""Distribution substrate: sharding rules, gradient compression (error
feedback), GPipe pipeline vs sequential oracle, HLO analyzer."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as PS

from repro.distributed.compression import (CompressionConfig, compress,
                                           init_residual)
from repro.distributed.sharding import Rules
from repro.models.param import P


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------
def test_rules_basic_mapping():
    r = Rules()
    assert r.spec(("embed", "heads")) == PS(None, "model")
    assert r.spec(("batch", None, None)) == PS(("data",), None, None)
    assert r.spec(("layers", "embed", "ffn")) == PS(None, None, "model")


def test_rules_conflict_resolution():
    """Same mesh axis twice in one spec → later dim degrades to None."""
    r = Rules(ep=True)
    s = r.spec(("experts", "embed", "ffn"))
    assert s == PS("model", None, None)
    r2 = Rules(ep=False)
    assert r2.spec(("experts", "embed", "ffn")) == PS(None, None, "model")


def test_rules_fsdp_and_multipod():
    r = Rules(dp_axes=("pod", "data"), fsdp=True)
    assert r.spec(("embed", "heads")) == PS(("pod", "data"), "model")
    assert r.spec(("batch", None)) == PS(("pod", "data"), None)
    # fsdp + batch in one spec: no double use of data
    assert r.spec(("batch", "embed")) == PS(("pod", "data"), None)


# ---------------------------------------------------------------------------
# Gradient compression — error feedback
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["int8", "topk"])
def test_error_feedback_preserves_signal(kind, rng):
    """Σ_t compressed_t  →  Σ_t g_t : EF residual carries the rounding
    error forward so the long-run average is unbiased."""
    ccfg = CompressionConfig(kind=kind, topk_frac=0.3)
    g = {"w": jnp.asarray(rng.normal(size=(64,)), jnp.float32)}
    res = init_residual(g)
    total_sent = jnp.zeros((64,))
    steps = 30
    for _ in range(steps):
        sent, res = compress(g, res, ccfg)
        total_sent = total_sent + sent["w"]
    expect = np.asarray(g["w"]) * steps
    got = np.asarray(total_sent)
    # residual bounded → averages converge
    assert np.abs(got - expect).max() <= np.abs(np.asarray(g["w"])).max() + 1e-3


def test_compression_noop():
    g = {"a": jnp.ones((4,))}
    out, res = compress(g, jnp.zeros(()), CompressionConfig(kind=None))
    assert out is g


def test_compress_handles_tuple_nodes(rng):
    """Param trees contain tuple stage nodes — regression for the
    tuple-leaf tree_map bug."""
    g = {"stages": [(jnp.ones((4,)), jnp.ones((2,)))], "x": jnp.ones((3,))}
    res = init_residual(g)
    out, res2 = compress(g, res, CompressionConfig(kind="int8"))
    assert jax.tree.structure(out) == jax.tree.structure(g)


# ---------------------------------------------------------------------------
# AdamW with tuple-containing trees (same regression class)
# ---------------------------------------------------------------------------
def test_adamw_tuple_tree(rng):
    from repro.optim.adamw import AdamW
    params = {"stages": [(jnp.ones((4,)), jnp.ones((2, 2)))],
              "embed": jnp.ones((3,))}
    grads = jax.tree.map(jnp.ones_like, params)
    opt = AdamW(lr=0.1)
    st = opt.init(params)
    p2, st2 = opt.update(grads, st, params)
    assert jax.tree.structure(p2) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)):
        assert (np.asarray(a) < np.asarray(b)).all()   # moved downhill


# ---------------------------------------------------------------------------
# GPipe pipeline vs sequential oracle (multi-device CPU via shard_map)
# ---------------------------------------------------------------------------
def test_pipeline_matches_sequential(rng):
    if jax.device_count() < 2:
        pytest.skip("needs >1 device")  # dryrun-only env has 512


def test_pipeline_single_stage_oracle(rng):
    """n_stages=1 degenerate ring equals plain application."""
    from repro.distributed.pipeline import pipeline_apply
    mesh = jax.make_mesh((1,), ("stage",), axis_types=(AxisType.Auto,))
    w = jnp.asarray(rng.normal(size=(1, 8, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(3, 4, 8)), jnp.float32)

    def block(p, h):
        return jnp.tanh(h @ p)

    out = pipeline_apply(block, w, x, mesh, axis="stage")
    ref = jnp.stack([block(w[0], x[i]) for i in range(3)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# HLO analyzer — the roofline's measurement tool
# ---------------------------------------------------------------------------
def test_hlo_flop_count_scan_vs_unroll():
    """Trip-count-aware FLOPs must match the closed form on a scan that
    XLA's own cost_analysis undercounts."""
    from repro.launch import hlo_analysis as H
    D, L, MB = 64, 5, 3

    def loss(params, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, params)
        return jnp.mean(h ** 2)

    def train(params, xs):
        def micro(acc, x):
            l, g = jax.value_and_grad(loss)(params, x)
            return (acc[0] + l, acc[1] + g), None
        (l, g), _ = jax.lax.scan(micro, (0.0, jnp.zeros_like(params)), xs)
        return l, g

    params = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    xs = jax.ShapeDtypeStruct((MB, 32, D), jnp.float32)
    c = jax.jit(train).lower(params, xs).compile()
    mod = H.module_analysis(c.as_text())
    expect = 2 * 32 * D * D * L * MB * 3       # fwd + dgrad + wgrad
    assert abs(mod["flops"] - expect) / expect < 0.05
    xla = float(c.cost_analysis().get("flops", 0.0))
    assert xla < 0.5 * expect                  # XLA's known undercount


def test_tpu_kernels_inventory_fixture():
    """Pallas kernels are found by their instruction base name on
    tpu_custom_call sites only (other custom calls do not count)."""
    from repro.launch import hlo_analysis as H
    hlo = """
HloModule test

%body (p: f32[4,2048]) -> f32[4,2048] {
  ROOT %mixed_matmul.12 = f32[4,2048]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {}}
}

ENTRY %main (a: bf16[4,2048]) -> f32[4,2048] {
  %mixed_matmul.1 = f32[4,2048]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call"
  %paged_attention = f32[4,2,8,128]{3,2,1,0} custom-call(%c), custom_call_target="tpu_custom_call"
  %sharding.3 = f32[4,2048]{1,0} custom-call(%a), custom_call_target="Sharding"
}
"""
    assert H.tpu_kernels(hlo) == {"mixed_matmul": 2, "paged_attention": 1}
    assert H.tpu_kernels("HloModule empty") == {}


def test_hlo_collective_parsing_fixture():
    from repro.launch import hlo_analysis as H
    hlo = """
HloModule test

%region_body (p: (s32[], f32[16,128])) -> (s32[], f32[16,128]) {
  %ar = f32[16,128]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256], use_global_device_ids=true, to_apply=%add
  ROOT %t = (s32[], f32[16,128]) tuple(%i, %ar)
}

%region_cond (p: (s32[], f32[16,128])) -> pred[] {
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[16,128]) -> f32[16,128] {
  %w = (s32[], f32[16,128]) while(%init), condition=%region_cond, body=%region_body, backend_config={"known_trip_count":{"n":"7"}}
  %ag = f32[64,128]{1,0} all-gather(%y), replica_groups=[64,4]<=[256], dimensions={0}
  ROOT %gte = f32[16,128] get-tuple-element(%w), index=1
}
"""
    s = H.collective_summary(hlo)
    ar = s["per_kind"]["all-reduce"]
    assert ar["count"] == 7
    assert ar["operand_bytes"] == 7 * 16 * 128 * 4
    ag = s["per_kind"]["all-gather"]
    assert ag["count"] == 1
    assert ag["operand_bytes"] == 64 * 128 * 4 // 4
    assert ag["wire_bytes"] == 64 * 128 * 4 * 3 // 4


def test_roofline_terms():
    from repro.launch.hlo_analysis import roofline_terms
    r = roofline_terms(197e12, 819e9, 0.0)     # 1s compute, 1s memory
    assert r["compute_s"] == pytest.approx(1.0)
    assert r["memory_s"] == pytest.approx(1.0)
    assert r["dominant"] in ("compute", "memory")
    r2 = roofline_terms(1e12, 1e9, 500e9)
    assert r2["dominant"] == "collective"
    assert r2["compute_fraction"] < 1.0
