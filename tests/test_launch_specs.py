"""Launcher-layer consistency: presets, input specs and abstract
quantized declarations build for every (arch × cell) — no device work
(P trees and ShapeDtypeStructs only), so the full 40-cell matrix is
checked in seconds.  The actual lower+compile evidence lives in
results/dryrun (launch/dryrun.py)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as PS

from repro.configs import registry
from repro.configs.base import SHAPE_CELLS, cell_applicable
from repro.core.qlinear import QLinear, QuantConfig
from repro.distributed.sharding import Rules
from repro.launch.inputs import (decode_inputs, prefill_inputs,
                                 train_inputs)
from repro.launch.qdeclare import declare_qlinear, declare_quantized
from repro.models import model as M
from repro.models.common import Parallel
from repro.models.param import P

RULES = Rules()
PAR = Parallel(tp=16, dp=16)


def _leaves_with_specs(abstract, specs):
    a = jax.tree.leaves(abstract,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, PS))
    return a, s


@pytest.mark.parametrize("arch", registry.ASSIGNED)
@pytest.mark.parametrize("cell", [c.name for c in SHAPE_CELLS])
def test_cell_specs_build_and_divide(arch, cell):
    """Every live cell's abstract inputs build, and every sharded dim is
    divisible by its mesh axes (the pjit boundary requirement that broke
    three archs before the ctx-sharded cache fix)."""
    from repro.configs.base import cell_by_name
    cfg = registry.get(arch)
    c = cell_by_name(cell)
    ok, why = cell_applicable(cfg, c)
    if not ok:
        assert "full-attention" in why
        return
    par = Parallel(tp=16, dp=16,
                   shard_batch=c.global_batch >= 16)
    axis_size = {"data": 16, "model": 16, "pod": 2}

    def check(abstract, specs):
        a, s = _leaves_with_specs(abstract, specs)
        assert len(a) == len(s)
        for sds, spec in zip(a, s):
            for dim, ax in zip(sds.shape, tuple(spec) + (None,) * 8):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else tuple(ax)
                total = int(np.prod([axis_size[x] for x in axes]))
                assert dim % total == 0, (arch, cell, sds.shape, spec)

    if c.kind == "train":
        inp, spec = train_inputs(cfg, c, par, RULES)
        check(inp, spec)
    elif c.kind == "prefill":
        inp, spec = prefill_inputs(cfg, c, par, RULES)
        check(inp, spec)
    else:
        (tok, pos, caches), (ts, ps2, cs) = decode_inputs(cfg, c, par,
                                                          RULES)
        check(caches, cs)


@pytest.mark.parametrize("arch", registry.ASSIGNED)
def test_declare_quantized_consistent(arch):
    """Abstract QLinear declarations mirror the real quantizer's shapes
    (packing divisibility, salient counts, spec-tree congruence)."""
    cfg = registry.get(arch)
    qcfg = QuantConfig(ratio=0.2, multiple=128)
    abstract, specs = declare_quantized(cfg, PAR, qcfg, RULES)
    n_q = 0

    def visit(a, s):
        nonlocal n_q
        if isinstance(a, QLinear):
            n_q += 1
            assert isinstance(s, QLinear)
            assert a.k_s % 128 == 0
            assert (a.k - a.k_s) % 8 == 0
            assert a.w4.shape[-2] == a.k_s // 2
            assert a.bits.shape[-2] == (a.k - a.k_s) // 8
    jax.tree.map(visit, abstract, specs,
                 is_leaf=lambda x: isinstance(x, QLinear))
    assert n_q > 0


def test_declare_qlinear_matches_quantize_linear(rng):
    """The abstract declaration predicts the real packed shapes."""
    from repro.core.qlinear import quantize_linear
    k, n = 1024, 256
    decl = declare_qlinear(P((k, n), ("embed", "ffn")),
                           QuantConfig(ratio=0.2, multiple=128))
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.02, jnp.float32)
    real = quantize_linear(w, None, QuantConfig(ratio=0.2, multiple=128))
    for f in QLinear._FIELDS:
        assert getattr(decl, f).shape == getattr(real, f).shape, f
        assert getattr(decl, f).dtype == getattr(real, f).dtype, f


def test_presets_cover_all_cells():
    """make_preset returns sane knobs for every cell without touching
    jax device state (uses a mesh-shaped stub)."""
    class StubDevices:
        size = 256

    class StubMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
        devices = StubDevices()
    from repro.launch.presets import make_preset
    from repro.configs.base import cell_by_name
    for arch in registry.ASSIGNED:
        cfg = registry.get(arch)
        for cell in SHAPE_CELLS:
            if not cell_applicable(cfg, cell)[0]:
                continue
            p = make_preset(cfg, cell, StubMesh())
            assert p.par.tp == 16
            assert p.par.microbatches >= 1
            assert p.par.remat == (cell.kind == "train")


def test_compile_cache_location(monkeypatch, tmp_path):
    """Entry points keep the persistent compile cache where
    $JAX_COMPILATION_CACHE_DIR says (setting nothing), else at the fixed
    in-repo .jax_cache."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert path.endswith(".jax_cache")
        assert (compile_cache.REPO_CACHE_DIR.parent / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
