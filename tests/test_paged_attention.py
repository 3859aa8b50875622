"""Paged flash-decode attention kernel: parity vs the XLA-gather
reference, autotuned KV tiles, the fully-inactive short-circuit, and
engine-level greedy identity (kernel on vs off) across preemption.

Parity structure mirrors test_kernels.py: the Pallas kernel (interpret
mode on CPU) against a pure-jnp oracle built exactly like
``layers.attention_decode_paged``'s fallback path — dense page gather,
implied-position mask, ``layers._attend``.  The engine-level identity
tests run in f32 (params AND KV pools): the two paths round differently
at the bf16 ulp, while an untrained tiny-lm's top-2 logit gaps sit at
that same ulp, so bf16 token identity would be a coin flip on ties —
in f32 the path delta (~1e-6 relative) is three orders below the gaps
and identity is robust.
"""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.kernels import autotune, ops
from repro.models import layers as L
from repro.models import model as M
from repro.models.common import Parallel
from repro.runtime.engine import Engine

PAR = Parallel(remat=False, attn_chunk=32)
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Kernel vs dense-gather oracle
# ---------------------------------------------------------------------------
def _oracle(q, k_pool, v_pool, bt, lens, window=None, softcap=None):
    """The XLA reference read: gather pages dense, mask implied
    positions, one-shot softmax (layers._attend semantics)."""
    b, hq, dh = q.shape
    _, ps, hkv, _ = k_pool.shape
    nblk = bt.shape[1]
    kctx = k_pool[jnp.clip(bt, 0)].reshape(b, nblk * ps, hkv, dh)
    vctx = v_pool[jnp.clip(bt, 0)].reshape(b, nblk * ps, hkv, dh)
    kp = L.paged_key_positions(jnp.asarray(bt), ps)
    pos = lens[:, None] - 1
    mask = jnp.logical_and(kp <= pos, kp >= 0)
    if window is not None:
        mask = jnp.logical_and(mask, pos - kp < window)
    o = L._attend(q[:, None], kctx, vctx, mask[:, None, :], softcap)
    return np.asarray(o[:, 0], np.float32)


def _pool_state(rng, num_pages, ps, hkv, dh, dtype):
    k_pool = jnp.asarray(rng.normal(size=(num_pages, ps, hkv, dh)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(num_pages, ps, hkv, dh)), dtype)
    return k_pool, v_pool


# pages per compute block: one page, a divisor, non-divisors of the
# 5-page table, and the whole table (None)
PPCB = [1, 2, 3, 4, None]


def _run(q, k_pool, v_pool, bt, lens, ppcb, **kw):
    nblk = np.asarray(bt).shape[1]
    return np.asarray(ops.paged_attention(
        q, k_pool[None], v_pool[None], jnp.asarray(bt), jnp.asarray(lens),
        layer=0, ppcb=nblk if ppcb is None else ppcb, **kw))


@pytest.mark.parametrize("ppcb", PPCB)
@pytest.mark.parametrize("rep", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_parity_ragged_gqa(rng, rep, dtype, ppcb):
    """Ragged lengths (incl. a page-boundary length and an inactive
    len=0 row) across GQA head ratios and pages per compute block."""
    b, num_pages, ps, hkv, dh, nblk = 4, 20, 8, 2, 16, 5
    hq = hkv * rep
    q = jnp.asarray(rng.normal(size=(b, hq, dh)), dtype)
    k_pool, v_pool = _pool_state(rng, num_pages, ps, hkv, dh, dtype)
    bt = np.full((b, nblk), -1, np.int32)
    bt[0, :3] = [3, 7, 1]
    bt[1, :1] = [0]
    bt[2, :5] = [2, 4, 5, 9, 11]
    lens = np.asarray([17, 8, 40, 0], np.int32)     # row 3: inactive
    out = _run(q, k_pool, v_pool, bt, lens, ppcb)
    ref = _oracle(q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens))
    tol = 1e-5 if dtype == jnp.float32 else 0.06 * math.sqrt(dh)
    np.testing.assert_allclose(out[:3], ref[:3], rtol=2e-2, atol=tol)
    # inactive row: exact zeros (never the reference's uniform garbage)
    np.testing.assert_array_equal(out[3], 0.0)


@pytest.mark.parametrize("ppcb", PPCB)
def test_kernel_parity_block_edges(rng, ppcb):
    """Lengths that end exactly on a compute-block edge and one token
    past it: the last block's masked tail and the next block's single
    live token."""
    b, num_pages, ps, hkv, dh, nblk = 4, 24, 8, 2, 16, 5
    tile = (nblk if ppcb is None else ppcb) * ps
    q = jnp.asarray(rng.normal(size=(b, hkv * 2, dh)), jnp.float32)
    k_pool, v_pool = _pool_state(rng, num_pages, ps, hkv, dh, jnp.float32)
    bt = np.arange(b * nblk, dtype=np.int32).reshape(b, nblk)
    lens = np.asarray([tile, min(tile + 1, nblk * ps), tile - 1,
                       nblk * ps], np.int32)
    out = _run(q, k_pool, v_pool, bt, lens, ppcb)
    ref = _oracle(q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens))
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=1e-5)


@pytest.mark.parametrize("ppcb", PPCB)
def test_kernel_parity_freed_pages_mid_table(rng, ppcb):
    """-1 entries in the MIDDLE of a table (freed pages) are masked like
    the implied-position reference, not attended via a clamped fetch —
    also when the hole sits inside a live compute block."""
    b, num_pages, ps, hkv, dh, nblk = 2, 16, 8, 2, 16, 4
    q = jnp.asarray(rng.normal(size=(b, hkv * 2, dh)), jnp.float32)
    k_pool, v_pool = _pool_state(rng, num_pages, ps, hkv, dh, jnp.float32)
    bt = np.asarray([[5, -1, 8, 2],
                     [1, 3, -1, -1]], np.int32)
    lens = np.asarray([29, 14], np.int32)
    out = _run(q, k_pool, v_pool, bt, lens, ppcb)
    ref = _oracle(q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens))
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=1e-5)


@pytest.mark.parametrize("ppcb", PPCB)
@pytest.mark.parametrize("window,softcap", [(12, None), (12, 30.0),
                                            (3, None), (None, 30.0)])
def test_kernel_parity_window_softcap(rng, window, softcap, ppcb):
    """Sliding windows that start mid-page and mid-block, with and
    without a logit softcap."""
    b, num_pages, ps, hkv, dh, nblk = 3, 16, 8, 2, 16, 5
    q = jnp.asarray(rng.normal(size=(b, hkv * 2, dh)), jnp.float32)
    k_pool, v_pool = _pool_state(rng, num_pages, ps, hkv, dh, jnp.float32)
    bt = np.full((b, nblk), -1, np.int32)
    bt[0, :3] = [3, 7, 1]
    bt[1, :2] = [0, 6]
    bt[2, :5] = [2, 4, 5, 9, 11]
    lens = np.asarray([23, 9, 37], np.int32)
    out = _run(q, k_pool, v_pool, bt, lens, ppcb, window=window,
               softcap=softcap)
    ref = _oracle(q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens),
                  window=window, softcap=softcap)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=1e-5)


@pytest.mark.parametrize("ppcb", PPCB)
def test_kernel_nan_in_unfetched_pages(rng, ppcb):
    """Pool pages no row fetches hold NaN, and so do the -1 holes'
    fallback page 0 and the tails past each row's table: the output
    stays finite and matches the reference on a clean pool — a page
    that is never fetched never reaches p @ V (0 x NaN is NaN)."""
    b, num_pages, ps, hkv, dh, nblk = 3, 16, 8, 2, 16, 5
    q = jnp.asarray(rng.normal(size=(b, hkv * 2, dh)), jnp.float32)
    k_pool, v_pool = _pool_state(rng, num_pages, ps, hkv, dh, jnp.float32)
    bt = np.asarray([[3, -1, 7, -1, -1],
                     [4, 6, 8, 9, 10],
                     [-1, -1, -1, -1, -1]], np.int32)
    lens = np.asarray([20, 33, 0], np.int32)
    used = np.zeros((num_pages,), bool)
    used[[3, 7, 4, 6, 8, 9, 10]] = True
    nan = jnp.asarray(~used)[:, None, None, None]
    k_nan = jnp.where(nan, jnp.nan, k_pool)
    v_nan = jnp.where(nan, jnp.nan, v_pool)
    out = _run(q, k_nan, v_nan, bt, lens, ppcb, window=30)
    assert np.isfinite(out).all()
    ref = _oracle(q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens),
                  window=30)
    np.testing.assert_allclose(out[:2], ref[:2], rtol=2e-2, atol=1e-5)
    np.testing.assert_array_equal(out[2], 0.0)


@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_reads_only_its_layer(rng, layer):
    """The kernel takes the stacked pool and DMAs pages of ``layer``
    alone: the other layers hold NaN or 1e30, and the output matches the
    XLA gather of that layer by itself."""
    b, num_pages, ps, hkv, dh, nblk = 3, 12, 8, 2, 16, 4
    q = jnp.asarray(rng.normal(size=(b, hkv * 2, dh)), jnp.float32)
    k_pool, v_pool = _pool_state(rng, num_pages, ps, hkv, dh, jnp.float32)
    fill = (jnp.nan, 1e30, jnp.nan)

    def stack(pool):
        return jnp.stack([pool if i == layer else jnp.full_like(pool, f)
                          for i, f in enumerate(fill)])
    bt = np.asarray([[3, 7, -1, -1],
                     [0, 1, 2, 5],
                     [9, 4, 11, -1]], np.int32)
    lens = np.asarray([13, 30, 17], np.int32)
    out = np.asarray(ops.paged_attention(
        q, stack(k_pool), stack(v_pool), jnp.asarray(bt), jnp.asarray(lens),
        layer=layer, ppcb=2))
    assert np.isfinite(out).all()
    ref = _oracle(q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens))
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=1e-5)


def test_kernel_bh_sweep_block_size_independent(rng):
    """Results must not depend on the pages per compute block, with four
    kv heads interleaved in each block's rows."""
    b, num_pages, ps, hkv, dh, nblk = 2, 12, 8, 4, 16, 3
    q = jnp.asarray(rng.normal(size=(b, hkv * 2, dh)), jnp.float32)
    k_pool, v_pool = _pool_state(rng, num_pages, ps, hkv, dh, jnp.float32)
    bt = np.asarray([[0, 1, 2], [3, 4, -1]], np.int32)
    lens = np.asarray([20, 11], np.int32)
    outs = [np.asarray(ops.paged_attention(
        q, k_pool[None], v_pool[None], jnp.asarray(bt), jnp.asarray(lens),
        layer=0, ppcb=ppcb))
        for ppcb in (1, 2, 3)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-6, atol=1e-6)


def test_fetched_page_counts_match_live_pages():
    """The fetch-contract replay (shared page_fetched — what
    serving_bench asserts on) fetches exactly the live pages: ceil(len/ps)
    for active rows, none for inactive ones and holes, and only the
    in-window pages under a sliding window."""
    from repro.kernels.paged_attention import fetched_page_counts
    ps = 8
    bt = np.asarray([[3, 7, 1, -1],      # 17 live tokens -> 3 pages
                     [0, -1, -1, -1],    # 8 live -> 1 page
                     [2, 4, 5, 9],       # 32 live -> 4 pages
                     [-1, -1, -1, -1],   # inactive -> nothing
                     [6, -1, 8, -1]],    # 20 live, one hole -> 2 pages
                    np.int32)
    lens = np.asarray([17, 8, 32, 0, 20], np.int32)
    np.testing.assert_array_equal(
        fetched_page_counts(bt, lens, ps), [3, 1, 4, 0, 2])
    # sliding window 8 over 32 live tokens: positions 24..31 = page 3
    # only; window 9 reaches one token into page 2
    win = fetched_page_counts(bt, lens, ps, window=8)
    assert win[2] == 1
    assert fetched_page_counts(bt, lens, ps, window=9)[2] == 2
    # every row obeys the serving_bench gate: pages*ps <= live + ps
    for fetched, live in zip(fetched_page_counts(bt, lens, ps), lens):
        assert fetched * ps <= live + ps


# ---------------------------------------------------------------------------
# Autotuned KV tiles
# ---------------------------------------------------------------------------
def test_choose_paged_blocks():
    c = autotune.choose_paged_blocks(8, 4, 128, 16, 160)
    assert c is not None
    assert c.vmem_bytes <= autotune.VMEM_BUDGET
    assert c.kv_bytes_per_token == 2 * 8 * 128 * 2
    # a starved budget degrades to fewer pages before giving up
    tight = autotune.choose_paged_blocks(8, 4, 128, 16, 160,
                                         vmem_budget=1 << 16)
    assert tight is None or tight.ppcb < c.ppcb
    assert autotune.choose_paged_blocks(8, 4, 128, 16, 160,
                                        vmem_budget=1 << 10) is None
    assert autotune.choose_paged_blocks(0, 4, 128, 16, 160) is None


def test_choose_paged_blocks_pages_per_block():
    """ppcb comes from the shape: 512 tokens a block where the table and
    VMEM allow, the whole table when it is narrower, fewer pages under a
    tight budget, and a footprint that grows with ppcb."""
    # qwen2.5-3b serving cell: 2 kv heads of 128, page 16, 160-page table
    assert autotune.choose_paged_blocks(2, 8, 128, 16, 160).ppcb == 32
    # qwen3-4b: 8 kv heads, 4 query heads each
    assert autotune.choose_paged_blocks(8, 4, 128, 16, 288).ppcb == 32
    # chip_smoke's 32-page table, and a narrower one
    assert autotune.choose_paged_blocks(2, 8, 128, 16, 32).ppcb == 32
    assert autotune.choose_paged_blocks(2, 8, 128, 16, 5).ppcb == 5
    budget = autotune.paged_attn_vmem_bytes(2, 8, 128, 16, 8)
    tight = autotune.choose_paged_blocks(2, 8, 128, 16, 160,
                                         vmem_budget=budget)
    assert tight.ppcb == 8 and tight.vmem_bytes == budget
    sizes = [autotune.paged_attn_vmem_bytes(2, 8, 128, 16, p)
             for p in (1, 2, 4, 8, 16, 32)]
    assert sizes == sorted(sizes) and len(set(sizes)) == len(sizes)
    # the cell's double-buffered K and V blocks of 512 tokens: 1 MiB
    assert sizes[-1] >= 4 * 512 * 2 * 128 * 2


def test_attn_block_counts_with_window():
    """The engine's live-block counter on a hand-made batch: blocks up
    to each row's length, less those wholly below the window start."""
    from repro.kernels.paged_attention import attn_block_counts
    ps, ppcb, nblk = 8, 2, 7                   # 16-token blocks, 4 a row
    lens = np.asarray([0, 1, 16, 17, 56, 40])
    assert attn_block_counts(lens, ps, ppcb, nblk) == (0 + 1 + 1 + 2 + 4
                                                       + 3, 6 * 4)
    # window 20: row 56 sees 36..55 -> blocks 2, 3; row 40 sees
    # 20..39 -> blocks 1, 2; row 17 sees 0..16 -> blocks 0, 1
    assert attn_block_counts(lens, ps, ppcb, nblk, window=20) == (
        0 + 1 + 1 + 2 + 2 + 2, 24)


def test_engine_counts_attention_blocks(subject):
    """EngineMetrics carries the running live / all compute-block sums
    of the decode kernel, and the live ones are a share of all."""
    cfg, params = subject
    eng = Engine(cfg, PAR, params, n_slots=2, max_seq=64,
                 prefill_buckets=(16, 32), paged=True, page_size=8)
    local = np.random.default_rng(0)
    for n in (9, 20):
        eng.submit(local.integers(1, cfg.vocab, size=n).astype(np.int32),
                   max_new=4)
    eng.run()
    snap = eng.metrics.snapshot()
    ppcb = eng.backend._attn_blocks[0]
    nb = -(-8 // ppcb)
    assert snap["attn_blocks"] == 2 * nb * snap["ticks"]
    assert 0 < snap["attn_blocks_live"] <= snap["attn_blocks"]


def test_paged_read_bytes_page_slack():
    """The cost-model contract serving_bench asserts: whole-page reads
    cost at most one page of slack past the live tokens."""
    per_tok = autotune.paged_kv_bytes_per_token(4, 64)
    for n in (1, 15, 16, 17, 100):
        got = autotune.paged_read_bytes(n, 16, 4, 64)
        assert got >= n * per_tok
        assert got <= (n + 16) * per_tok


# ---------------------------------------------------------------------------
# Layer-level dispatch and the fully-inactive short-circuit
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def subject():
    cfg = registry.get("tiny-lm").reduced()
    params = M.init_params(cfg, PAR, jax.random.PRNGKey(0))
    return cfg, params


def _to_f32(tree):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


def _paged_state(cfg, n_slots=2, num_pages=16, ps=8, dtype=jnp.float32):
    caches = M.init_paged_caches(cfg, PAR, n_slots, num_pages, ps)
    from repro.models.param import materialize
    caches = materialize(caches, jax.random.PRNGKey(1))
    if dtype == jnp.float32:
        caches = _to_f32(caches)
    return caches


def test_decode_step_paged_kernel_matches_xla(subject, rng):
    """Whole-model one-step parity: kernel vs XLA-gather reference on
    identical pool state (f32 so the comparison is tight)."""
    cfg, params = subject
    params = _to_f32(params)
    caches = _paged_state(cfg)
    caches = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape) * 0.3, a.dtype)
        if a.ndim >= 4 else a, caches)
    bt = np.asarray([[0, 1, -1, -1, -1, -1, -1, -1],
                     [2, 3, 4, -1, -1, -1, -1, -1]], np.int32)
    lens = np.asarray([10, 19], np.int32)
    tok = jnp.asarray(rng.integers(1, cfg.vocab, size=2), jnp.int32)
    pos = jnp.asarray(lens - 1)
    args = (params, tok, pos, caches, jnp.asarray(bt), jnp.asarray(lens))
    lk, ck = M.decode_step_paged(cfg, PAR, *args, max_seq=64,
                                 use_kernel=True)
    lx, cx = M.decode_step_paged(cfg, PAR, *args, max_seq=64,
                                 use_kernel=False)
    np.testing.assert_allclose(np.asarray(lk, np.float32),
                               np.asarray(lx, np.float32),
                               rtol=1e-4, atol=1e-4)
    # both paths scatter the same new K/V
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ck, cx)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_decode_step_paged_inactive_short_circuit(subject, rng, use_kernel):
    """Every block-table row -1 (no slot owns a page): the stage walk is
    skipped on device — caches come back untouched and the logits are
    finite (regression: the reference used to gather + mask a fully
    dense (B, nblk*ps) context for nothing)."""
    cfg, params = subject
    caches = _paged_state(cfg, dtype=jnp.bfloat16)
    bt = np.full((2, 8), -1, np.int32)
    lens = np.zeros((2,), np.int32)
    tok = jnp.asarray(rng.integers(1, cfg.vocab, size=2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    logits, new_caches = M.decode_step_paged(
        cfg, PAR, params, tok, pos, caches, jnp.asarray(bt),
        jnp.asarray(lens), max_seq=64, use_kernel=use_kernel)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), caches, new_caches)


# ---------------------------------------------------------------------------
# Engine-level greedy identity (kernel on vs off)
# ---------------------------------------------------------------------------
def _f32_engine(cfg, params, **kw):
    eng = Engine(cfg, PAR, params, n_slots=2, max_seq=64,
                 prefill_buckets=(16, 32), paged=True, page_size=8, **kw)
    eng.backend.caches = _to_f32(eng.backend.caches)
    return eng


@pytest.mark.parametrize("tight_pool", [False, True])
def test_engine_greedy_kernel_vs_xla_identical(subject, tight_pool):
    """Acceptance: greedy tokens through the flash-decode kernel are
    IDENTICAL to the XLA-gather reference engine — including through
    pool exhaustion, preemption and full-context resume (tight pool).
    f32 end-to-end; see module docstring for why bf16 can't carry a
    token-identity claim on an untrained subject."""
    cfg, params = subject
    params = _to_f32(params)
    local = np.random.default_rng(0)
    if tight_pool:
        prompts = [local.integers(1, cfg.vocab, size=13).astype(np.int32)
                   for _ in range(3)]
        kw = dict(pool_pages=6)
        max_new = 20
    else:
        prompts = [local.integers(1, cfg.vocab, size=n).astype(np.int32)
                   for n in (4, 9, 13, 7, 21)]
        kw = {}
        max_new = 6

    def run(kernel):
        eng = _f32_engine(cfg, params, paged_kernel=kernel, **kw)
        reqs = [eng.submit(p, max_new=max_new) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs], sum(r.preemptions
                                                 for r in reqs)
    toks_k, pre_k = run(True)
    toks_x, pre_x = run(False)
    assert toks_k == toks_x
    if tight_pool:
        assert pre_k >= 1 and pre_k == pre_x


def test_engine_greedy_kernel_vs_xla_hybrid_window(rng):
    """The sliding-window kernel branch through the FULL dispatch stack
    (engine → stage_step_paged → attention_decode_paged kernel path):
    recurrentgemma's local-attention blocks carry window=_kind_window
    into the kernel, interleaved with per-slot recurrent state.  The
    workload pushes contexts to 44 tokens against local_window=32, so
    the window mask BINDS and the below-window page-skip clamp
    (first > 0) runs, not just the causal tail.  f32 end-to-end,
    kernel vs XLA reference — greedy tokens identical."""
    cfg = registry.get("recurrentgemma-2b").reduced()
    assert cfg.local_window == 32
    params = _to_f32(M.init_params(cfg, PAR, jax.random.PRNGKey(0)))
    local = np.random.default_rng(0)
    prompts = [local.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in (30, 11, 37)]      # 37 truncates to the 32 bucket

    def run(kernel):
        eng = _f32_engine(cfg, params, paged_kernel=kernel)
        reqs = [eng.submit(p, max_new=12) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs]

    assert run(True) == run(False)


def test_engine_context_lens_follow_slots(subject, rng):
    """BlockTables.context_lens is the kernel's scalar-prefetch length
    operand: pos+1 while a slot decodes, 0 once released."""
    cfg, params = subject
    eng = Engine(cfg, PAR, params, n_slots=2, max_seq=64,
                 prefill_buckets=(16, 32), paged=True, page_size=8)
    r = eng.submit(rng.integers(1, cfg.vocab, size=9).astype(np.int32),
                   max_new=3)
    eng.step()
    # lens was fixed at pos+1 for the write this tick performed; pos has
    # since advanced past it, so a live slot reads lens == pos
    lens = eng.backend.tables.context_lens()
    assert lens[0] == eng.pos[0] > 0       # live slot
    assert lens[1] == 0                    # empty slot
    eng.run()
    assert r.done
    assert (eng.backend.tables.context_lens() == 0).all()


# ---------------------------------------------------------------------------
# Head-dim padding (lane-tile pools for dh off the 128 TPU tile)
# ---------------------------------------------------------------------------
def test_kernel_padded_pool_matches_unpadded(rng):
    """A lane-padded pool (zero tails past the logical dh) produces the
    same attention output as the unpadded layout: zero q lanes add
    nothing to q·k, the softmax scale stays 1/sqrt(dh_logical), and the
    padded output columns are sliced off."""
    b, num_pages, ps, hkv, dh, nblk = 3, 12, 8, 2, 16, 4
    q = jnp.asarray(rng.normal(size=(b, hkv * 2, dh)), jnp.float32)
    k_pool, v_pool = _pool_state(rng, num_pages, ps, hkv, dh, jnp.float32)
    bt = np.asarray([[3, 7, -1, -1],
                     [0, 1, 2, 5],
                     [-1, -1, -1, -1]], np.int32)
    lens = np.asarray([13, 30, 0], np.int32)
    out = np.asarray(ops.paged_attention(q, k_pool[None], v_pool[None],
                                         jnp.asarray(bt),
                                         jnp.asarray(lens), layer=0))
    pad = ((0, 0), (0, 0), (0, 0), (0, 16))        # dh 16 -> 32 pool tile
    out_p = np.asarray(ops.paged_attention(q, jnp.pad(k_pool, pad)[None],
                                           jnp.pad(v_pool, pad)[None],
                                           jnp.asarray(bt),
                                           jnp.asarray(lens), layer=0))
    assert out_p.shape == out.shape                # sliced back to dh
    np.testing.assert_allclose(out_p, out, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(out_p[2], 0.0)   # inactive row intact


def test_padded_head_dim_policy_and_gate(monkeypatch):
    """padded_head_dim rounds to the lane tile only off-tile and only on
    real TPU backends; the feasibility gate accepts a padded pool for a
    dh that would otherwise be rejected."""
    assert ops.padded_head_dim(96) == 96           # interpret: no tax
    monkeypatch.setattr(ops, "INTERPRET", False)
    assert ops.padded_head_dim(128) == 128
    assert ops.padded_head_dim(96) == 128
    assert ops.padded_head_dim(200) == 256
    # dh=96 alone fails the lane floor; with its padded pool it passes
    assert ops.paged_attention_blocks(8, 2, 2, 96, 4, pool_dh=96) is None
    assert ops.paged_attention_blocks(8, 2, 2, 96, 4,
                                      pool_dh=128) is not None
    # a pool narrower than the query head dim is never feasible
    monkeypatch.setattr(ops, "INTERPRET", True)
    assert ops.paged_attention_blocks(8, 2, 2, 96, 4, pool_dh=64) is None


@pytest.mark.parametrize("use_kernel", [True, False])
def test_engine_greedy_identical_with_padded_pools(subject, monkeypatch,
                                                   use_kernel):
    """End-to-end padded layout: force padded_head_dim to widen the pool
    (as a real TPU would for tiny-lm's dh=32), serve a full workload
    through BOTH read paths, and require greedy tokens identical to the
    unpadded engine — writers pad, readers slice, nothing leaks."""
    cfg, params = subject
    params = _to_f32(params)
    local = np.random.default_rng(0)
    prompts = [local.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in (4, 9, 13, 7, 21)]

    def run(force_pad):
        if force_pad:
            monkeypatch.setattr(ops, "padded_head_dim",
                                lambda dh: dh * 2)
        else:
            monkeypatch.setattr(ops, "padded_head_dim", lambda dh: dh)
        eng = _f32_engine(cfg, params, paged_kernel=use_kernel)
        dh_pool = eng.backend.caches[0][0]["k"].shape[-1]
        assert dh_pool == cfg.head_dim_ * (2 if force_pad else 1)
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs]

    assert run(False) == run(True)
