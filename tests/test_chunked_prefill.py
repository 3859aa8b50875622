"""Chunked paged-prefill: kernel-vs-XLA bit-exactness, chunked-vs-whole
identity, mid-prefill preemption, prefix compute-skipping and the
retention LRU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels import ops
from repro.models import model as M
from repro.models.common import Parallel
from repro.runtime.engine import Engine
from repro.runtime.paged_cache import (BlockTables, PagePool, PrefixCache,
                                       pages_for_tokens)
from repro.runtime.scheduler import Scheduler

PAR = Parallel(tp=1, dp=1, remat=False, attn_chunk=32)


@pytest.fixture(scope="module")
def subject():
    cfg = registry.get("tiny-lm").reduced()
    params = M.init_params(cfg, PAR, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Kernel vs XLA dense-gather fallback: bit-exact in f32
# ---------------------------------------------------------------------------
def _rand_case(rng, *, start, length, hkv=2, rep=2, dh=16, ps=4, c=8,
               nblk=8, pool_pages=12, mask_first_chunk_page=False):
    hq = hkv * rep
    pp = pool_pages + 1                          # + dump page
    k_pool = jnp.asarray(rng.normal(size=(2, pp, ps, hkv, dh)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(2, pp, ps, hkv, dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(c, hq, dh)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(c, hkv, dh)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(c, hkv, dh)), jnp.float32)
    n_pages = pages_for_tokens(start + length, ps)
    bt = np.full((nblk,), -1, np.int32)
    bt[:n_pages] = rng.permutation(pool_pages)[:n_pages]
    btw = bt.copy()
    if mask_first_chunk_page:                    # a shared (COW) block
        btw[start // ps] = -1
    return q, kn, vn, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(btw)


@pytest.mark.parametrize("start,length,window,softcap", [
    (8, 8, None, None),          # full chunk over context
    (0, 8, None, None),          # first chunk, no context
    (8, 5, None, None),          # ragged tail (page-straddling)
    (16, 3, None, None),         # ragged, deeper context
    (8, 8, 5, None),             # sliding window
    (16, 7, 6, 30.0),            # window + softcap + ragged
])
def test_kernel_matches_xla_bit_exact(start, length, window, softcap):
    rng = np.random.default_rng(start * 100 + length)
    q, kn, vn, kp, vp, bt, btw = _rand_case(rng, start=start, length=length)
    ok, kk, vk = ops.paged_prefill(q, kn, vn, kp, vp, bt, btw, start,
                                   length, layer=1, window=window,
                                   softcap=softcap)
    ox, kx, vx = ops.paged_prefill_xla(q, kn, vn, kp, vp, bt, btw, start,
                                       length, layer=1, window=window,
                                       softcap=softcap)
    P = kp.shape[1] - 1
    assert bool(jnp.all(ok[:length] == ox[:length])), \
        "kernel output must match the dense-gather fallback bit-exactly"
    assert bool(jnp.all(kk[:, :P] == kx[:, :P]))
    assert bool(jnp.all(vk[:, :P] == vx[:, :P]))


@pytest.mark.parametrize("hkv,rep", [(1, 4), (2, 1), (4, 2)])
def test_kernel_gqa_ratios(hkv, rep):
    rng = np.random.default_rng(hkv * 10 + rep)
    q, kn, vn, kp, vp, bt, btw = _rand_case(rng, start=8, length=8,
                                            hkv=hkv, rep=rep)
    ok, kk, vk = ops.paged_prefill(q, kn, vn, kp, vp, bt, btw, 8, 8,
                                   layer=0)
    ox, kx, vx = ops.paged_prefill_xla(q, kn, vn, kp, vp, bt, btw, 8, 8,
                                       layer=0)
    P = kp.shape[1] - 1          # dump-page garbage differs by design
    assert bool(jnp.all(ok == ox))
    assert bool(jnp.all(kk[:, :P] == kx[:, :P]))
    assert bool(jnp.all(vk[:, :P] == vx[:, :P]))


def test_masked_write_row_preserves_shared_pages():
    """A shared (writable-row -1) chunk page must NOT be rewritten: its
    writes land on the dump page, attention still sees the recomputed
    in-chunk K/V, and untouched pool pages stay bit-identical."""
    rng = np.random.default_rng(3)
    q, kn, vn, kp, vp, bt, btw = _rand_case(rng, start=8, length=8,
                                            mask_first_chunk_page=True)
    ok, kk, vk = ops.paged_prefill(q, kn, vn, kp, vp, bt, btw, 8, 8,
                                   layer=0)
    ox, kx, vx = ops.paged_prefill_xla(q, kn, vn, kp, vp, bt, btw, 8, 8,
                                       layer=0)
    masked_page = int(np.asarray(bt)[8 // 4])
    assert bool(jnp.all(kk[:, masked_page] == kp[:, masked_page])), \
        "masked (shared) page content must survive the fused scatter"
    assert bool(jnp.all(ok == ox))
    assert bool(jnp.all(kk[:, :-1] == kx[:, :-1]))


def test_autotune_prefill_choice():
    from repro.kernels import autotune
    ch = autotune.choose_prefill_blocks(64, 4, 2, 128, 16)
    assert ch is not None and 4 % ch.bh == 0
    assert autotune.choose_prefill_blocks(60, 4, 2, 128, 16) is None, \
        "chunk must tile into pages"
    assert autotune.paged_prefill_read_bytes(32, 16, 16, 2, 16) == \
        (2 + 1) * 16 * autotune.paged_kv_bytes_per_token(2, 16)


# (chunk, hkv, rep) -> the chooser's bh at dh 128, page 16: Qwen2.5-3B's
# chunk keeps all its 2 kv heads a grid step; Qwen3-4B's 8 kv heads fit
# a 128-token chunk only, since bh 4, 2 and 1 are not tiles Mosaic
# lowers (a K/V page block's second-minor dim) and all 8 heads at 256
# tokens need about 27.5 MiB of scoped VMEM; 16 kv heads split into 8s
@pytest.mark.parametrize("c,hkv,rep,bh", [
    (256, 2, 8, 2),              # qwen2.5-3b.batch_decode
    (256, 8, 4, None),           # qwen3-4b at a 256-token chunk
    (128, 8, 4, 8),              # qwen3-4b.long_decode
    (64, 16, 4, 16),
    (128, 16, 4, 8),
    (512, 4, 8, None),
])
def test_prefill_chooser_picks_tiles_mosaic_lowers(c, hkv, rep, bh):
    from repro.kernels import autotune
    ch = autotune.choose_prefill_blocks(c, hkv, rep, 128, 16)
    assert (ch and ch.bh) == bh
    if ch is not None:
        assert ch.vmem_bytes <= autotune.VMEM_LIMIT
    # the lane-padded (m, l) scratch and double-buffered q/out blocks
    # are counted: 8 heads x 4 x 256 rows need more than the limit
    assert autotune.paged_prefill_vmem_bytes(8, 4, 128, 16, 256) > \
        autotune.VMEM_LIMIT


def test_prefill_vmem_count_pads_lanes_and_sublanes():
    """Mosaic lays a block's last dim on 128 lanes and a K/V page tile's
    kv heads on 8 sublanes: a head dim of 64 costs what 128 does, and one
    kv head a grid step what 8 do at the same query rows."""
    from repro.kernels import autotune
    vmem = autotune.paged_prefill_vmem_bytes
    assert vmem(8, 2, 64, 16, 128) == vmem(8, 2, 128, 16, 128)
    assert vmem(1, 8, 128, 16, 64) == vmem(8, 1, 128, 16, 64)
    assert vmem(16, 1, 128, 16, 64) > vmem(8, 2, 128, 16, 64)


def test_untileable_chunk_takes_the_xla_fallback(monkeypatch):
    """At 8 kv heads of 128 and a 256-token chunk no kernel tile fits:
    the engine's chunk step takes the XLA dense-gather path (the kernel
    is never called) and still serves, and records no prefill tile."""
    import dataclasses

    from repro.configs.base import Stage
    cfg = dataclasses.replace(registry.get("qwen3-4b"), d_model=256,
                              d_ff=256, vocab=512,
                              stages=(Stage(("dense",), 1),))
    assert ops.paged_prefill_blocks(256, 16, 8, 4, 128) is None

    def refuse(*a, **kw):
        raise AssertionError("paged_prefill kernel called")

    monkeypatch.setattr(ops, "paged_prefill", refuse)
    params = M.init_params(cfg, PAR, jax.random.PRNGKey(0))
    eng = Engine(cfg, PAR, params, n_slots=2, max_seq=512, paged=True,
                 page_size=16, chunked_prefill=True, prefill_chunk=256)
    assert eng.metrics.prefill_bh is None
    assert eng.metrics.attn_ppcb is not None
    req = eng.submit(np.arange(1, 300, dtype=np.int32), max_new=2)
    while not req.done:
        eng.tick()
    assert eng.metrics.prefill_chunks == 2 and len(req.out_tokens) == 2


# ---------------------------------------------------------------------------
# Model-level: chunked == whole-prompt prefill (f32 logits)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("plen", [5, 16, 23, 48, 61])
def test_chunked_matches_whole_prompt_logits(subject, plen):
    """Whole-prompt dense prefill vs the chunked paged path on an
    all-f32 model (bf16 params would make the two paths differ at the
    storage dtype, not in the chunking math)."""
    cfg, params = subject
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if isinstance(a, jax.Array) and a.dtype == jnp.bfloat16 else a,
        params)
    ps, chunk, max_seq = 8, 16, 128
    rng = np.random.default_rng(plen)
    seq = rng.integers(1, cfg.vocab, size=plen).astype(np.int32)

    # whole-prompt dense prefill -> last-token logits
    batch = {"tokens": jnp.asarray(seq[None]),
             "positions": jnp.arange(plen, dtype=jnp.int32)[None]}
    ref_logits, _ = M.prefill(cfg, PAR, params, batch, max_seq)

    # chunked paged prefill over a real block table
    pool = PagePool(32, ps)
    tables = BlockTables(pool, 1, pages_for_tokens(max_seq, ps))
    assert tables.ensure_blocks(0, pages_for_tokens(plen, ps))
    caches = M.init_paged_caches(cfg, PAR, 1, 32, ps, dtype=jnp.float32)
    from repro.models.param import materialize
    caches = materialize(caches, jax.random.PRNGKey(0))
    bt = jnp.asarray(tables.as_array()[0])
    logits = None
    for start in range(0, plen, chunk):
        length = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :length] = seq[start:start + length]
        logits, caches = M.prefill_step_paged(
            cfg, PAR, params, jnp.asarray(toks), caches, bt, bt,
            start, length, max_seq=max_seq)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits[:, 0], np.float32),
                               rtol=2e-5, atol=2e-5)
    assert int(jnp.argmax(logits)) == int(jnp.argmax(ref_logits[:, 0]))


@pytest.mark.parametrize("plen", [5, 23, 61])
def test_engine_prefill_logits_chunked_matches_whole(subject, plen):
    """Engine.prefill_logits runs the engine's own prefill program: the
    chunked engine (several chunks) and the whole-prompt engine agree on
    an all-f32 model, and the chunked scratch slot returns its pages."""
    cfg, params = subject
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if isinstance(a, jax.Array) and a.dtype == jnp.bfloat16 else a,
        params)
    seq = np.random.default_rng(plen).integers(
        1, cfg.vocab, size=plen).astype(np.int32)
    kw = dict(n_slots=2, max_seq=128, prefill_buckets=(64, 128),
              paged=True, page_size=8, cache_dtype=jnp.float32)
    whole = Engine(cfg, PAR, params, **kw).prefill_logits(seq)
    eng = Engine(cfg, PAR, params, chunked_prefill=True, prefill_chunk=16,
                 **kw)
    chunked = eng.prefill_logits(seq)
    assert whole.shape == chunked.shape == (cfg.vocab_padded,)
    np.testing.assert_allclose(chunked, whole, rtol=2e-5, atol=2e-5)
    assert eng.backend.pool.free_pages == eng.backend.pool.num_pages
    assert not eng.has_work


def test_engine_lowered_steps_shapes(subject):
    """The lowered decode / chunk-prefill steps are the engine's own
    programs at its live shapes: they compile and return (n_slots, V)
    and (1, V) logits."""
    cfg, params = subject
    eng = Engine(cfg, PAR, params, n_slots=3, max_seq=64, paged=True,
                 page_size=8, chunked_prefill=True, prefill_chunk=16)
    steps = eng.backend.lowered_steps(eng.params)
    assert set(steps) == {"decode", "prefill_chunk"}
    logits = {k: v.compile().out_info[0] for k, v in steps.items()}
    assert logits["decode"].shape == (3, cfg.vocab)
    assert logits["prefill_chunk"].shape == (1, cfg.vocab)


def test_engine_donates_the_pool(subject):
    """The chunk and decode steps take the pool donated: after a tick
    that ran only a chunk, and after one that ran only a decode step,
    the pool arrays held before the tick are deleted, and the request
    still finishes from the pool the steps returned."""
    cfg, params = subject
    eng = Engine(cfg, PAR, params, n_slots=2, max_seq=64, paged=True,
                 page_size=8, chunked_prefill=True, prefill_chunk=16)
    req = eng.submit(np.arange(1, 21, dtype=np.int32), max_new=4)
    be = eng.backend

    def tick_and_check(chunks):
        before = jax.tree.leaves(be.caches)
        calls = be.prefill_chunk_calls
        eng.tick()
        assert be.prefill_chunk_calls == calls + chunks
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in jax.tree.leaves(be.caches))

    tick_and_check(1)             # first of two chunks: no decode yet
    assert not req.out_tokens
    eng.tick()                    # last chunk, then the first decode
    tick_and_check(0)             # a decode step alone
    eng.run()
    assert len(req.out_tokens) == 4


def test_contiguous_engine_donates_its_caches(subject):
    """The contiguous backend's splice and decode step take the caches
    donated too: a tick that admits and decodes deletes the caches held
    before it."""
    cfg, params = subject
    eng = Engine(cfg, PAR, params, n_slots=2, max_seq=64)
    req = eng.submit(np.arange(1, 11, dtype=np.int32), max_new=3)
    before = jax.tree.leaves(eng.backend.caches)
    eng.tick()
    assert all(a.is_deleted() for a in before)
    eng.run()
    assert len(req.out_tokens) == 3


def test_engine_chunked_vs_whole_greedy_identity(subject):
    """Engine-level: ragged prompts, f32 pools — greedy outputs of the
    chunked engine are bit-identical to the whole-prompt engine's."""
    cfg, params = subject
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32)
               for n in (5, 17, 31, 48, 64, 97)]

    def run(**kw):
        eng = Engine(cfg, PAR, params, n_slots=3, max_seq=128,
                     prefill_buckets=(16, 64, 128), paged=True,
                     page_size=8, cache_dtype=jnp.float32, **kw)
        reqs = [eng.submit(p, max_new=8) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs], eng

    whole, _ = run()
    chunked, eng = run(chunked_prefill=True, prefill_chunk=32)
    assert whole == chunked
    snap = eng.metrics.snapshot()
    assert snap["prefill_chunks"] > 0
    assert "prefill" not in snap["phase_step_s"], \
        "chunked engine must never run the dense whole-prompt prefill"


def test_engine_chunked_quantized_greedy_identity(subject):
    cfg, params = subject
    from repro.core.pipeline import quantize_params_data_free
    from repro.core.qlinear import QuantConfig
    qp = quantize_params_data_free(params,
                                   QuantConfig(ratio=0.25, multiple=16),
                                   min_dim=32)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32)
               for n in (7, 29, 50)]

    def run(**kw):
        eng = Engine(cfg, PAR, qp, n_slots=2, max_seq=128,
                     prefill_buckets=(64, 128), paged=True, page_size=8,
                     cache_dtype=jnp.float32, **kw)
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        eng.run()
        return [r.out_tokens for r in reqs]

    assert run() == run(chunked_prefill=True, prefill_chunk=16)


# ---------------------------------------------------------------------------
# Mid-prefill preemption + resume
# ---------------------------------------------------------------------------
def test_mid_prefill_preemption_resumes_identically(subject):
    cfg, params = subject
    rng = np.random.default_rng(21)
    short = rng.integers(1, cfg.vocab, size=8).astype(np.int32)
    long = rng.integers(1, cfg.vocab, size=90).astype(np.int32)

    def make():
        return Engine(cfg, PAR, params, n_slots=2, max_seq=128,
                      paged=True, page_size=8, cache_dtype=jnp.float32,
                      chunked_prefill=True, prefill_chunk=16)

    # clean run: no preemption
    eng = make()
    a0 = eng.submit(short, max_new=12)
    b0 = eng.submit(long, max_new=6)
    eng.run()
    assert b0.preemptions == 0

    # preempted run: evict the long request BETWEEN chunks, mid-prefill
    eng = make()
    a1 = eng.submit(short, max_new=12)
    b1 = eng.submit(long, max_new=6)
    for _ in range(3):
        eng.tick()
    slot_b = next(s for s, r in eng.running() if r.rid == b1.rid)
    st = eng._prefill_state[slot_b]
    assert 0 < st["frontier"] < len(long), "victim must be mid-prefill"
    slot_a = next(s for s, r in eng.running() if r.rid == a1.rid)
    assert eng._preempt_for(slot_a)      # newest-admitted victim = b1
    assert b1.preemptions == 1
    assert slot_b not in eng._prefill_state
    eng.run()
    assert a1.done and b1.done
    assert a1.out_tokens == a0.out_tokens
    assert b1.out_tokens == b0.out_tokens, \
        "mid-prefill preemption must resume to bit-identical greedy tokens"


# ---------------------------------------------------------------------------
# Prefix compute-skipping + retention LRU
# ---------------------------------------------------------------------------
def test_fully_shared_chunks_skip_kernel_calls(subject):
    cfg, params = subject
    ps, chunk = 8, 16
    rng = np.random.default_rng(31)
    common = rng.integers(1, cfg.vocab, size=48).astype(np.int32)
    eng = Engine(cfg, PAR, params, n_slots=1, max_seq=128, paged=True,
                 page_size=ps, cache_dtype=jnp.float32,
                 chunked_prefill=True, prefill_chunk=chunk,
                 prefix_sharing=True, prefix_retain_pages=8)
    tail_a = rng.integers(1, cfg.vocab, size=6).astype(np.int32)
    ra = eng.submit(np.concatenate([common, tail_a]), max_new=4)
    eng.run()
    calls_a = eng.backend.prefill_chunk_calls
    assert calls_a == -(-54 // chunk)            # 4 chunks, no sharing yet
    # same-prefix follower: the 6 shared pages cover chunks 1-3 whole;
    # only the tail chunk may run
    tail_b = rng.integers(1, cfg.vocab, size=3).astype(np.int32)
    rb = eng.submit(np.concatenate([common, tail_b]), max_new=4)
    eng.run()
    assert ra.done and rb.done
    assert eng.backend.prefill_chunk_calls - calls_a == 1, \
        "fully prefix-shared chunks must execute zero prefill-kernel calls"
    assert eng.metrics.prefill_tokens_skipped == 48
    st = eng.prefix_stats()
    assert st["hits"] >= 1 and st["cow_copies"] == 0


def test_cohort_catches_up_mid_prefill(subject):
    """Peers admitted in the SAME tick adopt pages a faster peer
    registered chunk-by-chunk — fewer total kernel calls, identical
    greedy output."""
    cfg, params = subject
    rng = np.random.default_rng(33)
    common = rng.integers(1, cfg.vocab, size=48).astype(np.int32)
    prompts = [np.concatenate([common, rng.integers(
        1, cfg.vocab, size=5).astype(np.int32)]) for _ in range(3)]

    def run(sharing):
        eng = Engine(cfg, PAR, params, n_slots=3, max_seq=128, paged=True,
                     page_size=8, cache_dtype=jnp.float32,
                     chunked_prefill=True, prefill_chunk=16,
                     prefix_sharing=sharing)
        reqs = [eng.submit(p, max_new=5) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs], eng.backend.prefill_chunk_calls

    base, calls0 = run(False)
    shared, calls1 = run(True)
    assert base == shared, "prefix catch-up must not change greedy output"
    assert calls1 < calls0


def test_retention_survives_cohort_and_evicts_under_pressure(subject):
    cfg, params = subject
    rng = np.random.default_rng(41)
    common = rng.integers(1, cfg.vocab, size=32).astype(np.int32)
    eng = Engine(cfg, PAR, params, n_slots=2, max_seq=64, paged=True,
                 page_size=8, pool_pages=16, cache_dtype=jnp.float32,
                 chunked_prefill=True, prefill_chunk=16,
                 prefix_sharing=True, prefix_retain_pages=4)
    r1 = eng.submit(np.concatenate(
        [common, rng.integers(1, cfg.vocab, size=3).astype(np.int32)]),
        max_new=4)
    eng.run()
    assert r1.done
    st = eng.prefix_stats()
    assert st["retained"] == 4              # cap < 4 full common pages
    assert eng.backend.pool.pages_in_use == st["retained"], \
        "retained pages outlive the cohort"
    # straggler hits the retained prefix
    calls = eng.backend.prefill_chunk_calls
    r2 = eng.submit(np.concatenate(
        [common, rng.integers(1, cfg.vocab, size=2).astype(np.int32)]),
        max_new=4)
    eng.run()
    assert r2.done
    assert eng.prefix_stats()["hits"] >= 1
    assert eng.backend.prefill_chunk_calls - calls == 1
    # pressure: fresh full-pool prompts force the retention LRU to yield
    big = [rng.integers(1, cfg.vocab, size=60).astype(np.int32)
           for _ in range(3)]
    reqs = [eng.submit(p, max_new=4) for p in big]
    eng.run()
    assert all(r.done for r in reqs)
    assert eng.prefix_stats()["evictions"] > 0


def test_retention_unit_deepest_first_eviction():
    pool = PagePool(16, 4)
    pc = PrefixCache(pool, retain_pages=16)
    toks = np.arange(12, dtype=np.int32)
    pages = pool.alloc(3)
    pc.register(toks, pages)
    assert pool.refcount(pages[0]) == 2     # owner + retainer
    pool.free(pages)                        # cohort dies; retention holds
    assert all(pool.refcount(p) == 1 for p in pages)
    assert pc.match(toks) == pages          # still hits
    # eviction drops the DEEPEST chunk of the group: the prefix degrades
    # to a shorter match instead of losing its chain head (which would
    # orphan every deeper page while they stayed pinned)
    assert pc.evict_for(1) == 1
    assert pc.match(toks) == pages[:2]
    assert pc.stats().evictions == 1
    # group LRU across prefixes: a fresh, recently-touched prefix
    # survives while the cold one keeps shrinking tail-first
    toks2 = 100 + np.arange(8, dtype=np.int32)
    pages2 = pool.alloc(2)
    pc.register(toks2, pages2)
    pool.free(pages2)
    assert pc.evict_for(1) == 1
    assert pc.match(toks) == pages[:1]      # cold prefix shrank again
    assert pc.match(toks2) == pages2        # hot prefix intact


def test_retention_admission_accounting_no_double_count(subject):
    """Regression: free_pages() counts retained pages as evictable
    headroom AND the shared-page hint used to discount the same pages
    from the head's need — the attach then pinned them, the remaining
    alloc found nothing to evict, and admission crashed on 'must
    reserve prompt pages first'.  The hint must only discount matched
    pages a LIVE request still holds."""
    cfg, params = subject
    rng = np.random.default_rng(55)
    eng = Engine(cfg, PAR, params, n_slots=2, max_seq=64, paged=True,
                 page_size=4, pool_pages=8, cache_dtype=jnp.float32,
                 chunked_prefill=True, prefill_chunk=8,
                 prefix_sharing=True, prefix_retain_pages=8)
    common = rng.integers(1, cfg.vocab, size=16).astype(np.int32)
    a = eng.submit(common, max_new=2)
    eng.run()
    assert a.done and eng.prefix_stats()["retained"] == 4
    # B occupies the 4 free pages and keeps decoding (its growth also
    # exercises pressure eviction against the retained prefix)
    b = eng.submit(rng.integers(1, cfg.vocab, size=13).astype(np.int32),
                   max_new=12)
    # C matches A's retained prefix but needs MORE pages than the pool
    # can supply once the attach pins them — it must wait, not crash
    c = eng.submit(np.concatenate(
        [common, rng.integers(1, cfg.vocab, size=8).astype(np.int32)]),
        max_new=2)
    eng.run()
    assert b.done and c.done


# ---------------------------------------------------------------------------
# Engine validation + scheduler hook
# ---------------------------------------------------------------------------
def test_chunked_engine_validation(subject):
    cfg, params = subject
    with pytest.raises(ValueError, match="requires paged"):
        Engine(cfg, PAR, params, chunked_prefill=True)
    with pytest.raises(ValueError, match="multiple of page_size"):
        Engine(cfg, PAR, params, paged=True, page_size=16,
               chunked_prefill=True, prefill_chunk=24)
    xcfg = registry.get("xlstm-1.3b").reduced()
    xparams = M.init_params(xcfg, PAR, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="attention-only"):
        Engine(xcfg, PAR, xparams, paged=True, chunked_prefill=True)


def test_scheduler_next_prefill_slot_class_order():
    class R:
        def __init__(self, rid, priority, admit_seq):
            self.rid, self.priority, self.admit_seq = rid, priority, admit_seq
    s = Scheduler()
    pre = {0: R(1, "batch", 1), 1: R(2, "realtime", 3),
           2: R(3, "standard", 2)}
    assert s.next_prefill_slot(pre) == 1         # highest class first
    del pre[1]
    assert s.next_prefill_slot(pre) == 2
    pre[3] = R(4, "standard", 1)
    assert s.next_prefill_slot(pre) == 3         # FCFS within class
    assert s.next_prefill_slot({}) is None
