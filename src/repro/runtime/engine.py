"""Serving engine: event-emitting tick loop over contiguous or paged KV.

Continuous-batching slot model: a fixed decode batch of `n_slots`; each
slot holds one request's cache and an independent position counter (the
decode step takes a (B,) position vector, so ragged progress is native).
New requests prefill (jitted, padded to `prefill_buckets`) and splice
their cache in; finished slots free immediately.

With ``chunked_prefill=True`` (paged, attention-only archs) the
whole-prompt pass disappears entirely: admission reserves the prompt's
pages and sets a *chunk frontier*, and each tick advances at most
``prefill_chunk`` tokens of prefill — one fused scatter+attend kernel
call (`repro.kernels.paged_prefill`) that writes the chunk's K/V
straight into the slot's pool pages and attends context + in-chunk
causal prefix — before the batched decode step runs over the
*decoding* slots (mid-prefill slots are masked out of the decode:
table rows -1, context lens 0).  A long prompt therefore costs every
concurrent decode at most one chunk of latency per tick instead of a
whole-prompt stall; preemption can land between chunks (the victim
re-prefills its context seq, greedy-identical); and prefix-cache hits
skip fully-shared chunks' kernel calls outright — including
mid-prefill catch-up adoption when a same-prefix cohort peer registers
pages first, and post-cohort hits through the retention LRU
(``prefix_retain_pages``).

The engine is a **reentrant tick loop**, not a batch-and-drain box:
:meth:`Engine.tick` advances every active slot by one decode step and
publishes typed events (:mod:`repro.runtime.events`) the moment they
happen — ``TokenEvent`` per sampled token, ``FinishEvent`` /
``PreemptEvent`` / ``ExpireEvent`` on lifecycle edges — through a
subscriber/queue bus (``Engine.subscribe`` / ``Engine.event_queue``).
:meth:`Engine.run` is now just a convenience driver over ``tick()``;
callers that stream (``launch/serve.py --stream``) drive ticks
themselves and drain the queue in between.  :meth:`Engine.cancel`
aborts a request wherever it is — queued requests leave the scheduler,
in-flight requests give their slot and pages back **in the same tick**
(the ``FinishEvent(reason="cancelled")`` carries the freed page count
as the receipt).  Each tick names its host work in profiler spans
(``engine.tick`` and the phases inside it, :mod:`repro.runtime.tracing`),
recorded whenever a ``jax.profiler`` session runs, and folds the XLA
compiles and garbage collections that happened inside it into the
metrics.

Two cache backends behind one interface:

  * **contiguous** (legacy): each slot owns a `max_seq`-sized ring-buffer
    region — memory is `n_slots × max_seq` regardless of actual lengths.
  * **paged**: all slots share one pool of fixed-size KV pages addressed
    through per-request block tables (`repro.runtime.paged_cache`), with
    the gather/scatter over page indices inside the jitted decode step.
    Memory scales with resident tokens; when the pool runs dry the
    scheduler preempts a victim and re-queues it.  With
    ``prefix_sharing=True`` the backend keeps a hash-keyed
    :class:`~repro.runtime.paged_cache.PrefixCache`: requests whose
    prompts share page-aligned prefix chunks attach to the existing
    pool pages copy-on-write (refcounted fork) instead of allocating
    and re-writing them — the common pages of N same-prompt requests
    exist once.

Admission/preemption policy lives in `repro.runtime.scheduler` (weighted
priority classes with an aging term, deadlines, class-aware victim
selection); serving counters in `repro.runtime.metrics`.  Weights may be
fp (bf16) or PTQ1.61-quantized (QLinear pytrees) — the same jitted step
serves both, which is the point of the paper-integrated runtime: sub-2-bit
weights cut the decode weight-traffic term ~10× (EXPERIMENTS.md
§Roofline), which is exactly why the KV cache, not the weights, becomes
the serving bottleneck.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.paged_attention import attn_block_counts
from repro.models import model as M
from repro.models import transformer as T
from repro.models.common import Parallel
from repro.models.param import materialize
from repro.runtime import tracing
from repro.runtime.events import (EventBus, ExpireEvent, FinishEvent,
                                  PreemptEvent, TokenEvent)
from repro.runtime.metrics import EngineMetrics
from repro.runtime.paged_cache import (BlockTables, PagePool, PrefixCache,
                                       pages_for_tokens)
from repro.runtime.scheduler import DEFAULT_CLASS, Scheduler
from repro.runtime.tracing import span

Tree = Any


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new: int = 32
    temperature: float = 0.0
    priority: str = DEFAULT_CLASS
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    expired: bool = False               # deadline passed while queued
    cancelled: bool = False             # aborted via Engine.cancel
    preemptions: int = 0
    deadline_t: Optional[float] = None  # absolute (scheduler clock)
    admit_seq: int = 0                  # set by the scheduler on admit
    prompt_cap: Optional[int] = None    # engine's max prefill length

    def n_prompt_tokens(self) -> int:
        """Tokens a (re-)prefill must cover: the prompt plus any tokens
        already generated before a preemption (minus the pending one).

        ``prompt_cap`` (the engine's decode ceiling, max_seq-1) is a
        safety bound for admission page accounting; in practice it never
        binds — fresh prompts are truncated below it at submit and a
        resume seq stops at max_seq-2 because generation ends at
        position max_seq-1 (`_start` asserts this)."""
        n = len(self.prompt) + max(0, len(self.out_tokens) - 1)
        return min(n, self.prompt_cap) if self.prompt_cap is not None else n


# ---------------------------------------------------------------------------
# Cache backends
# ---------------------------------------------------------------------------
# Every step that takes the KV caches and returns them new is jitted with
# the caches donated.  A backend stores each result back over the only
# reference it holds, so the program updates the pool in place; without
# donation XLA copies the whole pool into a fresh output buffer at every
# call and device memory holds it twice.
def paged_steps(cfg: ArchConfig, par: Parallel, *, max_seq: int,
                use_kernel: bool = True):
    """The paged decode step (pool: argument 3) and chunk-prefill step
    (pool: argument 2), jitted as the paged backend serves them.  The
    chunk step takes start/length as traced scalars, so every chunk of
    every prompt hits the ONE compiled (1, prefill_chunk) shape — no
    bucket ladder."""
    decode = jax.jit(functools.partial(
        M.decode_step_paged, cfg, par, max_seq=max_seq,
        use_kernel=use_kernel), donate_argnums=3)
    chunk = jax.jit(functools.partial(
        M.prefill_step_paged, cfg, par, max_seq=max_seq,
        use_kernel=use_kernel), donate_argnums=2)
    return decode, chunk


class _ContiguousBackend:
    """Legacy per-slot ring-buffer caches: (B, max_seq) regions."""

    name = "contiguous"

    def __init__(self, eng: "Engine"):
        self.eng = eng
        cache_decl = M.init_caches(eng.cfg, eng.par, eng.n_slots, eng.max_seq)
        self.caches = materialize(cache_decl, jax.random.PRNGKey(0))
        self._decode = jax.jit(functools.partial(
            M.decode_step, eng.cfg, eng.par, max_seq=eng.max_seq),
            donate_argnums=3)
        self._splice = jax.jit(functools.partial(M.splice_prefill, eng.cfg),
                               donate_argnums=0)

    def free_pages(self) -> Optional[int]:
        return None                      # slots pre-reserve max_seq

    def page_util(self) -> Optional[float]:
        return None

    def splice(self, slot: int, cache1: Tree, n_tokens: int,
               seq: Optional[np.ndarray] = None,
               shared: Optional[list] = None) -> None:
        self.caches = self._splice(self.caches, cache1,
                                   jnp.int32(slot))

    def ensure_capacity(self, slot: int, pos: int) -> bool:
        return True                      # region covers max_seq by design

    def release(self, slot: int) -> int:
        return 0                         # region is reused on next splice

    def decode(self, params, toks, pos):
        logits, self.caches = self._decode(params, toks, pos, self.caches)
        return logits


class _PagedBackend:
    """Shared page pool + per-slot block tables (see paged_cache.py)."""

    name = "paged"

    def __init__(self, eng: "Engine", page_size: int, pool_pages: int,
                 use_kernel: bool = True, prefix_sharing: bool = False,
                 cache_dtype=None, prefix_retain_pages: int = 0):
        self.eng = eng
        max_blocks = pages_for_tokens(eng.max_seq, page_size)
        self.pool = PagePool(pool_pages, page_size)
        self.tables = BlockTables(self.pool, eng.n_slots, max_blocks)
        self.prefix = (PrefixCache(self.pool,
                                   retain_pages=prefix_retain_pages)
                       if prefix_sharing else None)
        # admission-hint memo: rid -> matched pages, valid for one
        # (registry writes, pool frees) version — a blocked head is
        # hashed once, not once per tick, and splice reuses the pages
        self._hint_cache: Dict[int, list] = {}
        self._hint_ver = None
        cache_decl = M.init_paged_caches(eng.cfg, eng.par, eng.n_slots,
                                         pool_pages, page_size,
                                         dtype=cache_dtype)
        self.caches = materialize(cache_decl, jax.random.PRNGKey(0))
        self._decode, self._chunk_step = paged_steps(
            eng.cfg, eng.par, max_seq=eng.max_seq, use_kernel=use_kernel)
        self._splice = jax.jit(functools.partial(
            M.splice_prefill_paged, eng.cfg), donate_argnums=0)
        self._copy = jax.jit(functools.partial(M.copy_pages, eng.cfg),
                             donate_argnums=0)
        self.prefill_chunk_calls = 0
        self.prefill_kv_read_bytes = 0
        # (ppcb, nblk, window) of the decode kernel's calls, for the
        # live compute-block counter; None where decode takes the XLA path
        self._attn_blocks = (self._kernel_blocks(max_blocks)
                             if use_kernel else None)
        # the tiles the autotuner chose for the served shapes, once
        m = eng.metrics
        m.attn_ppcb = self._attn_blocks[0] if self._attn_blocks else None
        m.prefill_bh = (self._prefill_bh()
                        if use_kernel and eng.chunked_prefill else None)

    @property
    def page_size(self) -> int:
        return self.pool.page_size

    def _kernel_blocks(self, nblk: int):
        from repro.kernels import ops
        cfg = self.eng.cfg
        kinds = {k for s in cfg.stages for k in s.pattern} & set(T.ATTN_KINDS)
        if not kinds:
            return None
        hkv = self.eng.par.kv_heads_run(cfg.n_kv_heads, cfg.n_heads)
        choice = ops.paged_attention_blocks(
            self.page_size, hkv, cfg.n_heads // hkv, cfg.head_dim_, nblk)
        if choice is None:
            return None
        # layers of one config share their window; a mix counts none
        windows = {T._kind_window(cfg, k, self.eng.max_seq) for k in kinds}
        return choice.ppcb, nblk, windows.pop() if len(windows) == 1 else None

    def _prefill_bh(self) -> Optional[int]:
        """kv heads per grid step of the chunk kernel, or None where the
        chunk step takes the XLA path."""
        from repro.kernels import ops
        cfg = self.eng.cfg
        hkv = self.eng.par.kv_heads_run(cfg.n_kv_heads, cfg.n_heads)
        choice = ops.paged_prefill_blocks(
            self.eng.prefill_chunk, self.page_size, hkv,
            cfg.n_heads // hkv, cfg.head_dim_)
        return None if choice is None else choice.bh

    def free_pages(self) -> Optional[int]:
        """Admission headroom: the free list plus whatever the prefix
        retention LRU could evict on demand (the pool's pressure hook
        reclaims those inside ``alloc`` when the free list falls
        short)."""
        free = self.pool.free_pages
        if self.prefix is not None and self.prefix.retain_pages > 0:
            free += self.prefix.evictable()
        return free

    def page_util(self) -> Optional[float]:
        return self.pool.pages_in_use / self.pool.num_pages

    def shared_page_hint(self, rid: int, seq: np.ndarray) -> int:
        """Pages a prefix-cache attach would effectively save for
        ``seq`` right now (admission accounting: the scheduler subtracts
        them from the head's page need).  Registry state cannot change
        between this hint and the attach in ``splice`` — both happen
        inside the same host-side admission pass — so the matched pages
        are memoized by rid and the splice reuses them instead of
        re-hashing the prompt.  The memo survives across ticks until
        any registry write or page free (either can only change match
        results when it happens), so a queued head blocked on free
        pages does not pay O(prompt) hashing per tick.

        With retention on, matched pages whose ONLY holder is the
        retention LRU must NOT be discounted: :meth:`free_pages`
        already counts them as evictable headroom, and the attach pins
        them (refcount 2) so they stop being evictable the moment the
        request starts — discounting them too would double-count and
        admit a head whose remaining pages cannot actually be
        allocated.  Refcounts are re-read on every call (they can move
        without a free event)."""
        if self.prefix is None:
            return 0
        ver = (self.prefix.writes, self.pool.free_events)
        if ver != self._hint_ver:
            self._hint_cache.clear()
            self._hint_ver = ver
        if rid not in self._hint_cache:
            self._hint_cache[rid] = self.prefix.match(seq)
        pages = self._hint_cache[rid]
        if self.prefix.retain_pages > 0:
            return len(pages) - sum(1 for p in pages
                                    if self.pool.refcount(p) == 1)
        return len(pages)

    def _apply_cow(self) -> None:
        pairs = self.tables.drain_copies()
        if pairs:
            src = jnp.asarray([s for s, _ in pairs], jnp.int32)
            dst = jnp.asarray([d for _, d in pairs], jnp.int32)
            self.caches = self._copy(self.caches, src, dst)

    def splice(self, slot: int, cache1: Tree, n_tokens: int,
               seq: Optional[np.ndarray] = None,
               shared: Optional[list] = None) -> None:
        if self.prefix is not None and seq is not None:
            if shared is None:      # no admission hint: match here
                shared = self.prefix.match(seq)
            self.prefix.count_attach(len(shared))
            if shared:
                self.tables.fork(slot, shared)
        ok = self.tables.ensure_blocks(
            slot, pages_for_tokens(n_tokens, self.page_size))
        assert ok, "admission must reserve prompt pages first"
        self._apply_cow()
        # shared (forked) blocks are masked to -1: the device scatter
        # drops those writes — the pages already hold these tokens' KV
        bt_row = jnp.asarray(self.tables.writable_row(slot))
        self.caches = self._splice(self.caches, cache1, jnp.int32(slot),
                                   bt_row)
        if self.prefix is not None and seq is not None:
            self.prefix.register(seq, self.tables.owned(slot))

    def ensure_capacity(self, slot: int, pos: int) -> bool:
        return self.tables.ensure_for_position(slot, pos)

    def release(self, slot: int) -> int:
        return self.tables.release(slot)

    def decode(self, params, toks, pos, active=None):
        """One batched decode step.  ``active`` (np bool (n_slots,) or
        None) masks slots that must not decode this tick — mid-prefill
        slots under chunked prefill: their block-table rows go to -1
        (the device write is dropped) and their context lens to 0 (the
        kernel zero-fills), all in host numpy so the jitted signature
        never changes."""
        self._apply_cow()
        bt = self.tables.as_array()
        lens = self.tables.context_lens()
        if active is not None:
            bt = np.where(active[:, None], bt, -1)
            lens = np.where(active, lens, 0)
        if self._attn_blocks is not None:
            ppcb, nblk, window = self._attn_blocks
            self.eng.metrics.on_attn_blocks(*attn_block_counts(
                lens, self.page_size, ppcb, nblk, window))
        logits, self.caches = self._decode(params, toks, pos, self.caches,
                                           jnp.asarray(bt),
                                           jnp.asarray(lens))
        return logits

    def lowered_steps(self, params) -> Dict[str, Any]:
        """The jitted decode and chunk-prefill steps lowered at the
        argument shapes the tick loop passes (``jax.stages.Lowered``),
        so a caller can compile them and see which kernels they hold."""
        n = self.eng.n_slots
        slots = jnp.zeros((n,), jnp.int32)
        row = jnp.asarray(self.tables.as_array()[0])
        return {
            "decode": self._decode.lower(
                params, slots, slots, self.caches,
                jnp.asarray(self.tables.as_array()),
                jnp.asarray(self.tables.context_lens())),
            "prefill_chunk": self._chunk_step.lower(
                params, jnp.zeros((1, self.eng.prefill_chunk), jnp.int32),
                self.caches, row, row, jnp.int32(0), jnp.int32(1)),
        }

    def prefill_chunk(self, params, toks, slot: int, start: int,
                      length: int):
        """Advance ``slot``'s prefill by one chunk: fused scatter+attend
        straight into the slot's pool pages (kernel or XLA fallback —
        see models.layers.attention_prefill_paged).  Returns the chunk's
        last-live-row logits (1, V)."""
        self._apply_cow()
        bt_read = jnp.asarray(self.tables.as_array()[slot])
        bt_write = jnp.asarray(self.tables.writable_row(slot))
        logits, self.caches = self._chunk_step(
            params, toks, self.caches, bt_read, bt_write,
            jnp.int32(start), jnp.int32(length))
        self.prefill_chunk_calls += 1
        from repro.kernels import autotune
        eng = self.eng
        hkv = eng.par.kv_heads_run(eng.cfg.n_kv_heads, eng.cfg.n_heads)
        self.prefill_kv_read_bytes += eng.cfg.n_layers * \
            autotune.paged_prefill_read_bytes(
                start, length, self.page_size, hkv, eng.cfg.head_dim_)
        return logits


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------
class Engine:
    def __init__(self, cfg: ArchConfig, par: Parallel, params: Tree,
                 *, n_slots: int = 4, max_seq: int = 512,
                 prefill_buckets=(64, 256), seed: int = 0,
                 paged: bool = False, page_size: int = 16,
                 pool_pages: Optional[int] = None,
                 paged_kernel: bool = True,
                 prefix_sharing: bool = False,
                 prefix_retain_pages: int = 0,
                 chunked_prefill: bool = False,
                 prefill_chunk: int = 64,
                 prefill_chunks_per_tick: int = 1,
                 cache_dtype=None,
                 scheduler: Optional[Scheduler] = None,
                 metrics: Optional[EngineMetrics] = None,
                 fuse_projections: bool = False,
                 time_phases: bool = True):
        if fuse_projections:
            # N-fuse QKV / gate+up so each block's decode step issues 2
            # projection matmuls instead of 5 (exact for fp weights;
            # QLinear leaves stay unfused here — quantize with
            # quantize_params_data_free(fuse=True) for fused packed
            # layouts).
            params = T.fuse_params_for_decode(params)
        self.cfg, self.par, self.params = cfg, par, params
        self.n_slots, self.max_seq = n_slots, max_seq
        self.buckets = tuple(sorted(b for b in prefill_buckets
                                    if b <= max_seq)) or (max_seq,)
        if chunked_prefill:
            if not paged:
                raise ValueError("chunked_prefill requires paged=True "
                                 "(chunks scatter into pool pages)")
            kinds = {k for s in cfg.stages for k in s.pattern}
            if not kinds <= set(T.ATTN_KINDS):
                raise ValueError(
                    f"chunked_prefill supports attention-only stages, "
                    f"got kinds {sorted(kinds)} — recurrent cells carry "
                    f"sequential state across chunks; serve this arch "
                    f"with the whole-prompt path")
            if prefill_chunk <= 0 or prefill_chunk % page_size:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a positive "
                    f"multiple of page_size={page_size} (chunks must "
                    f"tile into pages)")
            if prefill_chunks_per_tick <= 0:
                raise ValueError("prefill_chunks_per_tick must be >= 1")
        self.chunked_prefill = chunked_prefill
        self.prefill_chunk = prefill_chunk
        self.prefill_chunks_per_tick = prefill_chunks_per_tick
        # a prefill of max_seq tokens would put the first decode write at
        # position max_seq (past every cache layout) — cap prompts one
        # short.  Chunked prefill has no bucket ladder (every chunk is
        # the same compiled shape), so only the decode ceiling caps it.
        self.max_prompt = (max_seq - 1 if chunked_prefill
                           else min(self.buckets[-1], max_seq - 1))
        self.key = jax.random.PRNGKey(seed)
        self.scheduler = scheduler or Scheduler()
        self.metrics = metrics or EngineMetrics()
        self.events = EventBus()
        tracing.install()
        # runtime counters at the last look (tick start, before tokens)
        self._seen = tracing.counters()

        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.pos = np.zeros((n_slots,), np.int32)
        self.cur_tok = np.zeros((n_slots,), np.int32)
        self.temps = np.zeros((n_slots,), np.float32)

        if paged:
            if page_size <= 0:
                raise ValueError(f"page_size must be positive, got {page_size}")
            if pool_pages is None:
                pool_pages = n_slots * pages_for_tokens(max_seq, page_size)
            # paged_kernel: paged decode attention through the Pallas
            # flash-decode kernel on feasible shapes (default); False
            # pins the XLA-gather reference path (oracle / debugging)
            self.backend = _PagedBackend(
                self, page_size, pool_pages,
                use_kernel=paged_kernel,
                prefix_sharing=prefix_sharing,
                cache_dtype=cache_dtype,
                prefix_retain_pages=prefix_retain_pages)
        else:
            if prefix_sharing:
                raise ValueError("prefix_sharing requires paged=True "
                                 "(sharing lives in the page allocator)")
            self.backend = _ContiguousBackend(self)
        if prefix_retain_pages and not prefix_sharing:
            raise ValueError("prefix_retain_pages requires "
                             "prefix_sharing=True (retention extends the "
                             "prefix cache's hit window)")
        # chunked prefill: slot -> in-progress prefill frontier state
        # ({"seq", "frontier", "resumed"}); a slot present here holds a
        # request but must not decode yet
        self._prefill_state: Dict[int, Dict[str, Any]] = {}

        self._prefill = jax.jit(functools.partial(
            M.prefill, cfg, par, max_seq=max_seq))
        self._sample = jax.jit(_sample_batched)
        self._rid = 0
        self._requests: Dict[int, Request] = {}
        self._tick_no = 0
        self._in_tick = False
        self._pending_cancels: List[int] = []
        # per-phase timing: each jitted shape's FIRST call includes the
        # XLA compile and is recorded under "<phase>_compile" so the
        # "prefill"/"decode" series are pure steady-state step times.
        # ``time_phases=False`` drops the block_until_ready sync on the
        # decode hot path entirely (on an accelerator it costs one extra
        # host-device round trip per generated token).
        self.time_phases = time_phases
        self._warm_shapes: set = set()

    def _timed(self, phase: str, shape_key, fn):
        """Run fn() and record its blocked wall time under ``phase`` (or
        ``phase_compile`` for the first call at ``shape_key``)."""
        if not self.time_phases:
            return fn()
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if (phase, shape_key) in self._warm_shapes:
            self.metrics.on_phase_time(phase, dt)
        else:
            self._warm_shapes.add((phase, shape_key))
            self.metrics.on_phase_time(phase + "_compile", dt)
            # compile wall time must not masquerade as an inter-token
            # gap in the TBT series (it already shows up in TTFT)
            self.metrics.on_stall()
        return out

    # -- event API ------------------------------------------------------
    def subscribe(self, cb):
        """Register a callback for every engine event.  Callbacks run
        inside ``tick()``; ``Engine.cancel`` called from one is deferred
        to the end of the current tick (still the same tick)."""
        return self.events.subscribe(cb)

    def event_queue(self, maxlen: Optional[int] = None):
        """A drainable event queue (collections.deque) — the streaming
        consumer's API: drain with popleft() between ticks."""
        return self.events.queue(maxlen)

    def _emit(self, ev) -> None:
        self.events.publish(ev)

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int = 32,
               temperature: float = 0.0,
               deadline_s: Optional[float] = None,
               priority: str = DEFAULT_CLASS) -> Request:
        prompt = np.asarray(prompt, np.int32)
        # prompts longer than the largest prefill bucket are left-truncated
        # (keep the most recent tokens — standard serving behavior)
        if len(prompt) > self.max_prompt:
            prompt = prompt[-self.max_prompt:]
        if not self.scheduler.has_class(priority):
            raise ValueError(f"unknown priority class {priority!r}")
        self._rid += 1
        deadline_t = (self.scheduler.clock() + deadline_s
                      if deadline_s is not None else None)
        # page-need cap for admission: resumes keep full context up to
        # the decode ceiling (max_seq-1), not the fresh-prompt bucket cap
        r = Request(self._rid, prompt, max_new, temperature,
                    priority=priority, deadline_t=deadline_t,
                    prompt_cap=self.max_seq - 1)
        if max_new <= 0:                     # degenerate: nothing to do
            r.done = True
            self.metrics.on_submit(r.rid, priority)
            self.metrics.on_finish(r.rid)
            self._emit(FinishEvent(r.rid, "empty", 0, 0, self._tick_no))
            return r
        if isinstance(self.backend, _PagedBackend):
            # max_new >= 1 here (degenerate requests returned above), so
            # this bound covers admission's prompt+first-decode-page need
            need = pages_for_tokens(
                min(len(prompt) + max_new, self.max_seq),
                self.backend.page_size)
            if need > self.backend.pool.num_pages:
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{self.backend.pool.num_pages}; grow --pool-pages")
        # rid -> request, for cancel(); registered only once the request
        # is truly accepted, and dropped at every terminal transition
        # (finish/expire/cancel) so a long-running tick loop does not
        # retain every request ever served
        self._requests[r.rid] = r
        self.scheduler.enqueue(r)
        self.metrics.on_submit(r.rid, priority)
        return r

    def _bucket(self, s: int) -> int:
        for b in self.buckets:
            if s <= b:
                return b
        # implicit top bucket: fresh prompts are truncated to max_prompt
        # ≤ buckets[-1] before this, so only preemption resumes land here
        # (their seq can reach max_seq-2 and must keep full context/
        # positions — one extra prefill compile, no truncation)
        return self.max_seq

    def _prefill_batch(self, seq: np.ndarray) -> Dict[str, jax.Array]:
        """Whole-prompt prefill input: ``seq`` left-padded to its bucket.
        Pad positions are -1: masked out of attention and never written
        into KV storage (ring p=-1 / paged scatter drop)."""
        s = len(seq)
        b = self._bucket(s)
        toks = np.full((1, b), 0, np.int32)
        toks[0, -s:] = seq
        idx = np.arange(b, dtype=np.int32)
        positions = np.where(idx >= b - s, idx - (b - s), -1)[None]
        return {"tokens": jnp.asarray(toks),
                "positions": jnp.asarray(positions)}

    def _chunk_tokens(self, seq: np.ndarray, start: int) -> jax.Array:
        """One prefill chunk's (1, prefill_chunk) tokens from ``start``,
        zero-padded past the prompt end."""
        c = self.prefill_chunk
        length = min(c, len(seq) - start)
        toks = np.zeros((1, c), np.int32)
        toks[0, :length] = seq[start:start + length]
        return jnp.asarray(toks)

    def prefill_logits(self, prompt: np.ndarray) -> np.ndarray:
        """Last-position logits (V,) f32 of ``prompt`` through this
        engine's own prefill program — chunk by chunk in a free slot
        under chunked prefill, else the whole-prompt bucket — without
        admitting a request.  For comparing one weight or kernel path
        against another; the slot's pages are released again."""
        seq = np.asarray(prompt, np.int32)
        if not self.chunked_prefill:
            logits, _ = self._prefill(self.params, self._prefill_batch(seq))
            return np.asarray(logits[0, -1], np.float32)
        be = self.backend
        slot = self.slot_req.index(None)
        if not be.tables.ensure_blocks(
                slot, pages_for_tokens(len(seq), be.page_size)):
            raise RuntimeError("no free pages for the prompt")
        try:
            for start in range(0, len(seq), self.prefill_chunk):
                logits = be.prefill_chunk(
                    self.params, self._chunk_tokens(seq, start), slot,
                    start, min(self.prefill_chunk, len(seq) - start))
            # read back BEFORE the release rewrites the block-table row:
            # on CPU the in-flight step may alias that numpy row
            return np.asarray(logits[0], np.float32)
        finally:
            be.release(slot)

    def _context_seq(self, r: Request) -> np.ndarray:
        """The token sequence a (re-)prefill of ``r`` must cover — the
        prompt, plus for preemption resumes the already-generated tokens
        minus the pending one (re-fed as the next decode input).  Also
        what the prefix cache matches/registers against."""
        if r.out_tokens:
            return np.concatenate([r.prompt,
                                   np.asarray(r.out_tokens[:-1], np.int32)])
        return r.prompt

    # ------------------------------------------------------------------
    def _start_chunked(self, slot: int, r: Request) -> None:
        """Occupy ``slot`` for chunked prefill: attach any shared prefix
        pages, reserve the prompt's pages, and set the chunk frontier —
        the actual compute happens chunk-by-chunk in
        :meth:`_advance_prefill` across subsequent ticks.  Chunks fully
        covered by prefix-cache pages are skipped outright (zero
        prefill-kernel calls for them): the frontier starts at the
        shared-page boundary, capped one page short of the prompt end so
        the final chunk always runs (its last-row logits seed the first
        sampled token)."""
        be = self.backend
        seq = self._context_seq(r)
        assert len(seq) <= self.max_seq - 1, (len(seq), self.max_seq)
        s = len(seq)
        ps = be.page_size
        shared: list = []
        if be.prefix is not None:
            hinted = be._hint_cache.pop(r.rid, None)
            shared = hinted if hinted is not None else be.prefix.match(seq)
            be.prefix.count_attach(len(shared))
            if shared:
                be.tables.fork(slot, shared)
        ok = be.tables.ensure_blocks(slot, pages_for_tokens(s, ps))
        assert ok, "admission must reserve prompt pages first"
        skip = min(len(shared) * ps, ((s - 1) // ps) * ps)
        if skip:
            self.metrics.on_prefill_skip(skip)
        self.slot_req[slot] = r
        self.temps[slot] = r.temperature
        st: Dict[str, Any] = {"seq": seq, "frontier": skip,
                              "resumed": bool(r.out_tokens)}
        if be.prefix is not None:
            # the admission match is current as of this version — the
            # catch-up pass in _advance_prefill only re-matches when a
            # peer has registered (or the pool freed) since
            st["match_ver"] = (be.prefix.writes, be.pool.free_events)
        self._prefill_state[slot] = st

    def _advance_prefill(self, slot: int) -> int:
        """Run ONE chunk of ``slot``'s in-progress prefill; on reaching
        the prompt end, graduate the slot to decoding (sample the first
        token from the final chunk's logits, or re-feed the pending
        token on a preemption resume).  Returns the live tokens
        processed."""
        st = self._prefill_state[slot]
        r = self.slot_req[slot]
        be = self.backend
        seq = st["seq"]
        s = len(seq)
        ps = be.page_size
        # ---- mid-prefill prefix catch-up: a cohort peer may have
        # registered pages for chunks we have not computed yet (it was
        # admitted with us, ahead of us in chunk order) — adopt its
        # pages and fast-forward the frontier, skipping those chunks'
        # kernel calls outright.  Memoized on the registry/pool version
        # so an unchanged registry costs no re-hash.
        if be.prefix is not None:
            ver = (be.prefix.writes, be.pool.free_events)
            if st.get("match_ver") != ver:
                st["match_ver"] = ver
                matched = be.prefix.match(seq)
                skip_to = min(len(matched) * ps, ((s - 1) // ps) * ps)
                if skip_to > st["frontier"]:
                    for blk in range(st["frontier"] // ps, skip_to // ps):
                        be.tables.adopt_shared(slot, blk, matched[blk])
                    be.prefix.count_attach(
                        skip_to // ps - st["frontier"] // ps)
                    self.metrics.on_prefill_skip(skip_to - st["frontier"])
                    st["frontier"] = skip_to
        start = st["frontier"]
        c = self.prefill_chunk
        length = min(c, s - start)
        with span("engine.prefill_chunk", rid=r.rid, start=start,
                  length=length):
            toks = self._chunk_tokens(seq, start)
            logits = self._timed(
                "prefill_chunk", c,
                lambda: self.backend.prefill_chunk(self.params, toks, slot,
                                                   start, length))
            st["frontier"] = start + length
            self.metrics.on_prefill_chunk(length)
            # register the freshly-completed full pages as they appear
            # (so cohort peers can catch up mid-prefill, not only after
            # we finish); the chain state makes each call O(chunk)
            if be.prefix is not None:
                st["reg_state"], _ = be.prefix.register_prefix(
                    seq[:st["frontier"]], be.tables.owned(slot),
                    st.get("reg_state"))
                st["match_ver"] = (be.prefix.writes, be.pool.free_events)
        if st["frontier"] < s:
            return length
        # ---- prompt complete: graduate to decoding -------------------
        del self._prefill_state[slot]
        # the first decode page: admission accounted prompt+1, but other
        # slots may have grown into that page since — preempt on
        # shortfall (possibly evicting this very request, which then
        # resumes from the queue)
        while self.slot_req[slot] is r and \
                not be.ensure_capacity(slot, s):
            if not self._preempt_for(slot):
                raise RuntimeError(
                    "page pool exhausted with no preemption victim; "
                    "grow --pool-pages")
        if self.slot_req[slot] is not r:
            return length               # evicted ourselves: re-queued
        if st["resumed"]:
            tok = r.out_tokens[-1]
        else:
            with span("engine.sample"):
                sampled = self._sample(logits.astype(jnp.float32),
                                       self._next_key(),
                                       jnp.asarray([r.temperature],
                                                   jnp.float32))
            with span("engine.readback"):
                tok = int(np.asarray(sampled)[0])
            self._note_runtime()
            r.out_tokens.append(tok)
            self.metrics.on_token(r.rid)
            self._emit(TokenEvent(r.rid, tok, len(r.out_tokens) - 1,
                                  self._tick_no))
            if len(r.out_tokens) >= r.max_new:   # max_new=1: done here
                r.done = True
                self.metrics.on_finish(r.rid)
                self._requests.pop(r.rid, None)
                freed = self.backend.release(slot)
                self.slot_req[slot] = None
                self._emit(FinishEvent(r.rid, "max_new",
                                       len(r.out_tokens), freed,
                                       self._tick_no))
                return length
        self.pos[slot] = s
        self.cur_tok[slot] = tok
        return length

    def _start(self, slot: int, r: Request) -> None:
        """(Re-)prefill `r` and occupy `slot`.

        Fresh requests prefill their prompt and sample the first token
        from the prefill logits.  Preempted requests prefill the prompt
        plus their already-generated tokens (minus the pending one, which
        is re-fed as the next decode input) so decoding continues where
        it stopped.
        """
        if self.chunked_prefill:
            return self._start_chunked(slot, r)
        resumed = bool(r.out_tokens)
        seq = self._context_seq(r)
        # a resume seq is bounded by the decode ceiling (generation stops
        # at pos max_seq-1), so the full context always fits a bucket
        assert len(seq) <= self.max_seq - 1, (len(seq), self.max_seq)
        s = len(seq)
        b = self._bucket(s)
        batch = self._prefill_batch(seq)
        logits, cache1 = self._timed(
            "prefill", b, lambda: self._prefill(self.params, batch))
        be = self.backend
        shared = None
        if isinstance(be, _PagedBackend) and be.prefix is not None:
            # the admission pass just matched this request's prefix; no
            # free or registration can have happened since — reuse it
            shared = be._hint_cache.pop(r.rid, None)
        be.splice(slot, cache1, s, seq, shared)
        # this slot decodes at position s THIS tick, after the growth
        # pass already ran — admission reserved the page (prompt+1)
        ok = self.backend.ensure_capacity(slot, s)
        assert ok, "admission must reserve the first decode page"
        if resumed:
            tok = r.out_tokens[-1]
        else:
            tok = int(self._sample(logits[:, -1].astype(jnp.float32),
                                   self._next_key(),
                                   jnp.asarray([r.temperature],
                                               jnp.float32))[0])
            self._note_runtime()
            r.out_tokens.append(tok)
            self.metrics.on_token(r.rid)
            self._emit(TokenEvent(r.rid, tok, len(r.out_tokens) - 1,
                                  self._tick_no))
            if len(r.out_tokens) >= r.max_new:   # max_new=1: done at prefill
                r.done = True
                self.metrics.on_finish(r.rid)
                self._requests.pop(r.rid, None)
                freed = self.backend.release(slot)
                self._emit(FinishEvent(r.rid, "max_new", len(r.out_tokens),
                                       freed, self._tick_no))
                return
        self.slot_req[slot] = r
        self.pos[slot] = s
        self.cur_tok[slot] = tok
        self.temps[slot] = r.temperature

    def _admit(self) -> None:
        for r in self.scheduler.expire():
            r.expired = True
            r.done = True
            self.metrics.on_expire(r.rid)
            self._requests.pop(r.rid, None)
            self._emit(ExpireEvent(r.rid, self._tick_no))
        shared_hint = None
        if isinstance(self.backend, _PagedBackend) and \
                self.backend.prefix is not None:
            shared_hint = (lambda req:
                           self.backend.shared_page_hint(
                               req.rid, self._context_seq(req)))
        for slot in range(self.n_slots):
            # while, not if: a max_new=1 request finishes AT prefill and
            # leaves the slot free — keep admitting into it so a tick
            # with an admissible queue never reports "nothing to do"
            while self.slot_req[slot] is None:
                r = self.scheduler.next_admissible(
                    self.backend.free_pages(),
                    getattr(self.backend, "page_size", 1),
                    shared_pages=shared_hint)
                if r is None:
                    return
                self.metrics.on_admit(r.rid)
                self._start(slot, r)

    # ------------------------------------------------------------------
    def _preempt_for(self, slot: int) -> bool:
        """Free pages by evicting a victim so `slot` can grow.  Returns
        False when no victim exists (pool too small for this request)."""
        running = {s: r for s, r in enumerate(self.slot_req)
                   if r is not None}
        victim = self.scheduler.choose_victim(running, exclude=slot)
        if victim is None:
            return False
        r = self.slot_req[victim]
        r.preemptions += 1
        self.metrics.on_preempt(r.rid)
        freed = self.backend.release(victim)
        self.slot_req[victim] = None
        # a mid-prefill victim abandons its chunk frontier: the resume
        # re-prefills the same context seq from the top (or from its
        # prefix-cache hit), reproducing identical greedy tokens
        self._prefill_state.pop(victim, None)
        self._emit(PreemptEvent(r.rid, victim, freed, self._tick_no))
        # front of its class queue: the victim becomes that class's
        # longest-waiting request and is re-admitted first (no
        # preemption starvation)
        self.scheduler.enqueue(r, front=True)
        return True

    def _grow_caches(self) -> None:
        """Before a decode tick, every active slot needs storage for the
        token it is about to write at `pos`.  On pool exhaustion, preempt
        and retry; preempting may evict the very slot we were growing."""
        for slot in range(self.n_slots):
            while self.slot_req[slot] is not None and \
                    slot not in self._prefill_state and \
                    not self.backend.ensure_capacity(slot, int(self.pos[slot])):
                if not self._preempt_for(slot):
                    raise RuntimeError(
                        "page pool exhausted with no preemption victim; "
                        "grow --pool-pages")

    def _next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    # ------------------------------------------------------------------
    def cancel(self, rid: int) -> bool:
        """Abort a request.  Queued requests leave the scheduler at
        once; in-flight requests release their slot and return their
        pages to the pool immediately — within the current tick when
        called from an event callback (processing is deferred to the
        tick's end so the decode loop is never mutated under itself).
        Emits ``FinishEvent(reason="cancelled", freed_pages=...)``.
        Returns False when the rid is unknown or already finished.

        A *deferred* cancel (issued from inside a callback) returns
        True optimistically: if the request reaches its natural finish
        later in the same tick, the cancel becomes a no-op and the
        terminal event is the natural ``FinishEvent`` (``max_new`` /
        ``max_seq``), not a cancelled one — consumers must treat ANY
        FinishEvent for the rid as terminal, never wait specifically
        for ``reason="cancelled"``."""
        r = self._requests.get(rid)
        if r is None or r.done:
            return False
        if self._in_tick:
            self._pending_cancels.append(rid)
            return True
        return self._do_cancel(rid)

    def _do_cancel(self, rid: int) -> bool:
        r = self._requests.get(rid)
        if r is None or r.done:
            return False
        freed = 0
        if self.scheduler.remove(rid) is None:
            # not queued: must be in a slot
            for slot, rr in enumerate(self.slot_req):
                if rr is not None and rr.rid == rid:
                    freed = self.backend.release(slot)
                    self.slot_req[slot] = None
                    self._prefill_state.pop(slot, None)
                    break
        r.done = True
        r.cancelled = True
        self.metrics.on_cancel(rid)
        self._requests.pop(rid, None)
        self._emit(FinishEvent(rid, "cancelled", len(r.out_tokens), freed,
                               self._tick_no))
        return True

    def running(self) -> List[Tuple[int, Request]]:
        """Active (slot, request) pairs, in slot order."""
        return [(s, r) for s, r in enumerate(self.slot_req)
                if r is not None]

    @property
    def has_work(self) -> bool:
        return bool(len(self.scheduler)
                    or any(r is not None for r in self.slot_req))

    def prefix_stats(self):
        """Prefix-cache counters (None unless prefix_sharing is on):
        lookups/hits, pages attached instead of allocated (the pages
        saved by sharing), tokens covered, live entries — plus the
        tables' COW copy count."""
        be = self.backend
        if not isinstance(be, _PagedBackend) or be.prefix is None:
            return None
        st = be.prefix.stats()
        return {"lookups": st.lookups, "hits": st.hits,
                "pages_attached": st.pages_attached,
                "tokens_shared": st.tokens_shared,
                "entries": st.entries,
                "retained": st.retained,
                "evictions": st.evictions,
                "cow_copies": be.tables.cow_copies,
                "forked_pages": be.tables.forked_pages}

    # ------------------------------------------------------------------
    def tick(self) -> bool:
        """One batched decode tick across all active slots; returns
        False when nothing was running or admissible.

        Growth runs BEFORE admission: if running slots need pages, any
        preemption happens first, and only then is the freed capacity
        offered to the queue — admitting first would make the fresh
        request the newest (default victim) and throw away its entire
        prefill in the same tick."""
        self._tick_no += 1
        self._in_tick = True
        self._seen = tracing.counters()
        with span("engine.tick"):
            try:
                return self._tick_body()
            finally:
                self._in_tick = False
                pending, self._pending_cancels = self._pending_cancels, []
                for rid in pending:          # deferred from event callbacks:
                    self._do_cancel(rid)     # still "the same tick"
                self._note_runtime()

    def _note_runtime(self) -> None:
        """Fold the XLA compiles and garbage collections since the last
        look into the metrics.  Called before tokens are counted, so an
        inter-token gap that holds a compile is a stall and stays out of
        the TBT series, whether or not phases are timed."""
        compiles, collections, pauses = now = tracing.counters()
        seen_compiles, seen_collections, seen_pauses = self._seen
        self._seen = now
        if compiles != seen_compiles:
            self.metrics.on_compiles(compiles - seen_compiles)
        if collections != seen_collections:
            self.metrics.on_gc(
                [a - b for a, b in zip(collections, seen_collections)],
                [a - b for a, b in zip(pauses, seen_pauses)])

    def _tick_body(self) -> bool:
        with span("engine.grow"):
            self._grow_caches()
        with span("engine.admit"):
            self._admit()
        if all(r is None for r in self.slot_req):
            return False
        self.metrics.on_tick(
            self.scheduler.queue_depth,
            sum(r is not None for r in self.slot_req),
            self.backend.page_util())
        # ---- chunked-prefill phase: a bounded slice of prefill work
        # interleaves with (instead of stalling) the decode step below.
        # The scheduler picks which in-progress prefill advances
        # (class-weighted, FCFS within a class); the budget caps the
        # prefill compute any single tick can absorb, which is what
        # bounds the inter-token gap of concurrent decodes.
        if self._prefill_state:
            for _ in range(self.prefill_chunks_per_tick):
                if not self._prefill_state:
                    break
                sl = self.scheduler.next_prefill_slot(
                    {s: self.slot_req[s] for s in self._prefill_state})
                self._advance_prefill(sl)
        decoding = [s for s, r in enumerate(self.slot_req)
                    if r is not None and s not in self._prefill_state]
        if not decoding:
            return True                 # pure-prefill tick
        with span("engine.decode"):
            active = None
            if self._prefill_state:
                active = np.zeros((self.n_slots,), bool)
                active[decoding] = True
            toks = jnp.asarray(self.cur_tok)
            pos = jnp.asarray(self.pos)
            logits = self._timed(
                "decode", self.backend.name,
                lambda: (self.backend.decode(self.params, toks, pos, active)
                         if active is not None else
                         self.backend.decode(self.params, toks, pos)))
        # one vectorized device sample across all slots (no per-slot
        # logits round-trips through numpy); dispatch and wait apart
        with span("engine.sample"):
            sampled = self._sample(logits.astype(jnp.float32),
                                   self._next_key(), jnp.asarray(self.temps))
        with span("engine.readback"):
            next_toks = np.asarray(sampled)
        with span("engine.emit"):
            self._note_runtime()
            for slot, r in enumerate(self.slot_req):
                if r is None or slot in self._prefill_state:
                    continue
                tok = int(next_toks[slot])
                r.out_tokens.append(tok)
                self.metrics.on_token(r.rid)
                self.pos[slot] += 1
                self.cur_tok[slot] = tok
                self._emit(TokenEvent(r.rid, tok, len(r.out_tokens) - 1,
                                      self._tick_no))
                # a cancel issued from an event callback is DEFERRED (see
                # tick()'s finally), so r.done cannot flip under this loop
                if len(r.out_tokens) >= r.max_new or \
                        self.pos[slot] >= self.max_seq - 1:
                    reason = ("max_new" if len(r.out_tokens) >= r.max_new
                              else "max_seq")
                    r.done = True
                    self.metrics.on_finish(r.rid)
                    self._requests.pop(r.rid, None)
                    freed = self.backend.release(slot)
                    self.slot_req[slot] = None
                    self._emit(FinishEvent(r.rid, reason,
                                           len(r.out_tokens), freed,
                                           self._tick_no))
        return True

    # back-compat alias: tick() is the reentrant primitive
    step = tick

    def run(self, max_ticks: int = 10_000, on_tick=None) -> None:
        """Drive ticks until the queue and slots drain.  ``on_tick``
        (no-arg callable) runs after every tick — streaming consumers
        drain their event queue there (see launch/serve.py) without
        re-implementing the loop, its stall guard, or the runaway
        ``max_ticks`` bound."""
        ticks = 0
        while self.has_work and ticks < max_ticks:
            if not self.tick():
                # nothing admissible and nothing running: only possible
                # when queued work cannot fit yet — avoid spinning
                if not any(r is not None for r in self.slot_req) and \
                        len(self.scheduler):
                    raise RuntimeError(
                        "queued request can never be admitted "
                        "(pool too small for its prompt)")
            if on_tick is not None:
                on_tick()
            ticks += 1


def _sample_batched(logits: jax.Array, key, temps: jax.Array) -> jax.Array:
    """Vectorized sampling for all slots in one device call.

    logits (B,V) f32; temps (B,): <=0 means greedy.  Per-slot subkeys
    keep slots independent; the greedy lane ignores the key entirely so
    temperature-0 decoding is deterministic.
    """
    greedy = jnp.argmax(logits, axis=-1)
    keys = jax.random.split(key, logits.shape[0])
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
