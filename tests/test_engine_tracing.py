"""Host spans inside Engine.tick and the runtime counters beside them.

Traces a few ticks of the tiny paged, chunked engine with the profiler
(spans land on the ``/host:CPU`` plane; a span's keyword args come back as
event stats) and checks the span tree, the per-chunk args, that no span
count grows with the slot count, the ``engine.gc`` span and counters, the
compile counter, and that a compile inside a tick stays out of the TBT
series with phase timing off.  One file, so one test worker owns the
profiler session.
"""
import gc
import glob
import os
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as M
from repro.models.common import Parallel
from repro.runtime import tracing
from repro.runtime.engine import Engine
from repro.runtime.metrics import EngineMetrics

PAR = Parallel(remat=False, attn_chunk=32)
TICK_PHASES = ["engine.grow", "engine.admit", "engine.decode",
               "engine.sample", "engine.readback", "engine.emit"]


@pytest.fixture(scope="module")
def subject():
    cfg = registry.get("tiny-lm").reduced()
    params = M.init_params(cfg, PAR, jax.random.PRNGKey(0))
    return cfg, params


def _engine(subject, n_slots=3, **kw):
    cfg, params = subject
    kw.setdefault("time_phases", False)
    return Engine(cfg, PAR, params, n_slots=n_slots, max_seq=72,
                  paged=True, page_size=8, chunked_prefill=True,
                  prefill_chunk=16, **kw)


def _prompts(subject, lens, seed=0):
    cfg, _ = subject
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
            for n in lens]


@dataclass
class Span:
    name: str
    start: int
    end: int
    stats: dict
    children: list = field(default_factory=list)


@contextmanager
def traced(path):
    """Record the block; yields a list filled with its ``engine.*`` spans,
    in start order, once the block ends."""
    out = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(glob.glob(os.path.join(
        str(path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend(Span(e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats))
                           for e in line.events
                           if e.name.startswith("engine."))
    out.sort(key=lambda s: (s.start, -s.end))


def _ticks(spans):
    """The ``engine.tick`` spans with their direct children (collections
    left out: they fall anywhere)."""
    ticks, stack = [], []
    for s in spans:
        if s.name == "engine.gc":
            continue
        while stack and stack[-1].end < s.end:
            stack.pop()
        if stack:
            stack[-1].children.append(s)
        elif s.name == "engine.tick":
            ticks.append(s)
        stack.append(s)
    return ticks


def test_tick_spans_nest_in_order_with_chunk_args(subject, tmp_path):
    eng = _engine(subject)
    lens = (20, 9, 33)
    chunks0 = eng.metrics.prefill_chunks
    with traced(tmp_path) as spans:
        reqs = [eng.submit(p, max_new=4) for p in _prompts(subject, lens)]
        eng.run()
    assert all(r.done for r in reqs)
    ticks = _ticks(spans)
    assert len(ticks) == eng.metrics.ticks
    decoded = 0
    for t in ticks:
        names = [c.name for c in t.children]
        assert names[:2] == ["engine.grow", "engine.admit"], names
        rest = names[2:]
        if "engine.decode" in rest:
            decoded += 1
            assert rest[-4:] == TICK_PHASES[2:], names
            rest = rest[:-4]
        # before the decode: chunks, each graduation sampling its first
        # token (dispatch, then the wait)
        while rest:
            assert rest[0] == "engine.prefill_chunk", names
            rest = rest[1:]
            if rest[:2] == ["engine.sample", "engine.readback"]:
                rest = rest[2:]
    assert decoded > 0
    chunks = [s for s in spans if s.name == "engine.prefill_chunk"]
    assert len(chunks) == eng.metrics.prefill_chunks - chunks0
    got = sorted((s.stats["rid"], s.stats["start"], s.stats["length"])
                 for s in chunks)
    want = sorted((r.rid, st, min(16, n - st))
                  for r, n in zip(reqs, lens) for st in range(0, n, 16))
    assert got == want


@pytest.mark.parametrize("n_slots", [2, 6])
def test_span_count_per_tick_does_not_scale_with_slots(subject, tmp_path,
                                                       n_slots):
    eng = _engine(subject, n_slots=n_slots)
    reqs = [eng.submit(p, max_new=12)
            for p in _prompts(subject, [9] * n_slots, seed=n_slots)]
    while any(not r.out_tokens for r in reqs):    # every slot decoding
        eng.tick()
    with traced(tmp_path) as spans:
        for _ in range(3):
            eng.tick()
    spans = [s for s in spans if s.name != "engine.gc"]
    # one tick span and its six phases, whatever the slot count
    assert len(spans) == 3 * (1 + len(TICK_PHASES))
    assert [[c.name for c in t.children] for t in _ticks(spans)] == \
        [TICK_PHASES] * 3


def test_collection_inside_a_tick_is_spanned_and_counted(subject,
                                                         tmp_path):
    eng = _engine(subject, n_slots=2)
    r = eng.submit(_prompts(subject, [9])[0], max_new=8)
    eng.tick()
    collected = []

    def collect(ev):
        if not collected:
            collected.append(gc.collect(2))

    eng.subscribe(collect)
    m = eng.metrics
    before = (list(m.gc_collections), list(m.gc_pause_s))
    _, gen_before, _ = tracing.counters()
    with traced(tmp_path) as spans:
        eng.tick()
    _, gen_after, _ = tracing.counters()
    assert collected and not r.done
    gcs = [s for s in spans if s.name == "engine.gc"]
    gen2 = [s for s in gcs if s.stats["generation"] == 2]
    assert len(gen2) == gen_after[2] - gen_before[2] >= 1
    assert all(s.stats["generation"] >= 1 for s in gcs)
    emit = [s for s in spans if s.name == "engine.emit"]
    assert len(emit) == 1
    assert emit[0].start <= gen2[0].start and gen2[0].end <= emit[0].end
    assert m.gc_collections[2] - before[0][2] == gen_after[2] - gen_before[2]
    assert m.gc_pause_s[2] > before[1][2]
    snap = m.snapshot()
    assert snap["gc_collections"] == m.gc_collections
    assert snap["gc_pause_s"] == m.gc_pause_s


def test_generation_zero_is_counted_without_a_span(tmp_path):
    _, gen_before, _ = tracing.counters()
    with traced(tmp_path) as spans:
        gc.collect(0)
    _, gen_after, _ = tracing.counters()
    assert gen_after[0] - gen_before[0] >= 1
    assert len([s for s in spans if s.name == "engine.gc"]) == \
        sum(gen_after[g] - gen_before[g] for g in (1, 2))


def test_compiles_counted_then_steady(subject):
    # a slot count no other test here uses: its programs compile afresh
    eng = _engine(subject, n_slots=5)
    reqs = [eng.submit(p, max_new=16)
            for p in _prompts(subject, [9, 12, 5, 14, 7], seed=5)]
    while any(not r.out_tokens for r in reqs):
        eng.tick()
    eng.tick()
    first = eng.metrics.compiles
    assert first > 0
    for _ in range(4):
        eng.tick()
    assert eng.metrics.compiles == first
    assert eng.metrics.snapshot()["compiles"] == first


def test_compile_gap_stays_out_of_tbt_without_phase_timing(subject):
    """With ``time_phases=False`` the first tick compiles the chunk, the
    sample and the decode programs: the gap from the first token to the
    second holds the decode compile, and is a stall, not TBT.  The
    metrics' clock reads 2**k at the request's k-th token, so each gap
    names the tokens it lies between."""
    req = []
    clock = lambda: float(2 ** len(req[0].out_tokens)) if req else 0.0
    eng = _engine(subject, n_slots=7, time_phases=False,
                  metrics=EngineMetrics(clock=clock))
    req.append(eng.submit(_prompts(subject, [11], seed=7)[0], max_new=6))
    eng.tick()
    assert len(req[0].out_tokens) == 2 and eng.metrics.compiles > 0
    eng.run()
    assert req[0].done and len(req[0].out_tokens) == 6
    # gaps 2->3 .. 5->6; 1->2 (2.0) spans the decode compile
    assert eng.metrics._req[req[0].rid].tbt == [4.0, 8.0, 16.0, 32.0]
    assert eng.metrics.snapshot()["tbt_p95_s"] == 32.0


def test_one_listener_and_one_hook_however_many_engines(subject):
    _engine(subject, n_slots=2)
    _engine(subject, n_slots=2)
    assert gc.callbacks.count(tracing._on_gc) == 1
    compiles, gen, _ = tracing.counters()
    jax.monitoring.record_event_duration_secs(tracing.COMPILE_EVENT, 0.0)
    gc.collect(0)
    compiles2, gen2, _ = tracing.counters()
    assert compiles2 - compiles == 1
    assert gen2[0] - gen[0] == 1
