"""One run of one cell: set-up, the measured window, the check, the
result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the packed weights from the seed (``weights.py``), the
program's paged ``Engine`` with chunked prefill and the Pallas kernels,
compiles its decode and chunk steps (failing when ``mixed_matmul``,
``paged_attention`` or ``paged_prefill`` is not a ``tpu_custom_call`` in
the step it belongs to), warms both up with one request, and, where the
mix asks, admits the first clients' requests so the window starts with
every slot decoding.  The window then drives ``Engine.tick()`` for
``--seconds``, offering the mix's requests and stamping each token when
its tick returns.  ``--trace 1`` records the window with the profiler
and reports the per-layer metrics instead of the end-to-end ones.  After
the window the sampled finished requests go through the plain
reference (``check.py``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from harness import check, e2e, spec as spec_mod, trace as trace_mod
from harness import traffic as traffic_mod
from harness.cells import BENCH_DIR, Benchmark
from harness.peaks import peaks

KERNELS = {"decode": ("mixed_matmul", "paged_attention"),
           "prefill_chunk": ("mixed_matmul", "paged_prefill")}
CACHE_DIR = BENCH_DIR / ".jax_cache"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class NoChip(RuntimeError):
    """JAX found no TPU, too few chips, or Pallas would interpret."""


class KernelMissing(RuntimeError):
    """A kernel fell back to XLA in a compiled step."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_chip(chips: int):
    """The devices of the run, or :class:`NoChip`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    from repro.kernels import ops
    if ops.INTERPRET:
        raise NoChip("Pallas kernels would run in interpret mode")
    return devices


def tpu_kernels(hlo: str) -> Dict[str, int]:
    """Pallas kernels compiled into TPU HLO, by name, with call sites."""
    import re
    out: Dict[str, int] = {}
    for m in re.finditer(r'^\s*(?:ROOT\s+)?%(?P<name>[\w\-]+?)(?:\.\d+)?\s*=.*'
                         r'custom_call_target="tpu_custom_call"', hlo, re.M):
        out[m.group("name")] = out.get(m.group("name"), 0) + 1
    return out


def check_kernels(engine) -> Dict[str, Dict[str, int]]:
    """Compile the engine's decode and chunk steps and fail unless each
    holds its kernels as ``tpu_custom_call``."""
    found = {}
    for step, lowered in engine.backend.lowered_steps(engine.params).items():
        found[step] = tpu_kernels(lowered.compile().as_text())
        for name in KERNELS[step]:
            if not found[step].get(name):
                raise KernelMissing(f"{name} is not a tpu_custom_call in "
                                    f"the compiled {step} step")
    return found


@dataclass
class Window:
    """What the window saw, for the end-to-end and per-layer readers."""
    t0: float = 0.0
    t1: float = 0.0
    records: Dict[int, e2e.Record] = field(default_factory=dict)
    tokens: Dict[int, List[int]] = field(default_factory=dict)
    chunks: List[Tuple[float, int, int]] = field(default_factory=list)
    compiles: int = 0
    late_s: List[float] = field(default_factory=list)
    tick_from: int = 0
    attempted: int = 0
    failed: int = 0


class Client:
    """Offers a mix's requests to the engine and stamps what comes back."""

    def __init__(self, engine, plan):
        self.engine = engine
        self.plan = plan
        self.next = 0
        self.by_rid: Dict[int, int] = {}
        self.w = Window()
        self.queue = engine.event_queue()
        self.finished: List[int] = []

    def outstanding(self) -> int:
        """Requests sent and not yet finished or failed."""
        return sum(1 for r in self.w.records.values()
                   if not (r.finished or r.failed))

    def send(self, due: float) -> None:
        """Submit the schedule's next request, due at ``due``."""
        import jax
        pl = self.plan[self.next]
        self.next += 1
        with jax.profiler.TraceAnnotation("bench.submit"):
            rec = e2e.Record(due=due, prompt_len=len(pl.prompt))
            self.w.records[pl.index] = rec
            self.w.tokens[pl.index] = []
            try:
                r = self.engine.submit(pl.prompt, max_new=pl.max_new)
            except ValueError as e:
                rec.failed = True
                log(f"request {pl.index} refused: {e}")
                return
            self.by_rid[r.rid] = pl.index

    def tick(self) -> Tuple[bool, List[int]]:
        """One engine tick, its events stamped; returns whether the engine
        had work and the requests that ended in it."""
        import jax
        from repro.runtime.events import (ExpireEvent, FinishEvent,
                                          TokenEvent)
        with jax.profiler.TraceAnnotation("bench.tick"):
            busy = self.engine.tick()
        now = time.perf_counter()
        done = []
        with jax.profiler.TraceAnnotation("bench.drain"):
            while self.queue:
                ev = self.queue.popleft()
                idx = self.by_rid.get(ev.rid)
                if idx is None:
                    continue
                if isinstance(ev, TokenEvent):
                    self.w.records[idx].stamps.append(now)
                    self.w.tokens[idx].append(int(ev.token))
                elif isinstance(ev, FinishEvent):
                    rec = self.w.records[idx]
                    if ev.reason in ("max_new", "max_seq"):
                        rec.finished = True
                        self.finished.append(idx)
                    else:
                        rec.failed = True
                    done.append(idx)
                elif isinstance(ev, ExpireEvent):
                    self.w.records[idx].failed = True
                    done.append(idx)
        return busy, done


def _enable_cache() -> str:
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def setup(bench: Benchmark, wl: Dict, seed: int, seconds: float,
          devices) -> Tuple[Any, Client, Dict, Dict, Dict[str, float]]:
    """Weights, engine, compile and kernel check, warm-up, context."""
    import jax
    from repro.models.common import Parallel
    from repro.runtime.engine import Engine

    from harness.weights import build_params

    spec = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    dm = spec_mod.dims(spec)
    dep = spec["deployment"]
    parts: Dict[str, float] = {}

    t = time.perf_counter()
    cfg, params = build_params(spec, seed)
    jax.block_until_ready(params)
    parts["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    page = int(dep["page_size"])
    engine = Engine(cfg, Parallel(), params, n_slots=int(mix["slots"]),
                    max_seq=int(mix["max_seq"]), seed=seed % (2 ** 31),
                    paged=True, page_size=page,
                    pool_pages=traffic_mod.pool_pages(
                        mix, dm.kv_bytes_per_token(), page),
                    paged_kernel=True, chunked_prefill=True,
                    prefill_chunk=int(dep["prefill_chunk"]),
                    time_phases=False)
    del params
    found = check_kernels(engine)
    parts["compile_s"] = time.perf_counter() - t
    log(f"kernels per compiled step: {json.dumps(found)}")

    t = time.perf_counter()
    warm = engine.submit(np.arange(1, int(dep["prefill_chunk"]) // 2,
                                   dtype=np.int32) % dm.vocab, max_new=3)
    while not warm.done:
        engine.tick()
    parts["warmup_s"] = time.perf_counter() - t

    plan = traffic_mod.schedule(mix, seed, dm.vocab, seconds)
    client = Client(engine, plan)
    t = time.perf_counter()
    if mix["loop"] == "closed" and mix.get("setup_prefill"):
        for _ in range(int(mix["clients"])):
            client.send(time.perf_counter())
        # every first prompt's chunks in one tick, not one chunk a tick
        # beside a full decode step; the window runs one a tick again
        per_tick = engine.prefill_chunks_per_tick
        engine.prefill_chunks_per_tick = sum(
            -(-len(p.prompt) // int(dep["prefill_chunk"]))
            for p in plan[:int(mix["clients"])])
        # until every first request has its first token: all decoding
        while any(not r.stamps and not r.failed
                  for r in client.w.records.values()):
            client.tick()
        engine.prefill_chunks_per_tick = per_tick
    parts["context_s"] = time.perf_counter() - t
    return engine, client, spec, mix, parts


def drive(engine, client: Client, mix: Dict, seconds: float) -> Window:
    """Offer the mix for ``seconds`` and stamp every token."""
    import jax
    from jax import monitoring

    w = client.w
    counting = {"on": False, "n": 0}

    def on_event(event, duration, **_):
        if counting["on"] and event in COMPILE_EVENTS:
            counting["n"] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    backend = engine.backend
    chunk_fn = backend.prefill_chunk

    def logged_chunk(params, toks, slot, start, length):
        w.chunks.append((time.perf_counter(), int(start), int(length)))
        return chunk_fn(params, toks, slot, start, length)

    backend.prefill_chunk = logged_chunk
    w.tick_from = len(engine.metrics.queue_depth)
    closed = mix["loop"] == "closed"
    counting["on"] = True
    w.t0 = t0 = time.perf_counter()
    t_end = t0 + seconds
    w.attempted = client.outstanding()
    if closed:
        while client.next < len(client.plan) and \
                client.outstanding() < int(mix["clients"]):
            client.send(t0)
            w.attempted += 1
    try:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if not closed:
                with jax.profiler.TraceAnnotation("bench.generate"):
                    while client.next < len(client.plan) and \
                            t0 + client.plan[client.next].due_s <= now:
                        due = t0 + client.plan[client.next].due_s
                        w.late_s.append(now - due)
                        client.send(due)
                        w.attempted += 1
            busy, done = client.tick()
            if closed:
                for _ in done:
                    if client.next < len(client.plan):
                        client.send(time.perf_counter())
                        w.attempted += 1
            elif not busy:
                nxt = (t0 + client.plan[client.next].due_s
                       if client.next < len(client.plan) else t_end)
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(max(0.0, min(nxt, t_end)
                                   - time.perf_counter()))
    finally:
        w.t1 = time.perf_counter()
        counting["on"] = False
        monitoring.unregister_event_duration_listener(on_event)
        backend.prefill_chunk = chunk_fn
    w.compiles = counting["n"]
    w.failed = sum(1 for r in w.records.values() if r.failed)
    return w


@dataclass
class Context:
    """What a per-layer metric reader gets."""
    dims: Any
    spec: Dict
    window: Window
    engine_metrics: Any
    trace: Optional[Dict]
    peaks: Dict
    derived: Dict = field(default_factory=dict)

    def note(self, name: str, text: str) -> None:
        """A reader's remark (such as which roofline bound applied),
        printed with the run's other remarks."""
        self.derived[name] = text
        log(f"{name}: {text}")

    def decode_rows(self) -> List[int]:
        """Context length of every decoded (not prefill-sampled) token the
        window stamped."""
        w = self.window
        out = []
        for idx, rec in w.records.items():
            for j, t in enumerate(rec.stamps):
                if j >= 1 and w.t0 <= t <= w.t1:
                    out.append(rec.prompt_len + j)
        return out


def _reduce_trace(path: str) -> Dict:
    plain = trace_mod.load(path)
    planes = sorted(plain["devices"])
    if not planes:
        raise RuntimeError("the trace holds no TPU device plane")
    return {"plain": plain, "devices": [trace_mod.DeviceTrace(
        plain["devices"][p]) for p in planes]}


def _device_summary(red: Dict) -> Tuple[float, float, Dict]:
    """busy_s (mean over chips), window_s and the breakdown."""
    devs = red["devices"]
    spans = []
    for d in devs:
        evs = [(s, e) for _, s, e in d.ops] + [(s, e) for _, s, e in d.modules]
        if evs:
            spans.append((min(s for s, _ in evs), max(e for _, e in evs)))
    host = red["plain"]["host"]
    win = [(s, s + dd) for n, s, dd in host if n == "bench.window"]
    lo, hi = (win[0] if win else (min(s for s, _ in spans),
                                  max(e for _, e in spans)))
    busy = [trace_mod.total(d.busy(lo, hi)) for d in devs]
    red["window_ns"] = (lo, hi)
    d0 = devs[0]
    breakdown = {"device_ops": [[n, t] for n, t in d0.top_ops(lo, hi)],
                 "idle_gaps": [[n, t] for n, t in trace_mod.label_gaps(
                     d0.gaps(lo, hi), host)]}
    return sum(busy) / len(busy) * 1e-9, (hi - lo) * 1e-9, breakdown


@dataclass
class Served:
    """A cell's run up to the check: the result line without ``correct``
    and the finished requests the check samples."""
    result: Dict[str, Any]
    spec: Dict
    requests: List[Tuple[np.ndarray, List[int]]]
    window: Window


def serve(bench: Benchmark, wl: Dict, seed: int, seconds: float,
          traced: bool, devices, t_process: float,
          before_free=None) -> Served:
    """Set-up, the window, the metrics; the engine is freed on return.
    ``before_free(engine, client)``, where given, runs just before (the
    readings tool's second witness)."""
    import jax

    engine, client, spec, mix, parts = setup(bench, wl, seed, seconds,
                                             devices)
    setup_s = time.time() - t_process
    log("set-up: " + ", ".join(f"{k} {v:.3f}" for k, v in parts.items())
        + f", total {setup_s:.3f} s")

    trace_dir = bench.dir / "out" / "trace"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only: bench.* annotations
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        w = drive(engine, client, mix, seconds)
    if traced:
        jax.profiler.stop_trace()
    chips = int(wl["chips"])
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    log(f"window {w.t1 - w.t0:.3f} s, requests {w.attempted}, "
        f"failed {w.failed}, finished {len(client.finished)}, "
        f"compiles in window {w.compiles}, ticks "
        f"{len(engine.metrics.queue_depth) - w.tick_from}, preemptions "
        f"{engine.metrics.preemptions}, page util at the close "
        f"{engine.backend.page_util():.4f}")
    if w.late_s:
        log(f"generator late: p50 {e2e.percentile(w.late_s, 50) * 1e3:.3f} "
            f"ms, max {max(w.late_s) * 1e3:.3f} ms")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    metrics: Dict[str, Dict[str, Any]] = {}
    result: Dict[str, Any] = {"attempted": w.attempted, "failed": w.failed,
                              "metrics": metrics, "device": device}
    if not traced:
        e2e_specs = {m["name"]: m for m in bench.end_to_end(wl["name"])}
        names = [n for n in e2e_specs if n != "setup_s"]
        for name, v in e2e.metrics(w.records, w.t0, w.t1, names).items():
            if v is not None:
                metrics[name] = {"value": v, "unit": e2e_specs[name]["unit"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        red = _reduce_trace(trace_mod.latest_xplane(str(trace_dir)))
        busy_s, window_s, breakdown = _device_summary(red)
        red["busy_s"], red["window_s"] = busy_s, window_s
        device["busy_s"], device["window_s"] = busy_s, window_s
        ctx = Context(dims=spec_mod.dims(spec), spec=spec, window=w, engine_metrics=engine.metrics, trace=red,
                      peaks=peaks(dev.device_kind))
        for m in bench.per_layer(wl["name"]):
            v = bench.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = breakdown
        shutil.rmtree(trace_dir, ignore_errors=True)

    served = {i: len(w.tokens[i]) for i in client.finished}
    pick = traffic_mod.check_sample(client.finished, served, seed,
                                    int(mix["check_requests"]))
    requests = [(client.plan[i].prompt, list(w.tokens[i])) for i in pick]
    if before_free is not None:
        before_free(engine, client, pick)
    del engine, client
    gc.collect()
    return Served(result, spec, requests, w)


def run(argv: List[str], t_process: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = Benchmark()
    wl = bench.workload(args.workload)
    limits_path = bench.dir / "limits" / f"{args.workload}.json"
    # a cell without its limits file can run, but never reads correct
    limits = (json.loads(limits_path.read_text())["limits"]
              if limits_path.is_file() else {})
    try:
        devices = require_chip(int(wl["chips"]))
    except NoChip as e:
        log(f"refused: {e}")
        return 3
    log(f"compile cache: {_enable_cache()}")
    try:
        out = serve(bench, wl, args.seed, args.seconds, bool(args.trace),
                    devices, t_process)
    except KernelMissing as e:
        log(f"refused: {e}")
        return 4

    # the check, once the program's state is freed
    t = time.perf_counter()
    if out.requests:
        got = check.compare(bench.reference(out.spec["reference"]),
                            out.spec, args.seed, out.requests)
    else:
        got = check.stats(np.zeros((0,)))
    log(f"reference over {len(out.requests)} requests, "
        f"{got['tokens_compared']} served tokens: "
        f"{time.perf_counter() - t:.3f} s; " + ", ".join(
            f"{k} {v}" for k, v in got.items()))
    correct, checks = check.judge(got, limits)
    for name, c in checks.items():
        print(f"[check] {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    result = {"correct": correct, **out.result, "checks": checks}
    print(json.dumps(result), flush=True)
    return 0
