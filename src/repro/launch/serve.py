"""Serving launcher: quantize a model with PTQ1.61, run the continuous-
batching engine over a stream of requests (deliverable b, serving flavor).

    PYTHONPATH=src python -m repro.launch.serve --arch tiny-lm --requests 8

Weights are quantized data-free (fast path) or with the full calibrated
pipeline (--calibrated).  ``--kernel`` dispatches the fused Pallas
mixed_matmul (interpret mode on CPU) instead of the XLA dequant path.
``--paged`` serves from the paged KV cache (block-table allocator +
priority-class/preemption scheduler; see repro.runtime.paged_cache) with
``--page-size`` tokens per page and a ``--pool-pages`` global budget;
paged decode attention runs through the Pallas flash-decode kernel on
feasible shapes (``--no-paged-kernel`` pins the XLA dense-gather
reference path).

Event-loop extras (this is the end-to-end demo of the engine's typed
event API):

  * ``--stream`` drives ``Engine.tick()`` directly and prints every
    ``TokenEvent`` the tick it is emitted (rid, output index, token) —
    no buffering until completion.
  * ``--cancel-after-s N`` cancels the longest-running in-flight
    request (earliest admitted, still decoding) once N seconds of
    serving have elapsed; the JSON output records the cancelled rids
    and how many pool pages each cancellation freed (same tick).
  * ``--priority a,b,c`` cycles the listed priority classes across the
    submitted requests (weighted-deficit admission with aging:
    realtime=8 / standard=4 / batch=1 by default); per-class TTFT/TBT
    land in the engine-metrics JSON.
  * ``--share-prefix`` enables copy-on-write prefix sharing
    (``Engine(prefix_sharing=True)``) and gives all requests a common
    page-aligned prompt prefix so the sharing is visible: the common
    pages are allocated once, and the JSON carries the prefix-cache
    counters (hits, pages attached instead of allocated, COW copies).

Engine metrics (tokens/s, TTFT, TBT p50/p95 overall and per class,
queue depth, page utilization) are included in the JSON output either
way.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core.bits import model_bits
from repro.core.pipeline import (quantize_model_ptq161,
                                 quantize_params_data_free)
from repro.core.qlinear import QuantConfig
from repro.data.synthetic import CorpusConfig, SyntheticCorpus
from repro.models import model as M
from repro.models.common import Parallel
from repro.runtime.engine import Engine
from repro.runtime.events import FinishEvent, TokenEvent

Tree = Any


def _drive(engine: Engine, *, stream: bool, cancel_after_s=None):
    """Event-API consumer over ``Engine.run(on_tick=...)``: drain the
    queue after every tick, print tokens when streaming, fire the demo
    cancellation once its deadline passes.  Returns the cancellation
    receipts.  The loop itself — stall guard, max_ticks runaway bound —
    stays in the engine."""
    q = engine.event_queue()
    cancelled = []
    state = {"did_cancel": False, "t0": time.time()}

    def after_tick():
        if cancel_after_s is not None and not state["did_cancel"] and \
                time.time() - state["t0"] >= cancel_after_s:
            active = engine.running()
            if active:
                # longest-running = earliest SUBMITTED still in a slot
                # (admit_seq is re-stamped on preemption resumes; rid
                # preserves the original order)
                _, victim = min(active, key=lambda sr: sr[1].rid)
                engine.cancel(victim.rid)
                state["did_cancel"] = True
        while q:
            ev = q.popleft()
            if isinstance(ev, TokenEvent) and stream:
                print(f"[stream] rid={ev.rid} idx={ev.index} "
                      f"tok={ev.token}", flush=True)
            elif isinstance(ev, FinishEvent) and ev.reason == "cancelled":
                cancelled.append({"rid": ev.rid, "tick": ev.tick,
                                  "tokens_before_cancel": ev.n_tokens,
                                  "freed_pages": ev.freed_pages})
                if stream:
                    print(f"[cancel] rid={ev.rid} freed_pages="
                          f"{ev.freed_pages}", flush=True)

    engine.run(on_tick=after_tick)
    after_tick()        # events from the final tick's teardown
    return cancelled


def quantize_model(args):
    """Build ``args.arch`` from a seed and quantize it as ``args.quantize``
    says.  Returns ``(cfg, par, qparams, quantize_s)``; the fp weights
    are dropped as soon as the packed ones exist, so the device holds
    one copy of the model from here on."""
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    par = Parallel(remat=False, attn_chunk=args.attn_chunk)
    params = M.init_params(cfg, par, jax.random.PRNGKey(args.seed))

    qcfg = QuantConfig(ratio=args.ratio, multiple=args.multiple,
                       steps=args.opt_steps, use_kernel=args.kernel)
    t0 = time.time()
    if args.quantize == "none":
        qparams = params
    elif args.quantize == "calibrated":
        if args.fused:
            print("[warn] --fused ignored for calibrated quantization "
                  "(per-projection QLinears cannot be fused post-hoc)")
        corpus = SyntheticCorpus(CorpusConfig(vocab=cfg.vocab,
                                              seed=args.seed))
        calib = [{"tokens": jnp.asarray(t)} for t, _ in
                 corpus.batches(1, args.calib_seq, args.calib_segments,
                                split="calib")]
        qparams = quantize_model_ptq161(cfg, par, params, calib, qcfg,
                                        min_dim=args.min_dim)
    else:  # data-free
        qparams = quantize_params_data_free(params, qcfg,
                                            min_dim=args.min_dim,
                                            fuse=args.fused)
    del params
    jax.block_until_ready(qparams)
    t_quant = time.time() - t0

    if args.quantize != "none":
        rep = model_bits(qparams)
        print(f"[quant] {args.quantize} in {t_quant:.1f}s — "
              f"{rep['avg_bits_per_quantized_weight']:.3f} bits/weight over "
              f"{rep['quantized_weights']:,} weights")
    return cfg, par, qparams, t_quant


def build_engine(args, cfg, par, qparams) -> Engine:
    """The serving engine the flags describe, over ``qparams``."""
    if args.share_prefix and not args.paged:
        raise SystemExit("--share-prefix requires --paged "
                         "(sharing lives in the page allocator)")
    if args.chunked_prefill and not args.paged:
        raise SystemExit("--chunked-prefill requires --paged "
                         "(chunks scatter into pool pages)")
    if args.prefix_retain and not args.share_prefix:
        raise SystemExit("--prefix-retain requires --share-prefix "
                         "(retention extends the prefix cache)")
    engine = Engine(cfg, par, qparams, n_slots=args.slots,
                    max_seq=args.max_seq,
                    prefill_buckets=(args.max_seq // 8, args.max_seq // 2),
                    paged=args.paged, page_size=args.page_size,
                    pool_pages=args.pool_pages,
                    paged_kernel=not args.no_paged_kernel,
                    prefix_sharing=args.share_prefix,
                    prefix_retain_pages=args.prefix_retain,
                    chunked_prefill=args.chunked_prefill,
                    prefill_chunk=args.prefill_chunk,
                    fuse_projections=args.fused and args.quantize == "none")
    for c in _classes(args):
        if not engine.scheduler.has_class(c):
            raise SystemExit(f"unknown priority class {c!r}; configured: "
                             f"{sorted(engine.scheduler.cfg.class_weights)}")
    return engine


def _classes(args):
    classes = [c.strip() for c in args.priority.split(",") if c.strip()]
    if not classes:
        raise SystemExit("--priority needs at least one class name "
                         "(e.g. --priority realtime,batch)")
    return classes


def submit_requests(args, engine: Engine):
    """Submit ``args.requests`` synthetic prompts (seeded) to ``engine``."""
    corpus = SyntheticCorpus(CorpusConfig(vocab=engine.cfg.vocab,
                                          seed=args.seed))
    classes = _classes(args)
    rng = np.random.default_rng(args.seed)
    # --share-prefix: a page-aligned common document prefix (half the
    # prompt budget) + per-request unique tails — the sharing workload
    common_len = 0
    common = np.zeros((0,), np.int32)
    if args.share_prefix:
        common_len = (args.max_seq // 8) // args.page_size * args.page_size
        common = corpus.document(9_999, max(common_len, args.page_size))
        common_len = len(common)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(4, args.max_seq // 4))
        tail = corpus.document(10_000 + i, plen)
        prompt = np.concatenate([common, tail]) if common_len else tail
        reqs.append(engine.submit(prompt, max_new=args.max_new,
                                  temperature=args.temperature,
                                  deadline_s=args.deadline_s,
                                  priority=classes[i % len(classes)]))
    return reqs


def serve(args, engine: Engine, reqs, t_quant: float):
    """Drive ``engine`` until ``reqs`` drain; print and return the
    result JSON."""
    t0 = time.time()
    if args.stream or args.cancel_after_s is not None:
        cancelled = _drive(engine, stream=args.stream,
                           cancel_after_s=args.cancel_after_s)
    else:
        engine.run()
        cancelled = []      # nothing cancels on the plain run() path
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in reqs)
    out = {
        "requests": len(reqs),
        "generated_tokens": toks,
        "wall_s": dt,
        "tokens_per_s": toks / max(dt, 1e-9),
        "all_done": all(r.done for r in reqs),
        "cancelled": cancelled,
        "priority_classes": _classes(args),
        "quantize_mode": args.quantize,
        "quantize_s": t_quant,
        "cache_backend": engine.backend.name,
        "prefix_sharing": engine.prefix_stats(),
        "engine_metrics": engine.metrics.snapshot(),
    }
    print(json.dumps(out, indent=2))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
    return out


def run(args):
    cfg, par, qparams, t_quant = quantize_model(args)
    engine = build_engine(args, cfg, par, qparams)
    return serve(args, engine, submit_requests(args, engine), t_quant)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="repro serving launcher")
    p.add_argument("--arch", default="tiny-lm")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--quantize", default="datafree",
                   choices=["none", "datafree", "calibrated"])
    p.add_argument("--kernel", action="store_true",
                   help="use the fused Pallas mixed_matmul path")
    p.add_argument("--fused", action="store_true",
                   help="N-fuse QKV / gate+up projections (decode fast "
                        "path): fused packed layouts for data-free "
                        "quantization, fp concat fusion for --quantize none")
    p.add_argument("--ratio", type=float, default=0.2)
    p.add_argument("--multiple", type=int, default=128,
                   help="salient-span rounding; 128 keeps both K spans "
                        "on the TPU lane tiling the packed kernel needs")
    p.add_argument("--min-dim", type=int, default=32)
    p.add_argument("--opt-steps", type=int, default=3)
    p.add_argument("--calib-segments", type=int, default=4)
    p.add_argument("--calib-seq", type=int, default=64)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--paged", action="store_true",
                   help="paged KV cache (block tables + shared page pool)")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per KV page (paged mode)")
    p.add_argument("--pool-pages", type=int, default=None,
                   help="total pages in the pool (default: full parity "
                        "with the contiguous layout, slots*max_seq/page)")
    p.add_argument("--no-paged-kernel", action="store_true",
                   help="pin paged decode attention to the XLA-gather "
                        "reference path instead of the Pallas "
                        "flash-decode kernel")
    p.add_argument("--stream", action="store_true",
                   help="drive tick() directly and print every token "
                        "the tick it is emitted (event API demo)")
    p.add_argument("--cancel-after-s", type=float, default=None,
                   help="after N seconds of serving, cancel the longest-"
                        "running in-flight request (its pages free the "
                        "same tick; receipts land in the JSON)")
    p.add_argument("--priority", default="standard",
                   help="comma list of priority classes cycled across "
                        "requests (realtime/standard/batch)")
    p.add_argument("--share-prefix", action="store_true",
                   help="copy-on-write prefix sharing + a common page-"
                        "aligned prompt prefix across requests (paged "
                        "mode only)")
    p.add_argument("--prefix-retain", type=int, default=0,
                   help="retain up to N freed prefix pages in an LRU "
                        "pool so late same-prefix requests still hit "
                        "after their cohort finished (needs "
                        "--share-prefix)")
    p.add_argument("--chunked-prefill", action="store_true",
                   help="advance prefills a chunk per tick, interleaved "
                        "with decode (fused scatter+attend paged-"
                        "prefill kernel; bounds the decode inter-token "
                        "gap under long prompts; paged mode only)")
    p.add_argument("--prefill-chunk", type=int, default=64,
                   help="prompt tokens per prefill chunk (multiple of "
                        "--page-size)")
    p.add_argument("--deadline-s", type=float, default=None,
                   help="per-request admission deadline in seconds")
    p.add_argument("--max-seq", type=int, default=128)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--attn-chunk", type=int, default=1024)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json-out", default=None)
    return p.parse_args(argv)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    run(parse_args())
