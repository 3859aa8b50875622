"""Training launcher: pjit'd train step with gradient accumulation,
fault-tolerant checkpoint/restart, failure injection, elastic re-mesh,
straggler watchdog and optional gradient compression.

CPU-runnable end-to-end driver (deliverable b):

    PYTHONPATH=src python -m repro.launch.train --arch tiny-lm --steps 200

On a real fleet the same module runs under the production mesh
(``--mesh pod|multipod`` — the dry-run proves those shardings compile);
the single-process container trains reduced configs on a (1,1) mesh.

Fault-tolerance path (tests/test_fault_tolerance.py):
    --fail-at-step 30 --save-every 10 --restore auto
injects a failure at step 30; the Supervisor restores step 20 and
re-runs.  Training is bit-deterministic across restarts because the data
stream is a pure function of the step counter.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import (latest_step, restore_checkpoint,
                                    save_checkpoint)
from repro.configs import registry
from repro.configs.base import ArchConfig
from repro.data.synthetic import CorpusConfig, SyntheticCorpus
from repro.distributed.compression import (CompressionConfig, compress,
                                           init_residual, wire_bytes)
from repro.distributed.fault import (FailureInjector, InjectedFailure,
                                     StragglerWatchdog, Supervisor)
from repro.distributed.sharding import (Rules, named_shardings,
                                        rules_for_mesh, specs_for_tree)
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import model as M
from repro.models.common import Parallel
from repro.models.param import P, is_leaf as is_p, tree_map_params
from repro.optim.adamw import AdamW, AdamWState, cosine_schedule

Tree = Any


# ---------------------------------------------------------------------------
# Train state & step
# ---------------------------------------------------------------------------
def make_train_step(cfg: ArchConfig, par: Parallel, opt: AdamW,
                    ccfg: CompressionConfig, param_spec: Optional[Tree] = None):
    """(state, batch) -> (state, metrics).  Gradient accumulation over
    ``par.microbatches`` via lax.scan keeps activation memory flat; the
    compressor (error-feedback int8/top-k) runs on the averaged gradient
    (EF equivalence — distributed/compression.py).

    ``param_spec`` (the params' PartitionSpec tree) shards the gradient
    ACCUMULATOR like the parameters (ZeRO-2): without it GSPMD keeps the
    accumulator replicated and emits a full f32 gradient all-reduce per
    microbatch — measured 8× the necessary gradient traffic on the FSDP
    archs (command-r/llava/mixtral train_4k, §Perf)."""

    def loss_fn(params, batch):
        return M.forward_loss(cfg, par, params, batch)

    def train_step(state, batch):
        params, opt_state, residual = (state["params"], state["opt"],
                                       state["residual"])
        mb = par.microbatches
        if mb > 1:
            b = batch["tokens"].shape[0]
            assert b % mb == 0, (b, mb)
            split = {k: v.reshape((mb, b // mb) + v.shape[1:])
                     for k, v in batch.items()}
            # without this constraint the partitioner factors the data axis
            # across (micro, batch) dims — each microbatch ends up only
            # dp/mb-way sharded, wasting mb× compute (found via the
            # roofline dry-run; see EXPERIMENTS.md §Perf)
            from jax.sharding import PartitionSpec as PS
            from repro.models.common import _batch_axes, in_mesh
            if in_mesh():
                split = {
                    k: jax.lax.with_sharding_constraint(
                        v, PS(None, _batch_axes(),
                              *([None] * (v.ndim - 2))))
                    for k, v in split.items()}

            def micro(carry, mbatch):
                loss, grads = jax.value_and_grad(loss_fn)(params, mbatch)
                acc_l, acc_g = carry
                return (acc_l + loss,
                        jax.tree.map(jnp.add, acc_g, grads)), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            from repro.models.common import in_mesh
            if param_spec is not None and in_mesh():
                zeros = jax.tree.map(
                    jax.lax.with_sharding_constraint, zeros, param_spec)
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.zeros((), jnp.float32), zeros), split)
            loss = loss / mb
            grads = jax.tree.map(lambda g: g / mb, grads)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)

        if ccfg.kind is not None:
            grads, residual = compress(grads, residual, ccfg)
        params, opt_state = opt.update(grads, opt_state, params)
        new_state = {"params": params, "opt": opt_state,
                     "residual": residual}
        return new_state, {"loss": loss}

    return train_step


def init_state(cfg: ArchConfig, par: Parallel, opt: AdamW,
               ccfg: CompressionConfig, seed: int = 0) -> Tree:
    params = M.init_params(cfg, par, jax.random.PRNGKey(seed))
    opt_state = opt.init(params)
    residual = (init_residual(params) if ccfg.kind is not None
                else jnp.zeros((), jnp.float32))
    return {"params": params, "opt": opt_state, "residual": residual}


def state_specs(cfg: ArchConfig, par: Parallel, rules: Rules,
                ccfg: CompressionConfig) -> Tree:
    """PartitionSpec tree matching init_state's structure."""
    declared = M.declare_params(cfg, par)
    pspec = specs_for_tree(declared, rules)
    from jax.sharding import PartitionSpec as PS
    ospec = AdamWState(step=PS(), mu=pspec, nu=pspec)
    rspec = pspec if ccfg.kind is not None else PS()
    return {"params": pspec, "opt": ospec, "residual": rspec}


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def build_mesh(kind: str):
    if kind == "host":
        return make_host_mesh()
    return make_production_mesh(multi_pod=(kind == "multipod"))


def run(args) -> Dict[str, Any]:
    mesh = build_mesh(args.mesh)
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        cfg = dataclasses.replace(cfg, vocab=min(cfg.vocab, 512))
    tp = mesh.shape["model"]
    dp = int(mesh.devices.size) // tp
    par = Parallel(tp=tp, dp=dp, microbatches=args.microbatches,
                   remat=args.remat, attn_chunk=args.attn_chunk,
                   sp=tp > 1)
    rules = rules_for_mesh(mesh, fsdp=args.fsdp)
    ccfg = CompressionConfig(kind=args.compression,
                             topk_frac=args.topk_frac)
    opt = AdamW(lr=args.lr, weight_decay=0.01, clip_norm=1.0,
                schedule=cosine_schedule(warmup=args.warmup,
                                         total=args.steps))
    pspec = specs_for_tree(M.declare_params(cfg, par), rules)
    step_fn = make_train_step(cfg, par, opt, ccfg, param_spec=pspec)

    corpus = SyntheticCorpus(CorpusConfig(vocab=cfg.vocab, seed=args.seed))

    def batch_at(step: int) -> Dict[str, jax.Array]:
        tok, tgt = next(corpus.batches(args.batch, args.seq, 1,
                                       split="train", host=step,
                                       n_hosts=1 << 30))
        return {"tokens": jnp.asarray(tok), "targets": jnp.asarray(tgt)}

    with mesh:
        state = init_state(cfg, par, opt, ccfg, seed=args.seed)
        sspec = state_specs(cfg, par, rules, ccfg)
        from jax.sharding import PartitionSpec as PS
        bspec = {"tokens": PS(rules.dp_axes if dp > 1 else None),
                 "targets": PS(rules.dp_axes if dp > 1 else None)}
        jstep = jax.jit(step_fn,
                        in_shardings=(named_shardings(mesh, sspec),
                                      named_shardings(mesh, bspec)),
                        out_shardings=(named_shardings(mesh, sspec), None),
                        donate_argnums=(0,))

        start = 0
        if args.restore == "auto" and args.ckpt_dir and \
                latest_step(args.ckpt_dir) is not None:
            state, start = restore_checkpoint(args.ckpt_dir, state)
            print(f"[restore] resumed from step {start}")

        injector = FailureInjector(tuple(args.fail_at_step or ()))
        watchdog = StragglerWatchdog()
        losses = []

        def restore() -> int:
            nonlocal state
            state, s = restore_checkpoint(args.ckpt_dir, state)
            return s

        def one_step(step: int):
            nonlocal state
            injector.maybe_fail(step)
            t0 = time.time()
            state, metrics = jstep(state, batch_at(step))
            loss = float(metrics["loss"])
            losses.append(loss)
            watchdog.observe(step, time.time() - t0)
            if step % args.log_every == 0:
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"({(time.time()-t0)*1e3:.0f} ms)")
            # checkpoint label = steps COMPLETED, so restore resumes at the
            # next step (no double-applied update after a restart)
            if args.ckpt_dir and (step + 1) % args.save_every == 0:
                save_checkpoint(args.ckpt_dir, step + 1, state)

        sup = Supervisor(restore, max_restarts=args.max_restarts)
        sup.run(one_step, start, args.steps)

        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, args.steps, state)

    out = {"final_loss": losses[-1] if losses else None,
           "first_loss": losses[0] if losses else None,
           "restarts": sup.restarts,
           "straggler_steps": watchdog.slow_steps,
           "wire_bytes": wire_bytes(state["params"], ccfg)}
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2)
    return out


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="repro training launcher")
    p.add_argument("--arch", default="tiny-lm")
    p.add_argument("--reduced", action="store_true",
                   help="train the reduced same-family config (CPU scale)")
    p.add_argument("--mesh", default="host",
                   choices=["host", "pod", "multipod"])
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--attn-chunk", type=int, default=1024)
    p.add_argument("--remat", action="store_true")
    p.add_argument("--fsdp", action="store_true")
    p.add_argument("--compression", default=None,
                   choices=[None, "int8", "topk"])
    p.add_argument("--topk-frac", type=float, default=0.1)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--save-every", type=int, default=50)
    p.add_argument("--restore", default="none", choices=["none", "auto"])
    p.add_argument("--fail-at-step", type=int, nargs="*", default=None)
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json-out", default=None)
    return p.parse_args(argv)


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    run(parse_args())
