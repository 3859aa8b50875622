"""Bring-up check of the serving path on one TPU chip.

    python3 chip_smoke.py

Builds Qwen2.5-3B at its published width (random weights from a seed),
quantizes it data-free with PTQ1.61 (QKV and gate+up fused), and serves
a few requests through the paged ``Engine`` with chunked prefill — the
path that runs all three Pallas kernels (``mixed_matmul``,
``paged_prefill``, ``paged_attention``) — in this one process, through
``repro.launch.serve``'s own pieces.

It exits non-zero, without the final ``"ok"`` line, when:

* JAX finds no TPU, or the kernels would run in Pallas interpret mode;
* a kernel is missing from the compiled decode or chunk-prefill step
  program (a gate that fell back to XLA is a failure here, not a slower
  success);
* a request does not finish with all its tokens;
* the kernel path's logits at one prompt's last prefill position differ
  from the XLA path's (``use_kernel=False``, ``paged_kernel=False``) by
  more than ``LOGIT_TOL`` of the reference's largest logit, or are not
  finite.

The last line of stdout is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SERVE_ARGV = ["--arch", "qwen2.5-3b", "--quantize", "datafree",
              "--kernel", "--fused", "--paged", "--chunked-prefill",
              "--page-size", "16", "--prefill-chunk", "64",
              "--requests", "4", "--slots", "4", "--max-seq", "512",
              "--max-new", "16", "--seed", "0"]

# Kernels each compiled step program must hold (as tpu_custom_call).
STEP_KERNELS = {"decode": ("mixed_matmul", "paged_attention"),
                "prefill_chunk": ("mixed_matmul", "paged_prefill")}

# max |logit_kernel - logit_xla| / max |logit_xla|.  Both paths run bf16
# activations through 36 layers and differ only in rounding order (the
# kernels accumulate in f32 where the XLA dequant path rounds each
# product to bf16), so a few percent is expected; a misplaced scale,
# permutation or page is an O(1) error.
LOGIT_TOL = 0.05


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def run_smoke(argv) -> dict:
    """Quantize, compile, serve and compare logits as ``argv`` (serve
    launcher flags) describes; raises :class:`SmokeFailure` on any
    failed check and returns the measured numbers otherwise, with the
    ``tpu_custom_call`` kernels found in each compiled step program
    (which only a TPU compile can hold — :func:`check_kernels`)."""
    import jax
    import numpy as np

    from repro.core.bits import model_bits
    from repro.core.qlinear import QLinear
    from repro.launch import serve
    from repro.launch.hlo_analysis import tpu_kernels

    args = serve.parse_args(argv)
    cfg, par, qparams, t_quant = serve.quantize_model(args)
    bits = model_bits(qparams)
    print(f"[smoke] model {cfg.name}: d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim_} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} layers={cfg.n_layers}",
          flush=True)
    print(f"[smoke] bits/weight {bits['avg_bits_per_quantized_weight']:.4f}"
          f" over {bits['quantized_weights']:,} quantized weights; "
          f"quantize_s {t_quant:.2f}", flush=True)

    engine = serve.build_engine(args, cfg, par, qparams)
    t0 = time.time()
    found = {}
    for step, lowered in engine.backend.lowered_steps(engine.params).items():
        found[step] = tpu_kernels(lowered.compile().as_text())
    compile_s = time.time() - t0
    print(f"[smoke] first compile of decode + prefill_chunk steps: "
          f"{compile_s:.2f}s; tpu_custom_call kernels {found}", flush=True)

    reqs = serve.submit_requests(args, engine)
    out = serve.serve(args, engine, reqs, t_quant)
    _check(out["all_done"], "not every request finished")
    want = args.requests * args.max_new
    _check(out["generated_tokens"] == want,
           f"generated {out['generated_tokens']} tokens, expected {want}")
    print(f"[smoke] generated {out['generated_tokens']} tokens for "
          f"{len(reqs)} requests in {out['wall_s']:.2f}s", flush=True)

    # the XLA reference: same packed weights, dequant path and the
    # dense-gather paged attention, through its own engine
    xla_params = jax.tree.map(
        lambda x: (dataclasses.replace(x, use_kernel=False)
                   if isinstance(x, QLinear) else x),
        engine.params, is_leaf=lambda x: isinstance(x, QLinear))
    ref_args = serve.parse_args(argv + ["--no-paged-kernel"])
    reference = serve.build_engine(ref_args, cfg, par, xla_params)
    prompt = max((r.prompt for r in reqs), key=len)
    got = engine.prefill_logits(prompt)
    ref = reference.prefill_logits(prompt)
    want = (cfg.vocab_padded,)
    _check(got.shape == want and ref.shape == want,
           f"logits shapes {got.shape} / {ref.shape}, expected {want}")
    got, ref = got[:cfg.vocab], ref[:cfg.vocab]     # past it: masked pad
    _check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
           "non-finite logits")
    diff = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    rel = diff / scale
    print(f"[smoke] logits at prompt position {len(prompt) - 1}: "
          f"max|kernel - xla| = {diff:.6g}, max|xla| = {scale:.6g}, "
          f"ratio {rel:.6g} (bound {LOGIT_TOL}); argmax "
          f"{int(got.argmax())} vs {int(ref.argmax())}", flush=True)
    _check(rel <= LOGIT_TOL,
           f"kernel-vs-XLA logit difference {rel:.4g} exceeds {LOGIT_TOL}")
    return {"quantize_s": t_quant, "compile_s": compile_s,
            "generated_tokens": out["generated_tokens"],
            "logit_rel_diff": rel, "kernels": found}


def check_kernels(found: dict) -> None:
    """Every kernel of :data:`STEP_KERNELS` is compiled into its step."""
    for step, names in STEP_KERNELS.items():
        for name in names:
            _check(found[step].get(name, 0) > 0,
                   f"{name} is not a tpu_custom_call in the compiled "
                   f"{step} step (it fell back to XLA)")


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.kernels import ops
    from repro.launch import compile_cache
    if ops.INTERPRET:
        print("[smoke] FAIL: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    print(f"[smoke] compile cache: {compile_cache.enable()}", flush=True)
    try:
        check_kernels(run_smoke(SERVE_ARGV)["kernels"])
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats()
    print(f"[smoke] peak HBM: {stats['peak_bytes_in_use']} bytes "
          f"({stats['peak_bytes_in_use'] / 2**30:.3f} GiB) of "
          f"{stats.get('bytes_limit')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
