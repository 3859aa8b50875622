"""The harness on the CPU: extended from files alone, steered through
short runs of a reduced configuration in Pallas interpret mode, refusing
to run without a chip, and failing its check when the timed path is
broken underneath.

The chip checks (``require_chip``, the ``tpu_custom_call`` kernel check)
and the trace reduction are replaced from here, in the test, since no
TPU exists on this machine; everything else runs as on the chip.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import cells, driver, trace as trace_mod  # noqa: E402
from harness.peaks import peaks  # noqa: E402

TINY = {
    "source": "a reduced Qwen-style decoder for CPU tests",
    "reference": "qwen_dense",
    "published": {"hidden_size": 256, "intermediate_size": 512,
                  "num_hidden_layers": 2, "num_attention_heads": 4,
                  "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
                  "rope_theta": 10000.0, "tie_word_embeddings": True,
                  "hidden_act": "silu", "vocab_size": 500},
    "architecture": {"head_dim": 64, "qkv_bias": True, "qk_norm": True},
    "init": {"std": 0.1, "bias_std": 0.1, "norm_jitter": 0.1,
             "salient_scale": 2.0},
    "deployment": {"quant_ratio": 0.2, "salient_multiple": 32,
                   "fused_projections": True, "dtype": "bfloat16",
                   "page_size": 8, "prefill_chunk": 16},
}
TINY_CHAT = {
    "loop": "open", "rate_per_s": 3.0,
    "prompt": {"dist": "lognormal", "median": 14, "sigma": 0.6,
               "min": 4, "max": 40},
    "output": {"dist": "uniform", "min": 10, "max": 20},
    "slots": 4, "max_seq": 64, "pool_bytes": 1e9, "check_requests": 3,
}
TICKS_METRIC = '''"""Ticks the engine ran in the window (its own per-tick counter)."""
LAYER = "scheduler"
UNIT = "ticks"
BETTER = "higher"
MOVES = "tbt_p50_ms"


def read(ctx):
    return float(len(ctx.engine_metrics.queue_depth) - ctx.window.tick_from)
'''
CELL = "tiny.tiny_chat"
# the mean served-token gap a sound run of the tiny cell may show: sound
# runs read 0 to 0.00096 over eight seeds (20-27), the float8 control
# 0.091 to 0.126 on the same requests
TINY_LIMIT = 0.008


def _digest(root: Path):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and "out" not in p.parts}


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """A copy of the benchmark with one configuration, one traffic mix, one
    per-layer metric and one cell added as new files and entries."""
    tmp = tmp_path_factory.mktemp("bench_ext")
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out",
                                                  ".jax_cache"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = _digest(tmp / "bench")
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (tmp / "bench" / "traffic" / "tiny_chat.json").write_text(
        json.dumps(TINY_CHAT))
    (tmp / "bench" / "metrics" / "ticks_in_window.py").write_text(
        TICKS_METRIC)
    (tmp / "bench" / "limits").mkdir(exist_ok=True)
    (tmp / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": {"mean_logit_gap": TINY_LIMIT}}))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "CPU test"})
    spec["workloads"].append({"name": CELL, "config": "tiny",
                              "traffic": "tiny_chat", "chips": 1,
                              "why": "CPU test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("tbt_p50_ms", "tbt_p95_ms", "setup_s") and \
                "workloads" in m:
            m["workloads"].append(CELL)
    spec["per_layer"].append({"name": "ticks_in_window", "unit": "ticks",
                              "better": "higher", "source": "program_counter",
                              "layer": "scheduler", "moves": "tbt_p50_ms",
                              "workloads": [CELL]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp, before


def _no_trace_plane(path):
    return {"plain": {"devices": {}, "host": []},
            "devices": [trace_mod.DeviceTrace({"ops": [], "modules": []})]}


@pytest.fixture
def steered(extended, monkeypatch):
    """Run the harness here: the chip checks pass, nothing is cached."""
    tmp, _ = extended
    monkeypatch.setattr(cells, "BENCH_DIR", tmp / "bench")
    monkeypatch.setattr(driver, "require_chip",
                        lambda chips: __import__("jax").devices()[:chips])
    monkeypatch.setattr(driver, "check_kernels", lambda engine: {})
    monkeypatch.setattr(driver, "_enable_cache", lambda: "not enabled")
    monkeypatch.setattr(driver, "_reduce_trace", _no_trace_plane)
    monkeypatch.setattr(driver, "peaks",
                        lambda kind: peaks("TPU v5 lite"))
    monkeypatch.setattr(driver, "_device_summary",
                        lambda red: (1e-3, 1.0, {"device_ops": [],
                                                 "idle_gaps": []}))
    return tmp


def _run(capsys, *args):
    rc = driver.run(["--workload", CELL, "--seconds", "3", *args],
                    time.time())
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)


def test_refuses_without_a_chip(extended, monkeypatch, capsys):
    tmp, _ = extended
    monkeypatch.setattr(cells, "BENCH_DIR", tmp / "bench")
    rc, res = _run(capsys, "--seed", "1")
    assert rc != 0 and res is None


def test_refuses_from_bench_files_alone(tmp_path):
    """A checkout of only BENCHMARK.json and bench/ has no program: the
    command exits non-zero and prints no result."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out",
                                                  ".jax_cache"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_extended_cell_runs_from_new_files(steered, extended, capsys):
    tmp, before = extended
    rc, res = _run(capsys, "--seed", str(2 ** 33 + 5), "--trace", "0")
    assert rc == 0
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"tbt_p50_ms", "tbt_p95_ms", "setup_s"}
    assert res["device"]["count"] == 1 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["mean_logit_gap"]["limit"] == TINY_LIMIT
    # the new per-layer metric is found by name in the traced run
    rc, res = _run(capsys, "--seed", "6", "--trace", "1")
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["ticks_in_window"]["value"] > 0
    assert "breakdown" in res
    # nothing that was there before changed
    after = _digest(tmp / "bench")
    assert {k: after[k] for k in before} == before


def _sample_plus_one(orig):
    def bad(logits, key, temps):
        return (orig(logits, key, temps) + 1) % 500
    return bad


def _state_unchanged(orig):
    def bad(*args, **kw):
        logits, _ = orig(*args, **kw)
        return logits, args[5]            # the caches it was given
    return bad


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(steered, monkeypatch, capsys,
                                          fault):
    from repro.models import model as M
    from repro.runtime import engine as E
    if fault == "token_altered":
        monkeypatch.setattr(E, "_sample_batched",
                            _sample_plus_one(E._sample_batched))
    else:
        monkeypatch.setattr(M, "decode_step_paged",
                            _state_unchanged(M.decode_step_paged))
    rc, res = _run(capsys, "--seed", "7", "--trace", "0")
    assert rc == 0
    assert res["correct"] is False
    assert res["checks"]["mean_logit_gap"]["value"] > TINY_LIMIT


def test_control_fails_where_sound_runs_pass(steered):
    """The check's control at the tiny size: on the prompts and tokens a
    sound run served, the reference computed in float8 picks tokens whose
    float32 gap exceeds the limit, while the program's served tokens stay
    within it, on three seeds."""
    import jax
    from harness import check
    bench = cells.Benchmark()
    wl = bench.workload(CELL)
    devices = jax.devices()[:1]
    for seed in (11, 12, 13):
        out = driver.serve(bench, wl, seed, 3.0, False, devices, time.time())
        ref = bench.reference(out.spec["reference"])
        hi = ref.served_logits(out.spec, seed, out.requests)
        lo = ref.served_logits(out.spec, seed, out.requests, lowp="f8")
        program = check.stats(check.served_gaps(
            hi, [s for _, s in out.requests]))["mean_logit_gap"]
        control = check.stats(check.control_gaps(hi, lo))["mean_logit_gap"]
        assert program <= TINY_LIMIT < control, (seed, program, control)


class _Lowered:
    def __init__(self, text):
        self.text = text

    def compile(self):
        return self

    def as_text(self):
        return self.text


class _Engine:
    params = None

    def __init__(self, texts):
        self.backend = self
        self.texts = texts

    def lowered_steps(self, params):
        return {k: _Lowered(v) for k, v in self.texts.items()}


def _hlo(*kernels):
    return "\n".join(
        f'  %{k}.{i} = f32[8]{{0}} custom-call(%p), '
        f'custom_call_target="tpu_custom_call"' for i, k in enumerate(kernels))


def test_kernel_check_refuses_a_step_that_fell_back_to_xla():
    ok = _Engine({"decode": _hlo("mixed_matmul", "paged_attention"),
                  "prefill_chunk": _hlo("mixed_matmul", "paged_prefill")})
    assert driver.check_kernels(ok)["decode"] == {"mixed_matmul": 1,
                                                  "paged_attention": 1}
    fell_back = _Engine({"decode": _hlo("mixed_matmul"),
                         "prefill_chunk": _hlo("mixed_matmul",
                                               "paged_prefill")})
    with pytest.raises(driver.KernelMissing, match="paged_attention"):
        driver.check_kernels(fell_back)


def test_interpret_mode_is_refused(monkeypatch):
    """A TPU platform with Pallas in interpret mode is still no chip."""
    import jax
    from repro.kernels import ops

    class Dev:
        platform = "tpu"
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(ops, "INTERPRET", True)
    with pytest.raises(driver.NoChip, match="interpret"):
        driver.require_chip(1)
