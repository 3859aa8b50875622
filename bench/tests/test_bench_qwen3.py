"""Qwen3-4B in the benchmark: the program against the plain reference at a
small Qwen3 shape on the CPU, the configuration file against the
program's registry, and the two readers of the chunk program
(``prefill_chunk_ms``, ``paged_prefill_roofline``)."""
import gzip
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import driver, trace  # noqa: E402
from harness.cells import Benchmark  # noqa: E402
from harness.peaks import peaks  # noqa: E402

FAMILY = Benchmark().family("qwen_dense")

# Qwen3's mechanisms at a CPU size: per-head q/k RMSNorm, no QKV bias,
# 8 query / 2 KV heads (4 a group) of 64, so q heads x head_dim (512) is
# not the hidden size (256)
SMALL = {
    "name": "qwen3-small",
    "source": "a reduced Qwen3-style decoder for CPU tests",
    "reference": "qwen_dense",
    "family": "qwen_dense",
    "published": {"hidden_size": 256, "intermediate_size": 512,
                  "num_hidden_layers": 2, "num_attention_heads": 8,
                  "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
                  "rope_theta": 1e6, "tie_word_embeddings": True,
                  "hidden_act": "silu", "vocab_size": 500},
    "architecture": {"head_dim": 64, "qkv_bias": False, "qk_norm": True},
    "init": {"std": 0.1, "bias_std": 0.1, "norm_jitter": 0.1,
             "salient_scale": 2.0},
    "deployment": {"quant_ratio": 0.2, "salient_multiple": 32,
                   "fused_projections": True, "dtype": "bfloat16",
                   "page_size": 16, "prefill_chunk": 64},
}
SEED = 7
PROMPT = 530          # 9 chunks of 64; the context passes 512 tokens
NEW = 12
MAX_SEQ = 640         # 40 pages of 16: two paged_attention compute blocks
# Logits are compared as max |program - reference| over max |reference|
# and as the rms difference over the reference's spread.  The program
# serves bf16 activations and KV over the same packed weights: it reads
# 0.013 / 0.012 at this seed, as does the reference rounded to bf16 alone
# (0.011 / 0.011); the reference rounded to float8 reads 0.34 / 0.31.
# Both limits sit near the geometric mean, nearer the program.
LOGIT_LIMITS = {"max_rel": 0.05, "rms_rel": 0.05}


def _logit_error(got, ref):
    d = got - ref
    return {"max_rel": float(np.abs(d).max() / np.abs(ref).max()),
            "rms_rel": float(np.sqrt(np.mean(d * d)) / ref.std())}


@pytest.fixture(scope="module")
def served():
    """One request through the program's paged engine (chunked prefill,
    both paged kernels in interpret mode, packed weights drawn from the
    seed): its prompt, served tokens, and the program's logits at each
    served position (the final chunk's, then each decode step's)."""
    from repro.models.common import Parallel
    from repro.runtime.engine import Engine
    from repro.runtime.events import TokenEvent

    cfg, params = FAMILY.build_params(SMALL, SEED)
    dep = SMALL["deployment"]
    eng = Engine(cfg, Parallel(), params, n_slots=2, max_seq=MAX_SEQ,
                 paged=True, page_size=dep["page_size"], paged_kernel=True,
                 chunked_prefill=True, prefill_chunk=dep["prefill_chunk"])
    be = eng.backend
    chunk_fn, decode_fn = be.prefill_chunk, be.decode
    chunks, steps = [], []

    def chunk(params, toks, slot, start, length):
        lg = chunk_fn(params, toks, slot, start, length)
        chunks.append(np.asarray(lg[0, :cfg.vocab], np.float32))
        return lg

    def decode(params, toks, pos, active=None):
        lg = decode_fn(params, toks, pos, active)
        steps.append(np.asarray(lg[0, :cfg.vocab], np.float32))
        return lg

    be.prefill_chunk, be.decode = chunk, decode
    prompt = np.random.default_rng(SEED).integers(
        0, cfg.vocab, size=PROMPT).astype(np.int32)
    events = eng.event_queue()
    req = eng.submit(prompt, max_new=NEW)
    while not req.done:
        eng.tick()
    toks = [int(e.token) for e in events if isinstance(e, TokenEvent)]
    logits = np.stack([chunks[-1]] + steps[:len(toks) - 1])
    return eng, prompt, toks, logits, len(chunks)


def test_program_logits_match_the_reference(served):
    """Prefill through several chunks, then decode through the paged pool
    past one compute block: the program's logits at every served
    position against the plain float32 reference's full forward, and
    the float8 reference failing the same limits."""
    eng, prompt, toks, logits, n_chunks = served
    assert n_chunks == -(-PROMPT // 64) and len(toks) == NEW
    ppcb, nblk, _ = eng.backend._attn_blocks
    assert nblk > ppcb and (PROMPT + NEW) > ppcb * 16
    ref = Benchmark().reference("qwen_dense")
    hi = ref.served_logits(SMALL, SEED, [(prompt, toks)])[0]
    got = _logit_error(logits, hi)
    assert all(got[k] <= lim for k, lim in LOGIT_LIMITS.items()), got
    f8 = ref.served_logits(SMALL, SEED, [(prompt, toks)], lowp="f8")[0]
    low = _logit_error(f8, hi)
    assert all(low[k] > lim for k, lim in LOGIT_LIMITS.items()), low


def test_engine_records_its_kernel_tiles(served):
    eng = served[0]
    ppcb, _, _ = eng.backend._attn_blocks
    snap = eng.metrics.snapshot()
    assert snap["attn_ppcb"] == ppcb == 32
    assert snap["prefill_bh"] == 2


def test_qwen3_4b_file_matches_the_program_registry():
    """The configuration file the benchmark serves and the program's own
    ``qwen3-4b`` agree on every shape and on the RoPE base."""
    from repro.configs import registry
    got = FAMILY.arch_config(Benchmark().config("qwen3-4b"))
    want = registry.get("qwen3-4b")
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim_", "d_ff",
                  "vocab", "n_layers", "qkv_bias", "qk_norm",
                  "tied_embeddings", "act", "rope_theta", "stages"):
        assert getattr(got, field) == getattr(want, field), field
    assert want.rope_theta == 1e6 and want.head_dim_ * want.n_heads == 4096
    assert "Qwen3-4B" in want.source


CHAT = BENCH / "tests" / "data" / "trace_v5e_chat.json.gz"
QWEN25 = FAMILY.dims(Benchmark().config("qwen2.5-3b"))


@pytest.fixture(scope="module")
def chat():
    """One tick of Qwen2.5-3B recorded on a TPU v5e: a 256-token chunk at
    position 512, then the decode step."""
    plain = json.load(gzip.open(CHAT))
    dev = trace.DeviceTrace(plain["devices"]["/device:TPU:0"])
    red = {"plain": plain, "devices": [dev]}
    driver._device_summary(red)
    return red


def _ctx(red, em=None):
    return driver.Context(dims=QWEN25, family=FAMILY,
                          spec=Benchmark().config("qwen2.5-3b"),
                          window=driver.Window(), engine_metrics=em,
                          trace=red, peaks=peaks("TPU v5 lite"))


def test_prefill_chunk_ms_on_the_recorded_chat_tick(chat):
    read = Benchmark().metric_reader("prefill_chunk_ms")
    assert read(_ctx(chat)) == pytest.approx(44.762737)


def test_paged_prefill_roofline_reads_the_chunk_spans(chat, tmp_path):
    """The chunk's (start, length) come from the engine's span inside the
    window, read from the run's trace file; a span outside the window
    is left out."""
    import jax
    span = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with span("engine.prefill_chunk", rid=1, start=0, length=256):
        pass
    with span("bench.window"):
        with span("engine.prefill_chunk", rid=2, start=512, length=256):
            pass
    jax.profiler.stop_trace()
    xplane = trace.latest_xplane(str(tmp_path))
    (_, s, d), = [h for h in trace.load(xplane)["host"]
                  if h[0] == "bench.window"]
    red = dict(chat, window_ns=(s, s + d))
    read = Benchmark().metric_reader("paged_prefill_roofline")
    read.__globals__["TRACE_DIR"] = tmp_path
    ctx = _ctx(red, types.SimpleNamespace(attn_ppcb=32, prefill_bh=2))
    f, b = FAMILY.paged_prefill(QWEN25, 512, 256)
    want = 100 * max(f / 197e12, b / 819e9) / 5028554e-9
    assert read(ctx) == pytest.approx(want)
    note = ctx.derived["paged_prefill_roofline"]
    assert note.startswith("compute-bound, 36 calls, 1 chunk steps, "
                           "1 chunk spans, 256 tokens")
    assert note.endswith("ppcb 32, paged_prefill bh 2")


def test_chunk_readers_give_nothing_without_chunks(tmp_path):
    """A window with no chunk step (the recorded decode-only trace), or a
    program whose trace holds no chunk span, gives no reading."""
    decode_only = json.load(gzip.open(BENCH / "tests" / "data"
                                      / "trace_v5e_batch_decode.json.gz"))
    red = {"plain": decode_only, "devices": [trace.DeviceTrace(
        decode_only["devices"]["/device:TPU:0"])]}
    driver._device_summary(red)
    bench = Benchmark()
    for name in ("prefill_chunk_ms", "paged_prefill_roofline"):
        assert bench.metric_reader(name)(_ctx(red)) is None
    plain = json.load(gzip.open(CHAT))
    red = {"plain": plain, "devices": [trace.DeviceTrace(
        plain["devices"]["/device:TPU:0"])]}
    driver._device_summary(red)
    read = bench.metric_reader("paged_prefill_roofline")
    read.__globals__["TRACE_DIR"] = tmp_path          # no trace file
    assert read(_ctx(red)) is None
