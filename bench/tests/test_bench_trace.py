"""Work functions against hand counts, the peak table, and the reduction
from a trace to metrics on a small trace recorded on a TPU v5e."""
import gzip
import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import roofline, trace, work  # noqa: E402
from harness.cells import Benchmark  # noqa: E402
from harness.peaks import peaks  # noqa: E402

FAMILY = Benchmark().family("qwen_dense")
QWEN25 = FAMILY.dims(Benchmark().config("qwen2.5-3b"))


def test_mixed_matmul_hand_count():
    # K=N=2048: 384 salient rows (0.2 of 2048 rounded to 128), 1664 binary
    flops, nbytes = work.mixed_matmul(4, 2048, 2048, 0.2, 128)
    assert flops == 2 * 4 * 2048 * 2048
    weights = 384 * 2048 / 2 + 1664 * 2048 / 8 + 2 * (2 * 384 + 2048 + 1664)
    assert weights == 828160
    assert nbytes == weights + 2 * 4 * (2048 + 2048)


def test_paged_attention_hand_count():
    flops, nbytes = FAMILY.paged_attention(QWEN25, [100, 200])
    assert flops == 4 * 16 * 128 * 300 * 36
    # 1 KiB of K+V per token per layer (2 heads of 128, bf16), q and out
    assert nbytes == (300 * 1024 + 2 * 2 * 2 * 16 * 128) * 36


def test_paged_prefill_hand_count():
    flops, nbytes = FAMILY.paged_prefill(QWEN25, 256, 100)
    keys = 100 * 256 + 100 * 101 / 2
    assert flops == 4 * 16 * 128 * keys * 36
    assert nbytes == ((256 + 200) * 1024 + 2 * 2 * 100 * 16 * 128) * 36


def test_decode_token_flops_counts_head_and_context():
    lin = (2048 * 2560 + 2048 * 2048 + 2048 * 22016 + 11008 * 2048) * 36
    assert FAMILY.decode_token_flops(QWEN25, 10) == \
        2 * (lin + 2048 * 151936) + 4 * 16 * 128 * 10 * 36


def test_roofline_share_names_its_bound():
    pk = peaks("TPU v5 lite")
    pct, bound = roofline.share(197e12, 1.0, 2.0, pk)
    assert bound == "compute" and pct == pytest.approx(50.0)
    pct, bound = roofline.share(1.0, 819e9, 4.0, pk)
    assert bound == "memory" and pct == pytest.approx(25.0)
    assert roofline.share(1.0, 1.0, 0.0, pk) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v99")


def test_base_name_and_union():
    assert trace.base_name("mixed_matmul.12") == "mixed_matmul"
    assert trace.base_name("fusion.3.1") == "fusion"
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.total(trace.clip([(0, 10)], 2, 5)) == 3


DATA = BENCH / "tests" / "data" / "trace_v5e_batch_decode.json.gz"


@pytest.fixture(scope="module")
def recorded():
    """Two decode steps of qwen2.5-3b.batch_decode, recorded on a TPU v5e
    (64 slots), with the benchmark's host spans, times in ns from the
    cut's start."""
    from harness import driver
    plain = json.load(gzip.open(DATA))
    red = {"plain": plain,
           "devices": [trace.DeviceTrace(plain["devices"]["/device:TPU:0"])]}
    busy, window, breakdown = driver._device_summary(red)
    red["busy_s"], red["window_s"] = busy, window
    return plain, red, breakdown


def _ctx(red, stamps=(-0.5, 0.3, 0.6), family=FAMILY):
    from harness import driver, e2e
    w = driver.Window(t0=0.0, t1=1.0)
    for i in range(64):            # two decoded tokens per slot, ctx 301/302
        w.records[i] = e2e.Record(due=-1.0, prompt_len=300,
                                  stamps=list(stamps))
    return driver.Context(dims=QWEN25, family=family,
                          spec=Benchmark().config("qwen2.5-3b"),
                          window=w, engine_metrics=None, trace=red,
                          peaks=peaks("TPU v5 lite"))


def test_decode_executions_are_found_by_their_kernel(recorded):
    plain, red, _ = recorded
    dev = red["devices"][0]
    ex = dev.executions("paged_attention")
    steps = [m for m in plain["devices"]["/device:TPU:0"]["modules"]
             if m[0].startswith("jit__unknown")]
    assert len(ex) == len(steps) == 2
    assert dev.executions("paged_prefill") == []
    assert dev.kernel_time("paged_attention", ex)[1] == 2 * 36
    assert dev.kernel_time("mixed_matmul", ex)[1] == 2 * 4 * 36


def test_kernel_time_is_the_sum_of_its_events(recorded):
    plain, red, _ = recorded
    ops = plain["devices"]["/device:TPU:0"]["ops"]
    for kernel in ("paged_attention", "mixed_matmul"):
        want = sum(d for n, s, d in ops if n.split(".")[0] == kernel)
        got, _ = red["devices"][0].kernel_time(kernel)
        assert got == pytest.approx(want)
    assert red["devices"][0].kernel_time("paged_attention")[0] == \
        pytest.approx(147668815.0)


def test_device_busy_idle_and_breakdown(recorded):
    _, red, breakdown = recorded
    assert red["window_s"] == pytest.approx(0.287260271)
    assert red["busy_s"] == pytest.approx(0.27542861)
    assert red["busy_s"] < red["window_s"]
    names = [n for n, _ in breakdown["device_ops"]]
    assert names[:4] == ["paged_attention", "copy", "slice_bitcast_fusion",
                         "mixed_matmul"]
    assert breakdown["device_ops"][0][1] == pytest.approx(0.147668815)
    assert len(breakdown["idle_gaps"]) == 10
    assert breakdown["idle_gaps"][0] == ["bench.tick",
                                         pytest.approx(0.005714688)]


def test_metric_readers_on_the_recorded_trace(recorded):
    _, red, _ = recorded
    bench = Benchmark()
    ctx = _ctx(red)
    read = lambda name: bench.metric_reader(name)(ctx)
    assert read("decode_step_ms") == pytest.approx(
        (137.355881 + 137.5289) / 2)
    assert read("device_idle_share") == pytest.approx(
        100 * (1 - 0.27542861 / 0.287260271))
    # mixed_matmul: 128 decoded rows over 2 steps, weights read per step
    f1, b1 = FAMILY.decode_matmuls(QWEN25, 1, 0.2, 128)
    f0, b0 = FAMILY.decode_matmuls(QWEN25, 0, 0.2, 128)
    t = 18204912e-9
    want = 100 * max(128 * f1 / 197e12, (2 * b0 + 128 * (b1 - b0)) / 819e9) / t
    assert read("mixed_matmul_roofline") == pytest.approx(want)
    f, b = FAMILY.paged_attention(QWEN25, [301] * 64 + [302] * 64)
    want = 100 * max(f / 197e12, b / 819e9) / 147668815e-9
    assert read("paged_attention_roofline") == pytest.approx(want)
    flops = sum(FAMILY.decode_token_flops(QWEN25, c)
                for c in [301] * 64 + [302] * 64)
    wall = (281260271.0 - 2000000.0) * 1e-9
    assert read("step_mfu") == pytest.approx(100 * flops / wall / 197e12)
    for name in ("mixed_matmul_roofline", "paged_attention_roofline",
                 "step_mfu"):
        assert 0 < read(name) < 100


DECODE_READERS = ("mixed_matmul_roofline", "paged_attention_roofline",
                  "step_mfu")


def test_readers_take_the_work_from_the_context_family(recorded):
    """The decode path's rooflines and ``step_mfu`` count the work through
    ``ctx.family``: a family that counts three times the dense work reads
    three times as high on the same trace."""
    _, red, _ = recorded
    times3 = lambda got: tuple(3 * x for x in got)  # noqa: E731
    family = types.SimpleNamespace(
        decode_matmul_work=lambda ctx, steps, rows: times3(
            FAMILY.decode_matmul_work(ctx, steps, rows)),
        decode_attention_work=lambda ctx, rows: times3(
            FAMILY.decode_attention_work(ctx, rows)),
        decode_step_flops=lambda ctx, rows: 3 * FAMILY.decode_step_flops(
            ctx, rows))
    bench = Benchmark()
    for name in DECODE_READERS:
        read = bench.metric_reader(name)
        assert read(_ctx(red, family=family)) == \
            pytest.approx(3 * read(_ctx(red))), name


def test_readers_count_only_the_traced_decode_steps(recorded):
    """Where the trace stops before the window's end (the profiler's cap),
    the decode readers count the rows of the traced steps only: a third
    decode tick that the trace does not hold leaves each reading as it
    is."""
    _, red, _ = recorded
    cut = _ctx(red, stamps=(-0.5, 0.3, 0.6, 0.9))
    full = _ctx(red)
    assert len(cut.decode_rows()) == 3 * 64
    assert cut.decode_rows(2) == full.decode_rows() == full.decode_rows(2)
    assert cut.decode_rows(3) == cut.decode_rows(9) == cut.decode_rows()
    bench = Benchmark()
    for name in DECODE_READERS:
        read = bench.metric_reader(name)
        assert read(cut) == read(full), name


def test_metric_modules_agree_with_benchmark_json():
    bench = Benchmark()
    import importlib.util
    for m in bench.spec["per_layer"]:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec_ = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(mod)
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.MOVES) == \
            (m["layer"], m["unit"], m["better"], m["moves"]), m["name"]


CHAT = BENCH / "tests" / "data" / "trace_v5e_chat.json.gz"


def test_chunk_and_decode_steps_of_a_chat_tick():
    """One tick of Qwen2.5-3B serving 32 slots, recorded on a TPU v5e: a
    256-token prefill chunk, then the decode step.  The chunk program is
    found, timed and held against its roofline by the harness alone."""
    from harness import driver
    from harness.roofline import CHUNK, kernel_seconds, program_spans, share
    plain = json.load(gzip.open(CHAT))
    dev = trace.DeviceTrace(plain["devices"]["/device:TPU:0"])
    red = {"plain": plain, "devices": [dev]}
    busy, window, breakdown = driver._device_summary(red)
    red["busy_s"], red["window_s"] = busy, window
    chunk, decode = dev.executions("paged_prefill"), \
        dev.executions("paged_attention")
    assert len(chunk) == len(decode) == 1 and chunk[0][1] < decode[0][0]
    assert dev.kernel_time("paged_prefill", chunk) == (5028554.0, 36)
    assert dev.kernel_time("mixed_matmul", chunk) == (26240487.0, 144)
    assert dev.kernel_time("mixed_matmul", decode) == (8410660.0, 144)
    assert breakdown["device_ops"][0] == ["mixed_matmul",
                                         pytest.approx(0.034651147)]
    ctx = _ctx(red)
    ctx.window.chunks.append((0.0, 512, 256))
    bench = Benchmark()
    assert dev.op_time_in(program_spans(ctx, CHUNK)) == \
        [pytest.approx(44762737.0)]
    assert bench.metric_reader("decode_step_ms")(ctx) == \
        pytest.approx(40.756949)
    secs, calls = kernel_seconds(ctx, "paged_prefill", CHUNK)
    assert (secs, calls) == (pytest.approx(5028554e-9), 36)
    f, b = FAMILY.paged_prefill(QWEN25, 512, 256)
    want = 100 * max(f / 197e12, b / 819e9) / 5028554e-9
    assert share(f, b, secs, ctx.peaks)[0] == pytest.approx(want)
