"""The engine's host spans in the benchmark: ``host_tick_ms`` and the idle
split by span, on hand-made intervals, on a trace recorded here on the
CPU, and on a window of ``qwen2.5-3b.batch_decode`` recorded on a TPU v5e
with the engine's spans."""
import gzip
import json
import os
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import driver, host_spans, trace  # noqa: E402
from harness.cells import Benchmark  # noqa: E402

DATA = BENCH / "tests" / "data"


def _ctx(red):
    return driver.Context(dims=None, spec=None, window=None,
                          engine_metrics=None, trace=red, peaks=None)


def _reader():
    return Benchmark().metric_reader("host_tick_ms")


def _tick(t0, chunk=False, readback=60.0):
    """The spans of one tick cycle at ``t0`` (ns): the tick and its
    phases, then the client's drain."""
    h = [["bench.tick", t0 - 1, 102], ["engine.tick", t0, 100],
         ["engine.grow", t0 + 1, 2], ["engine.admit", t0 + 3, 2]]
    if chunk:
        h.append(["engine.prefill_chunk", t0 + 5, 10])
    h += [["engine.decode", t0 + 20, 5], ["engine.sample", t0 + 25, 2],
          ["engine.readback", t0 + 27, readback],
          ["engine.emit", t0 + 27 + readback, 100 - 27 - readback - 1],
          ["bench.drain", t0 + 102, 5]]
    return h


def test_host_tick_is_the_tick_less_its_readback():
    host = [["bench.window", 0, 1000]]
    host += _tick(10, readback=60)
    host += _tick(130, chunk=True, readback=50)
    host += _tick(250, readback=70)
    # a pure-prefill tick (no decode) and a tick outside the window
    host += [["engine.tick", 370, 20], ["engine.prefill_chunk", 372, 10],
             ["engine.tick", 1100, 100], ["engine.decode", 1110, 5]]
    assert host_spans.host_tick_ns(host) == [40, 50, 30]
    red = {"plain": {"host": host, "devices": {}}}
    assert _reader()(_ctx(red)) == pytest.approx(40e-6)
    med = host_spans.phase_medians_ms(host)
    assert med["engine.tick"] == pytest.approx(100e-6)
    assert med["engine.readback"] == pytest.approx(60e-6)
    assert med["engine.prefill_chunk"] == 0.0


def test_no_engine_span_gives_no_reading():
    host = [["bench.window", 0, 1000], ["bench.tick", 10, 100],
            ["bench.drain", 111, 5]]
    assert not host_spans.has_engine_spans(host)
    assert host_spans.host_tick_ns(host) == []
    assert host_spans.phase_medians_ms(host) == {}
    assert _reader()(_ctx({"plain": {"host": host, "devices": {}}})) is None


def test_idle_is_split_by_the_innermost_span_over_each_interval():
    host = [["bench.window", 0, 100], ["bench.tick", 10, 50],
            ["engine.tick", 12, 40], ["engine.emit", 20, 10]]
    gaps = [(5, 15), (18, 35), (70, 80), (95, 110)]
    got = dict(host_spans.idle_by_span(gaps, host))
    assert got == {"bench.window": 5 + 10 + 5, "bench.tick": 2,
                   "engine.tick": 3 + 2 + 5, "engine.emit": 10,
                   "none": 10}
    assert sum(got.values()) == trace.total(gaps)


def test_reader_rereads_the_runs_trace_file(tmp_path):
    """The benchmark's reduction keeps only ``bench.*``; the reader reads
    the same run's trace file again for the engine's spans, and leaves a
    file of another run alone."""
    import jax
    span = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with span("bench.window"):
        for _ in range(3):
            with span("bench.tick"), span("engine.tick"):
                with span("engine.decode"):
                    time.sleep(0.002)
                with span("engine.readback"):
                    time.sleep(0.004)
                with span("engine.emit"):
                    time.sleep(0.001)
    jax.profiler.stop_trace()
    xplane = trace.latest_xplane(str(tmp_path))
    plain = trace.load(xplane)
    assert not host_spans.has_engine_spans(plain["host"])
    host = host_spans.load_host(xplane)
    names = sorted({n for n, _, _ in host})
    assert names == ["bench.tick", "bench.window", "engine.decode",
                     "engine.emit", "engine.readback", "engine.tick"]
    ticks = host_spans.host_tick_ns(host)
    assert len(ticks) == 3 and all(2.9e6 < t < 50e6 for t in ticks)
    read = _reader()
    read.__globals__["TRACE_DIR"] = tmp_path
    ctx = _ctx({"plain": plain})
    assert read(ctx) == pytest.approx(sorted(ticks)[1] * 1e-6)
    assert int(ctx.derived["trace_bytes"]) == os.path.getsize(xplane)
    other = {"host": [["bench.window", 1.0, 2.0]], "devices": {}}
    assert read(_ctx({"plain": other})) is None


def test_traced_run_reads_host_tick_ms_here(tmp_path, monkeypatch):
    """A traced window of a tiny cell through the harness's own ``serve``
    on the CPU: the reader finds the run's trace file by the window span
    and reads the engine's ticks from it.  Only the chip checks and the
    device-plane reduction are replaced (no TPU plane exists here)."""
    import jax
    from harness import cells
    from test_bench_harness import TINY, TINY_CHAT

    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (bench_dir / "traffic" / "tiny_chat.json").write_text(
        json.dumps(TINY_CHAT))
    (bench_dir / "metrics" / "host_tick_ms.py").write_text(
        (BENCH / "metrics" / "host_tick_ms.py").read_text())
    spec = {"workloads": [{"name": "tiny.tiny_chat", "config": "tiny",
                           "traffic": "tiny_chat", "chips": 1}],
            "end_to_end": [],
            "per_layer": [{"name": "host_tick_ms", "unit": "ms"}]}
    bench = Benchmark(bench_dir=bench_dir, spec=spec)

    def reduce_host_only(path):
        return {"plain": trace.load(path),
                "devices": [trace.DeviceTrace({"ops": [], "modules": []})]}

    def window_only(red):
        (_, s, d), = [h for h in red["plain"]["host"]
                      if h[0] == "bench.window"]
        red["window_ns"] = (s, s + d)
        return 0.0, d * 1e-9, {"device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(cells, "BENCH_DIR", bench_dir)
    monkeypatch.setattr(driver, "check_kernels", lambda engine: {})
    monkeypatch.setattr(driver, "_reduce_trace", reduce_host_only)
    monkeypatch.setattr(driver, "_device_summary", window_only)
    monkeypatch.setattr(driver, "peaks", lambda kind: None)
    out = driver.serve(bench, spec["workloads"][0], 5, 2.0, True,
                       jax.devices()[:1], time.time())
    ms = out.result["metrics"]["host_tick_ms"]
    assert ms["unit"] == "ms" and 0 < ms["value"] < 2000
    assert not (bench_dir / "out" / "trace").exists()


SPANS = DATA / "trace_v5e_batch_decode_spans.json.gz"


@pytest.fixture(scope="module")
def recorded_spans():
    """Three tick cycles of qwen2.5-3b.batch_decode (64 slots) recorded on
    a TPU v5e with the engine's spans: a tick that runs a prefill chunk
    before the decode, one whose chunk graduates its request (a first
    token sampled and read back) before the decode, and a plain decode
    tick; each cycle from its ``engine.tick`` start to the next one's,
    times in ns from the cut's start, the cut as ``bench.window``."""
    plain = json.load(gzip.open(SPANS))
    dev = trace.DeviceTrace(plain["devices"]["/device:TPU:0"])
    red = {"plain": plain, "devices": [dev]}
    busy, window, breakdown = driver._device_summary(red)
    red["busy_s"], red["window_s"] = busy, window
    return plain, red, breakdown


def test_host_tick_ms_on_the_recorded_ticks(recorded_spans):
    plain, red, _ = recorded_spans
    host = plain["host"]
    dev = red["devices"][0]
    assert len(dev.executions("paged_attention")) == 3
    assert len(dev.executions("paged_prefill")) == 2
    assert host_spans.host_tick_ns(host) == [6520620.0, 7896920.0,
                                             4029340.0]
    assert _reader()(_ctx(red)) == pytest.approx(6.52062)
    med = host_spans.phase_medians_ms(host)
    assert med["engine.decode"] == pytest.approx(1.92734)
    assert med["engine.readback"] == pytest.approx(146.023617)


def test_idle_gaps_carry_engine_spans(recorded_spans):
    _, red, breakdown = recorded_spans
    gaps = breakdown["idle_gaps"]
    assert len(gaps) == 10 and all(n.startswith("engine.") for n, _ in gaps)
    assert gaps[0] == ["engine.readback", pytest.approx(0.00444965)]
    lo, hi = red["window_ns"]
    idle = red["devices"][0].gaps(lo, hi)
    split = dict(host_spans.idle_by_span(idle, red["plain"]["host"]))
    assert sum(split.values()) == pytest.approx(trace.total(idle))
    outside = sum(split.get(n, 0.0)
                  for n in ("bench.tick", "bench.window", "none"))
    assert outside / trace.total(idle) < 0.01
    assert max(split, key=split.get) == "engine.readback"


@pytest.mark.parametrize("name", ["trace_v5e_batch_decode.json.gz",
                                  "trace_v5e_chat.json.gz"])
def test_traces_without_engine_spans_give_no_reading(name):
    plain = json.load(gzip.open(DATA / name))
    dev = trace.DeviceTrace(plain["devices"]["/device:TPU:0"])
    red = {"plain": plain, "devices": [dev]}
    driver._device_summary(red)
    assert not host_spans.has_engine_spans(plain["host"])
    assert _reader()(_ctx(red)) is None
