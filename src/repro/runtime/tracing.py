"""Host spans and process-wide runtime counters for the engine's tick loop.

The engine names its host work inside every tick with :func:`span`
(``jax.profiler.TraceAnnotation``): ``engine.tick`` around the whole
tick, and inside it ``engine.grow``, ``engine.admit``,
``engine.prefill_chunk`` (with the request's ``rid`` and the chunk's
``start`` and ``length``), ``engine.decode``, ``engine.sample``,
``engine.readback`` (the host blocked on a device result) and
``engine.emit``.  Whether they are recorded is decided by whether a
profiler session runs (``jax.profiler.start_trace``); they then land on
the ``/host:CPU`` plane on the same clock as the device planes, so a gap
in the device's timeline can be named by the host work over it.  With no
session running a span costs about a microsecond.

Two process-wide hooks are installed once, however many engines are
built (:func:`install`):

* a ``jax.monitoring`` listener that counts XLA backend compiles (every
  executable JAX builds or loads from the persistent compile cache);
* a ``gc.callbacks`` hook that counts every garbage collection and its
  pause by generation, and opens an ``engine.gc`` span (with its
  ``generation``) over each collection of generation 1 or 2.

:func:`counters` reads their running totals; the engine folds the
difference over each tick into its
:class:`~repro.runtime.metrics.EngineMetrics`.
"""
from __future__ import annotations

import gc
import time
from typing import Optional, Tuple

import jax

span = jax.profiler.TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GC_SPAN_MIN_GENERATION = 1          # generation 0 is too frequent to span

Counters = Tuple[int, Tuple[int, ...], Tuple[float, ...]]

_compiles = 0
_gc_collections = [0] * len(gc.get_count())
_gc_pause_s = [0.0] * len(gc.get_count())
# (start, open span or None) of the collection in progress
_gc_open: Optional[Tuple[float, Optional[span]]] = None
_installed = False


def _on_compile(event: str, duration_secs: float, **_) -> None:
    global _compiles
    if event == COMPILE_EVENT:
        _compiles += 1


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    gen = info["generation"]
    if phase == "start":
        sp = None
        if gen >= GC_SPAN_MIN_GENERATION:
            sp = span("engine.gc", generation=gen)
            sp.__enter__()
        _gc_open = (time.perf_counter(), sp)
    elif _gc_open is not None:
        t0, sp = _gc_open
        _gc_open = None
        if sp is not None:
            sp.__exit__(None, None, None)
        _gc_collections[gen] += 1
        _gc_pause_s[gen] += time.perf_counter() - t0


def install() -> None:
    """Register the compile listener and the gc hook, once per process."""
    global _installed
    if _installed:
        return
    _installed = True
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    gc.callbacks.append(_on_gc)


def counters() -> Counters:
    """(compiles, collections by generation, pause seconds by generation)
    since :func:`install`."""
    return _compiles, tuple(_gc_collections), tuple(_gc_pause_s)
