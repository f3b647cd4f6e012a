"""Named host spans on the profiler's clock.

`span(name)` is a `jax.profiler.TraceAnnotation`. With no trace running it
costs well under a microsecond; under `jax.profiler.trace` it is written on
the host plane, on the same clock as the device's operations, so a reader
can tell what the host was doing while the chip was idle. A span adds no
dispatch and no host sync. The names, from outer to inner:

    router.tick      InProcessRouter.step
    router.route     mail, deadline sweep, probes and dispatch, steal,
                     failover: the tick before its engines step
    engine.step      Engine.step
    replica.admit    ReplicaCore.begin_step: admission and packed prefill
    backend.prefill  JaxPagedBackend.prefill_batch: packing, upload, dispatch
    backend.sync     the one blocking device-to-host read of a prefill round
                     or a decode step
    replica.finish   ReplicaCore.finish_step: the batch's decode, reaping
    backend.decode   JaxPagedBackend.decode / decode_many
    backend.upload   JaxPagedBackend._sync_slots: the batch-state upload
    engine.stream    token drain and finish callbacks
    host.gc          a Python garbage collection of generation 1 or 2

Spans carry no engine suffix: a reader assigns one to an engine by what
encloses it. `replica/core.py` stays free of JAX (the simulator shares it),
so the engine opens the `replica.*` spans around its calls into the core.
"""
from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation


def span(name: str) -> TraceAnnotation:
    return TraceAnnotation(name)


_gc_open: list = []


def _on_gc(phase: str, info: dict) -> None:
    if info["generation"] == 0:
        return
    if phase == "start":
        s = TraceAnnotation("host.gc")
        s.__enter__()
        _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def install_gc_span() -> None:
    """Open `host.gc` around every collection of generation 1 or 2, once
    per process (a collection's start and stop run on one thread, with no
    other collection between them)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
