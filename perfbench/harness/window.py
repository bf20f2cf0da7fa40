"""The measured window and what it prints about itself.

The window opens with ``block_until_ready`` on the state after warm-up and
closes with ``block_until_ready`` on the last step's outputs. In between,
steps are dispatched back to back, and the step metrics come back in one
``device_get`` per log window, as ``repro.train.train()`` does; there is no
per-step sync. The fetch of a log window's metrics comes after the next
log window is dispatched, so the device always holds a log window of
queued steps while the host waits: a late wake-up of the host (up to
0.76 s seen on the chip) then costs no device time. The rate is every
token of every step over the wall time between the two syncs.

Python's collector runs inside the window as it does in ``train()``; its
passes and the longest of them are counted. Compilations are counted with
JAX's compile-time listener, and the longest host wait at a log-window
sync is kept.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import jax
import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileCounter:
    """Records every backend compile (a persistent-cache load included) as
    (function, seconds, from_cache); ``count`` is those while active."""

    def __init__(self):
        self.count = 0
        self.active = False
        self.log: list[tuple] = []
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == CACHE_HIT_EVENT:        # comes inside the compile event
            self._hit = True
        if event != COMPILE_EVENT:
            return
        self.log.append((kw.get("fun_name", "?"), duration, self._hit))
        self._hit = False
        if self.active:
            self.count += 1


@dataclasses.dataclass
class WindowResult:
    steps: int
    seconds: float
    losses: np.ndarray
    compiles: int
    longest_sync_s: float
    gc_collections: int
    longest_gc_s: float
    state: object


def _annotate(name):
    return jax.profiler.TraceAnnotation(name)


def _fetch(pending, losses) -> float:
    """One device_get of a log window's losses; returns the wait."""
    t = time.perf_counter()
    with _annotate("wait"):
        losses.extend(jax.device_get(pending))
    return time.perf_counter() - t


def run_window(step, state, batches, *, seconds: float, log_every: int,
               counter: CompileCounter, max_steps: int | None = None,
               first: int = 0, annotate: bool = False) -> WindowResult:
    """Dispatch log windows of ``log_every`` steps until ``seconds`` have
    passed (or ``max_steps`` steps), cycling through ``batches`` from
    index ``first``. ``annotate`` wraps the timed part in a ``window``
    span for the profiler."""
    passes, started = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            passes.append(time.perf_counter() - started[0])
    gc.callbacks.append(on_gc)
    counter.count, counter.active = 0, True
    losses, n, longest = [], 0, 0.0
    try:
        jax.block_until_ready(state)
        span = _annotate("window") if annotate else contextlib.nullcontext()
        span.__enter__()
        t0 = time.perf_counter()
        previous = None
        while True:
            pending = []
            with _annotate("dispatch"):
                for _ in range(log_every):
                    state, metrics = step(state,
                                          batches[(first + n) % len(batches)])
                    pending.append(metrics.loss)
                    n += 1
            if previous is not None:
                longest = max(longest, _fetch(previous, losses))
            previous = pending
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds or (max_steps and n >= max_steps):
                break
        longest = max(longest, _fetch(previous, losses))
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
        span.__exit__(None, None, None)
    finally:
        counter.active = False
        gc.callbacks.remove(on_gc)
    return WindowResult(n, elapsed, np.asarray(losses, np.float64),
                        counter.count, longest, len(passes),
                        max(passes, default=0.0), state)
