"""Runtime-assisted retrace/transfer detection (DESIGN.md §10).

Three instruments, all cheap enough to wrap real serving code:

  * ``compile_watch()`` -- compile-cache instrumentation: flips
    ``jax_log_compiles`` and captures the "Compiling <name> ..." records
    jax's dispatch/pxla loggers emit once per (program, shape) compile.
    A jit cache hit emits nothing, so a steady-state region that compiles
    ANYTHING is a retrace by definition -- content-dependent shapes,
    unhashable statics and fresh-function-per-call bugs all surface here.
  * ``transfer_watch()`` -- ``jax.transfer_guard`` wiring plus the
    planned-fetch budget.  Implicit host->device transfers raise under
    the guard on every backend.  Implicit device->host conversions are
    NOT interceptable from Python on the CPU backend (jaxlib's ArrayImpl
    serves numpy through the C buffer protocol, and host-resident buffers
    make the d2h guard a no-op), so the d2h side is enforced by
    construction instead: every PLANNED fetch on the hot path goes
    through ``device_fetch`` (the one sanctioned spelling, budgeted by
    lint rule ANA006), the watcher counts those, and the serve gate
    asserts the count matches the drain's exact retire budget.  Anything
    pulled outside ``device_fetch`` is a lint violation (ANA005); on a
    real TPU backend the same ``transfer_guard`` wiring additionally
    raises on it at runtime.
  * ``gc_watch()`` -- the interpreter's garbage-collection pauses: counts
    and seconds per generation, each pause a ``gc.gen<N>`` profiler
    annotation, so a trace names the host gaps the collector causes.

``device_fetch`` lives here -- importable by ``core``/``serving`` without
cycles (this module depends only on jax + stdlib).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import logging
import threading
import time
from typing import Dict, Iterator, List

import jax

# Loggers that emit one WARNING record per actual compilation (with
# ``jax_log_compiles``): "Compiling <fn> ..." for every lowered program,
# jit and shard_map alike.
_COMPILE_LOGGERS = (
    "jax._src.interpreters.pxla",
    "jax._src.dispatch",
)
_COMPILE_PREFIXES = ("Compiling ",)

_fetch_count_lock = threading.Lock()
_fetch_count = 0


def device_fetch(value):
    """The sanctioned device->host fetch (DESIGN.md §10).

    Semantically ``jax.device_get`` -- numpy arrays and pytrees pass
    through -- but counted, so the runtime gate can assert that a
    steady-state drain performs EXACTLY its planned number of fetches and
    nothing more.  Hot-path code must use this (or ``jax.device_get``)
    instead of ``np.asarray``/``int()`` on device values; lint rule
    ANA006 requires each call site to carry an allowlist entry naming its
    budget.
    """
    global _fetch_count
    with _fetch_count_lock:
        _fetch_count += 1
    return jax.device_get(value)


def fetch_count() -> int:
    """Total ``device_fetch`` calls this process (monotonic counter)."""
    return _fetch_count


@dataclasses.dataclass
class CompileRecord:
    logger: str
    message: str


class CompileWatch:
    """Captured compile events; ``count`` == number of programs compiled."""

    def __init__(self) -> None:
        self.records: List[CompileRecord] = []

    @property
    def count(self) -> int:
        return len(self.records)

    def messages(self) -> List[str]:
        return [r.message for r in self.records]


class _CaptureHandler(logging.Handler):
    def __init__(self, watch: CompileWatch, logger_name: str) -> None:
        super().__init__(level=logging.DEBUG)
        self._watch = watch
        self._logger_name = logger_name

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith(_COMPILE_PREFIXES):
            self._watch.records.append(
                CompileRecord(self._logger_name, msg.split("\n", 1)[0])
            )


@contextlib.contextmanager
def compile_watch() -> Iterator[CompileWatch]:
    """Capture every compilation inside the block.

    Zero records over a region means every program the region ran was
    already in the jit cache -- the steady-state contract.  The handler
    swallows the records (propagation off) so gated serving loops do not
    spray WARNINGs to stderr.
    """
    watch = CompileWatch()
    prev_flag = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    attached = []
    for name in _COMPILE_LOGGERS:
        logger = logging.getLogger(name)
        handler = _CaptureHandler(watch, name)
        logger.addHandler(handler)
        attached.append((logger, handler, logger.propagate, logger.level))
        logger.propagate = False
        if logger.level > logging.WARNING or logger.level == logging.NOTSET:
            logger.setLevel(logging.WARNING)
    try:
        yield watch
    finally:
        for logger, handler, propagate, level in attached:
            logger.removeHandler(handler)
            logger.propagate = propagate
            logger.setLevel(level)
        jax.config.update("jax_log_compiles", prev_flag)


@dataclasses.dataclass
class TransferWatch:
    """Fetches observed (via ``device_fetch``) inside a ``transfer_watch``."""

    fetches_before: int = 0

    @property
    def fetches(self) -> int:
        return fetch_count() - self.fetches_before


@contextlib.contextmanager
def transfer_watch() -> Iterator[TransferWatch]:
    """Forbid implicit transfers; count sanctioned fetches.

    Implicit host->device raises immediately (every backend).  Implicit
    device->host raises on backends with device-resident buffers (TPU/GPU)
    -- on CPU it is physically free and invisible, which is exactly why
    planned fetches must route through ``device_fetch`` (counted here) and
    implicit pulls are a STATIC lint violation (ANA005).  Explicit
    ``jax.device_put`` / ``jax.device_get`` stay legal under "disallow":
    the contract bans *unplanned* movement, not movement.
    """
    watch = TransferWatch(fetches_before=fetch_count())
    with jax.transfer_guard_host_to_device("disallow"), \
            jax.transfer_guard_device_to_host("disallow"):
        yield watch


@dataclasses.dataclass
class GCWatch:
    """Collections seen inside a ``gc_watch``, per generation."""

    collections: Dict[int, int] = dataclasses.field(default_factory=dict)
    seconds: Dict[int, float] = dataclasses.field(default_factory=dict)

    @property
    def total_s(self) -> float:
        return sum(self.seconds.values())


@contextlib.contextmanager
def gc_watch() -> Iterator[GCWatch]:
    """Count and time every garbage collection inside the block.

    A ``gc.callbacks`` hook, registered for the block only, times each
    collection and wraps it in a ``gc.gen<N>`` ``TraceAnnotation``, which
    records only while a profiler trace is active.
    """
    watch = GCWatch()
    started = []  # (t0, annotation) of the collection under way

    def hook(phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            annotation = jax.profiler.TraceAnnotation(f"gc.gen{gen}")
            annotation.__enter__()
            started.append((time.perf_counter(), annotation))
        elif started:  # a "stop" whose "start" came before the block is dropped
            t0, annotation = started.pop()
            dt = time.perf_counter() - t0
            annotation.__exit__(None, None, None)
            watch.collections[gen] = watch.collections.get(gen, 0) + 1
            watch.seconds[gen] = watch.seconds.get(gen, 0.0) + dt

    gc.callbacks.append(hook)
    try:
        yield watch
    finally:
        gc.callbacks.remove(hook)
