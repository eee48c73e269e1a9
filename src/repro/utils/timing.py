"""Lightweight wall-clock instrumentation.

The paper reports drain time, transfer time, blocking checkpoint time and
total persist time separately (§4.2–4.5); every CRUM phase here is timed so
benchmarks can reproduce those splits.

This module is the one instrumentation point for spans inside a process:
every :meth:`Timings.measure` is also a span of the same name in the
``jax.profiler`` trace (on the device trace's clock), and :func:`span` is
that span alone, for code that holds no ``Timings``. Spans are recorded
only while a profiler session runs. Causal spans that cross processes
(fork-persist child, proxy, cluster workers) go to ``repro.obs.trace``'s
JSONL shards instead.
"""
from __future__ import annotations

import sys
import time
from collections import defaultdict
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field

_OFF = nullcontext()


def span(name: str, **args):
    """A span ``name`` in the ``jax.profiler`` trace, with ``args`` (counts
    such as ``bytes=``) attached to it.

    Without a profiler session it costs a dictionary lookup and a flag
    test. A process that has not imported JAX has no session, and this
    imports nothing.
    """
    prof = sys.modules.get("jax._src.profiler")
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return _OFF
    return prof.TraceAnnotation(name, **args)


@dataclass
class Timings:
    """Accumulates named durations (seconds)."""

    totals: dict[str, float] = field(default_factory=lambda: defaultdict(float))
    counts: dict[str, int] = field(default_factory=lambda: defaultdict(int))

    def add(self, name: str, seconds: float) -> None:
        self.totals[name] += seconds
        self.counts[name] += 1

    def mean(self, name: str) -> float:
        c = self.counts.get(name, 0)
        return self.totals[name] / c if c else 0.0

    def summary(self) -> dict[str, dict[str, float]]:
        return {
            k: {"total_s": self.totals[k], "count": self.counts[k], "mean_s": self.mean(k)}
            for k in sorted(self.totals)
        }

    @contextmanager
    def measure(self, name: str, **args):
        """Times the block under ``name``; it is also :func:`span` ``name``
        with ``args``."""
        with span(name, **args):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)


class Timer:
    """Context manager returning elapsed seconds via ``.elapsed``."""

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        self.elapsed = 0.0
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed = time.perf_counter() - self._t0
