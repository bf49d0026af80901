"""Wall-clock phase timers for the control plane and the replay.

``PhaseTimers`` wraps each control-plane phase (rollout / detect /
forecast / plan / verify) in a ``with timers.phase(name):`` block and
keeps two ledgers: lifetime totals/counts (for end-of-run summaries and
the latency bench's ``--timers`` mode) and a per-window scratch dict the
experiment driver drains with ``pop_window()`` into a ``PhaseTimings``
trace event.

Timers are always on — one ``perf_counter`` pair and two dict updates per
phase per window is noise next to a jit'd rollout slice — so the
zero-overhead split applies only to the *event emission*, which happens
solely when a recorder is attached.

Each phase can also be a span on the profiler's clock: give the
constructor a factory ``name -> context manager`` (callers that own jax
pass ``jax.profiler.TraceAnnotation``) and a prefix, and ``phase(name)``
opens ``annotate(f"{prefix}.{name}")`` around its block.  A phase opened
inside another is a child span of it.  The factory is injected because
this package imports no jax.  With the profiler off a ``TraceAnnotation``
adds about 0.1 us to a phase's 2 us (TPU v5e host); still, open phases
per window or per call, never per chunk or per tick, and never inside
traced (jit/scan) code.

Note what a phase time means here: the detector/forecaster/policy phases
include JAX dispatch and (on first call) compilation, so the first
window's numbers are dominated by jit warm-up.  ``summary()`` reports
mean over *all* calls; read long runs, not single windows.
"""
from __future__ import annotations

import contextlib
import time


class PhaseTimers:
    """Named wall-clock accumulators with per-window drain, each phase
    optionally a span named ``{prefix}.{name}`` on the profiler's clock."""

    def __init__(self, prefix: str = "", annotate=None):
        self.prefix = prefix
        self._annotate = annotate
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self._window: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        span = (contextlib.nullcontext() if self._annotate is None
                else self._annotate(f"{self.prefix}.{name}"))
        with span:
            start = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - start
                self.totals[name] = self.totals.get(name, 0.0) + dt
                self.counts[name] = self.counts.get(name, 0) + 1
                self._window[name] = self._window.get(name, 0.0) + dt

    def pop_window(self) -> dict[str, float]:
        """Return and clear the seconds accumulated since the last pop."""
        w = self._window
        self._window = {}
        return w

    def summary(self) -> dict[str, dict]:
        """Per-phase ``{total_s, calls, mean_ms}`` over the whole run."""
        return {
            name: {
                "total_s": total,
                "calls": self.counts.get(name, 0),
                "mean_ms": 1e3 * total / max(self.counts.get(name, 0), 1),
            }
            for name, total in self.totals.items()
        }
