"""Host-clock spans around the program's public calls, and compile events.

``Spans.wrap(obj, "method", "Name")`` replaces the method on that one
instance with a wrapper that records ``(name, start, end)`` on the host's
``perf_counter`` clock and, while a device trace is on, writes the same
interval into the profiler's trace as ``bench.<name>``.  Every wrapped call
returns host values (numpy arrays, decisions, reports), so the span ends when
the work it started has ended.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Spans:
    def __init__(self, traced: bool = False):
        self.traced = traced
        self.records: List[Tuple[str, float, float]] = []
        self.on = True

    def wrap(self, obj, attr: str, name: str,
             after: Optional[Callable] = None) -> None:
        """Record every call of ``obj.attr``; ``after(args, kwargs, out)``
        sees each call's inputs and result (the output checks use it)."""
        inner = getattr(obj, attr)
        rec = self.records
        if self.traced:
            import jax

            label = functools.partial(jax.profiler.TraceAnnotation,
                                      f"bench.{name}")
        else:
            label = contextlib.nullcontext

        def call(*args, **kwargs):
            t0 = time.perf_counter()
            with label():
                out = inner(*args, **kwargs)
            t1 = time.perf_counter()
            if self.on:
                rec.append((name, t0, t1))
            if after is not None:
                after(args, kwargs, out)
            return out

        setattr(obj, attr, call)

    def total(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> float:
        """Seconds spent in spans called ``name`` that lie inside [lo, hi]."""
        return sum(min(t1, hi) - max(t0, lo) for n, t0, t1 in self.records
                   if n == name and t1 > lo and t0 < hi)


class Compiles:
    """Backend compile events on the host clock (``jax.monitoring``)."""

    def __init__(self):
        self.events: List[Tuple[float, float]] = []

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), secs))

    def between(self, lo: float, hi: float) -> Dict[str, float]:
        inside = [s for t, s in self.events if lo <= t <= hi]
        return {"n": len(inside), "s": sum(inside)}

    def install(self) -> "Compiles":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self
