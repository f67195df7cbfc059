"""Named spans on the JAX profiler's clock.

``span(name, **stats)`` returns the :class:`jax.profiler.TraceAnnotation`
``repro.<name>``.  It is inert unless a profiler session is active
(``jax.profiler.trace(dir)``); then it is written into the same
``.xplane.pb`` as the device operations, with ``stats`` (integers) as event
stats, so each device gap can be matched to the host work around it.  Counts
known only at the end of a span go on with ``set_metadata(**stats)``.

Spans go per phase, never per instance.  ``SPANS`` is the catalogue: span
name -> the stats it carries.  The device side names its operations with
``jax.named_scope`` instead: ``precompute`` (draws and the effective-cost
gather) and ``event_core`` (the sequential core and its max / mean
epilogue) in the batched event program.

A profiler session needs JAX, so until JAX is imported every span is a
no-op and nothing imports JAX for it.
"""

from __future__ import annotations

import sys

SPANS = {
    # sim/campaign.py ReplayBatch.step and its phases
    "replay.step": ("t", "lanes"),
    "replay.decide": ("requests",),
    "replay.learn": ("lanes",),
    # sim/backends/jax_batched.py
    "backend.lockstep": ("instances",),
    "backend.batch": ("instances",),
    "backend.host_instances": ("closed", "event"),
    "events.rows": ("sched_hits", "sched_misses", "steal_hits",
                    "steal_misses"),
    "sched.build": ("kind",),           # 0 central, 1 steal, 2 weighted
    # live: the slots of the precompute's tiles that some chunk reaches;
    # tile_rows: the lanes of one tile (8, or 1 for long schedules)
    "events.pack": ("K", "rows", "real", "chunks", "live", "tile_rows"),
    # with the grid stack's shape, the program's shapes
    "events.dispatch": ("P", "K", "rows", "grid_rows", "grid_cols"),
    "events.wait": ("rows",),
    # core/simpolicy.py, sim/whatif.py
    "simpolicy.decide": ("candidates",),
    "whatif.price": ("cached",),
}


class _Off:
    """The span used before JAX is imported: no profiler can be on."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats) -> None:
        pass


_OFF = _Off()
_annotation = None


def span(name: str, **stats):
    """The profiler annotation ``repro.<name>`` carrying ``stats``."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return _OFF
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation("repro." + name, **stats)
