"""Public wrapper around the Pallas event kernel.

On CPU the kernel executes in interpret mode — the kernel body runs in
Python with real block indexing, which is what ``tests/test_event_kernel.py``
validates.  On TPU backends the same call compiles via Mosaic
(``tests/test_chip_compile.py`` compiles the event kernel for a v5e).
"""

from __future__ import annotations

import jax

from .event_loop import DEFAULT_SEG, event_finish as _event_finish


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def preferred_event_core() -> str:
    """Platform default for the batched engine's sequential event core.

    On TPU the Pallas event kernel compiles via Mosaic and is the default;
    whether it beats the ``lax.while_loop`` core there is not measured yet.
    On CPU the kernel only *interprets* (a correctness vehicle), so the
    vmapped ``lax.while_loop`` reference stays the default there.  Kept here
    so the interpret-vs-compile platform policy lives in one module.
    """
    return "while_loop" if _interpret() else "pallas"


def event_finish(eff, speed, jitter, h_eff, bcost, forced, count, *,
                 seg: int = DEFAULT_SEG):
    return _event_finish(eff, speed, jitter, h_eff, bcost, forced, count,
                         seg=seg, interpret=_interpret())

