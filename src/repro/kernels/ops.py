"""Public jit'd wrappers around the Pallas kernels.

On CPU the kernels execute in interpret mode — the kernel body runs in
Python with real block indexing, which is what the per-kernel allclose tests
validate.  On TPU backends the same calls compile via Mosaic
(``tests/test_chip_compile.py`` compiles the event kernel for a v5e).
"""

from __future__ import annotations

import jax

from .event_loop import event_finish as _event_finish
from .flash_attention import flash_attention as _flash
from .rmsnorm import rmsnorm as _rmsnorm
from .ssd_scan import ssd_scan as _ssd


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


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_kv: int = 512):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
                  interpret=_interpret())


def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, head_block: int = 8):
    return _ssd(x, dt, A, B, C, chunk=chunk, head_block=head_block,
                interpret=_interpret())


def rmsnorm(x, w, *, eps: float = 1e-5, block_rows: int = 256):
    return _rmsnorm(x, w, eps=eps, block_rows=block_rows,
                    interpret=_interpret())


def event_finish(eff, speed, jitter, h_eff, bcost, forced, count, *,
                 seg: int = 512):
    return _event_finish(eff, speed, jitter, h_eff, bcost, forced, count,
                         seg=seg, interpret=_interpret())

