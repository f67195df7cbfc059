"""Op-name paths of the event program's device operations, read from its
compiled HLO text.

The device trace names each operation by its HLO instruction and carries no
op-name stat (TPU v5e, JAX 0.9: the stats of an ``XLA Ops`` event are its
device offset and duration).  So the scopes the program sets with
``jax.named_scope`` are taken from the compiled module: the event program is
lowered again for each shape that its ``repro.events.dispatch`` spans name
(the run's compile cache holds it), and each instruction's
``metadata={op_name=...}`` is kept under the instruction's name, and under
its name and result shape (``fusion.3 f32[2097152]{0}``): the trace gives
both, and the shape tells apart programs that reuse a name.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, Set, Tuple

_INSTR = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ')
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')

#: (P, K, rows, grid_rows, grid_cols) of one compiled event program
Shape = Tuple[int, int, int, int, int]


def op_key(text: str) -> str:
    """``name shape`` of an instruction's text (``%fusion.3 = f32[8]{0}
    fusion(...)``); the bare name when the text is a name alone."""
    head = text.lstrip().removeprefix("ROOT ").split(" ", 3)
    name = head[0].lstrip("%")
    if len(head) > 2 and head[1] == "=" and not head[2].startswith("("):
        return f"{name} {head[2]}"
    return name


def instruction_paths(hlo_text: str) -> Dict[str, str]:
    """Op key (name, and name with result shape) -> op-name path, for
    every instruction of an HLO module's text; "" for one that carries no
    op name, so that a name missing here is one the module does not have."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line, m.end())
            out[m.group(1)] = out[f"{m.group(1)} {m.group(2)}"] = (
                op.group(1) if op else "")
    return out


def event_program_paths(core: str, shapes: Iterable[Shape]
                        ) -> Dict[str, Set[str]]:
    """Op key -> its op-name paths over the single-device event programs
    of ``shapes`` run with event core ``core``."""
    import jax
    import jax.numpy as jnp

    from repro.sim.backends.jax_batched import _batched_events

    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    out: Dict[str, Set[str]] = {}
    for P, K, B, S, G1 in sorted(set(shapes)):
        sd = jax.ShapeDtypeStruct
        args = (sd((S, G1), f32), sd((B,), i32), sd((B,), f32),
                sd((B, K), i32), sd((B, K), i32), sd((B, K), f32),
                sd((B,), i32), sd((B, K), i32), sd((B,), u32), sd((B,), f32),
                sd((B,), f32), sd((B, P), f32), sd((B,), f32), sd((), f32),
                sd((), f32), sd((), f32))
        text = _batched_events.lower(P, core, *args).compile().as_text()
        for name, path in instruction_paths(text).items():
            out.setdefault(name, set()).add(path)
    return out
