"""Share of the padded (rows x K) slots of the window's event batches that
hold a real chunk: the sum of ``chunks`` over the sum of ``rows`` x ``K``
of the ``repro.events.pack`` spans."""

from harness.program import load


def read(ctx):
    prog = load(ctx)
    if prog is None:
        return None
    lo, hi = prog.window
    packs = [st for n, s, _, st in prog.spans
             if n == "repro.events.pack" and lo <= s < hi]
    slots = sum(st["rows"] * st["K"] for st in packs)
    if slots <= 0:
        return None
    chunks = sum(st["chunks"] for st in packs)
    ctx.notes["pad_chunks"] = chunks
    ctx.notes["pad_slots"] = slots
    return 100.0 * chunks / slots
