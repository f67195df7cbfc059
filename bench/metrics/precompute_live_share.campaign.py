"""Share of the padded (rows x K) slots of the window's event batches that
the precompute's live tiles cover: the sum of ``live`` over the sum of
``rows`` x ``K`` of the ``repro.events.pack`` spans.  A program whose pack
spans carry no ``live`` reads None."""

from harness.program import load


def read(ctx):
    prog = load(ctx)
    if prog is None:
        return None
    lo, hi = prog.window
    packs = [st for n, s, _, st in prog.spans
             if n == "repro.events.pack" and lo <= s < hi]
    slots = sum(st["rows"] * st["K"] for st in packs)
    if slots <= 0 or any("live" not in st for st in packs):
        return None
    live = sum(st["live"] for st in packs)
    ctx.notes["live_slots"] = live
    return 100.0 * live / slots
