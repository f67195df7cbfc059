"""The program's own profiler spans and device scopes in a traced window.

The program writes ``repro.<span>`` annotations with integer stats
(``repro.tracing``) and names its device operations with
``jax.named_scope``; this module reads both from the window's
``.xplane.pb``, beside ``harness.trace``, which it leaves as it is.

* span seconds: the union of a set of spans inside ``bench.window``, less
  the union of another set (a parent's time counts once);
* scope seconds: the device time of the operations whose op-name path holds
  a scope, averaged over the chips used; the trace carries no op-name path,
  so it comes from the compiled event programs (``harness.scopes``), and
  when more than ``MAX_UNMATCHED`` of the event program's device time is in
  operations those programs do not name, there are no scope seconds;
* idle gaps named by the innermost span, benchmark or program, at their
  middle, and the coverage of the benchmark's spans by the program's.

A program without these spans (the parent of the change that added them)
gives empty sets: every reader then returns None.
"""

from __future__ import annotations

import bisect
import os
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Set, Tuple

from harness.scopes import op_key
from harness.trace import (DEVICE_PREFIX, OPS_LINE, WINDOW, Interval, clip,
                           newest_xplane, short_name, union)

PREFIX = "repro."
MODULES_LINE = "XLA Modules"
#: the device scopes of the event program, and a fragment of its module name
SCOPES = ("precompute", "event_core")
EVENTS_MODULE = "batched_events"
#: the split of an operation of another program, and of one of the event
#: program that no compiled event program names
OTHER, UNMATCHED = "other", "?"
#: the largest share of the event program's device time that may be
#: unmatched before the scopes are not read
MAX_UNMATCHED = 0.01
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(BENCH, "out", "trace")

#: a host span: (name, start ns, end ns, stats)
Span = Tuple[str, int, int, Dict[str, int]]


def _measure(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def _minus(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Disjoint sorted intervals ``a`` less disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


@dataclass
class Program:
    window: Interval
    #: ``repro.*`` spans, prefix kept
    spans: List[Span] = field(default_factory=list)
    #: ``bench.*`` spans but the window: (name, start, end)
    bench: List[Tuple[str, int, int]] = field(default_factory=list)
    #: device operations of the chips used: (device, short name, op key,
    #: module name ("" outside every module, None without a modules line),
    #: start, end)
    ops: List[Tuple[int, str, str, Optional[str], int, int]] = field(
        default_factory=list)
    n_devices: int = 1
    #: op key -> op-name paths in the compiled event programs
    hlo_paths: Dict[str, Set[str]] = field(default_factory=dict)
    _all: Optional[List[Interval]] = field(default=None, repr=False)

    def _union(self, names: Iterable[str]) -> List[Interval]:
        names = set(names)
        lo, hi = self.window
        return union(clip([(s, e) for n, s, e, _ in self.spans
                           if n in names], lo, hi))

    def seconds(self, names: Iterable[str],
                minus: Iterable[str] = ()) -> float:
        """Seconds of the window inside spans ``names`` and outside spans
        ``minus``."""
        keep = _minus(self._union(names), self._union(minus))
        return _measure(keep) * 1e-9

    def scope_of_op(self, key: str, module: Optional[str]) -> str:
        """The scope of ``SCOPES`` that holds an operation, from the
        compiled event programs by name and result shape, else by name where
        all of them agree; "" for an operation of the event program in no
        scope, ``OTHER`` outside its module, ``UNMATCHED`` when no compiled
        event program has an instruction of the operation's name."""
        if module is not None and EVENTS_MODULE not in module:
            return OTHER
        paths = self.hlo_paths.get(key)
        if paths is None:
            paths = self.hlo_paths.get(key.split(" ")[0])
        if paths is None:
            return UNMATCHED
        for scope in SCOPES:
            if paths and all(f"/{scope}/" in p + "/" for p in paths):
                return scope
        return ""

    def scope_split(self) -> Dict[str, Dict[str, float]]:
        """Short op name -> {split of ``scope_of_op``: device seconds in
        the window}, averaged over the chips."""
        lo, hi = self.window
        out: Dict[str, Dict[str, float]] = {}
        for _, name, key, module, s, e in self.ops:
            if e <= lo or s >= hi:
                continue
            scope = self.scope_of_op(key, module)
            by = out.setdefault(name, {})
            by[scope] = by.get(scope, 0.0) + (
                (min(e, hi) - max(s, lo)) * 1e-9 / max(1, self.n_devices))
        return out

    def unmatched(self) -> Tuple[float, float]:
        """Device seconds of the event program's operations that no
        compiled event program names, and its device seconds in all."""
        split = self.scope_split()
        return (sum(by.get(UNMATCHED, 0.0) for by in split.values()),
                sum(v for by in split.values() for k, v in by.items()
                    if k != OTHER))

    def scope_seconds(self, scope: str) -> Optional[float]:
        """Device seconds in the window of operations under ``scope``,
        averaged over the chips; None when no operation's path is known or
        more than ``MAX_UNMATCHED`` of the event program's time is in
        operations of unknown name."""
        if not self.hlo_paths:
            return None
        lost, total = self.unmatched()
        if lost > MAX_UNMATCHED * total:
            return None
        return sum(by.get(scope, 0.0) for by in self.scope_split().values())

    def dispatch_shapes(self) -> List[Tuple[int, int, int, int, int]]:
        """(P, K, rows, grid_rows, grid_cols) of every event program the
        ``repro.events.dispatch`` spans name."""
        keys = ("P", "K", "rows", "grid_rows", "grid_cols")
        return sorted({tuple(st[k] for k in keys) for n, _, _, st in self.spans
                       if n == "repro.events.dispatch"
                       and all(k in st for k in keys)})

    def name_at(self, t: int) -> str:
        """The innermost span, benchmark or program, covering host time
        ``t``: ``repro.*`` names in full, ``bench.*`` without the prefix,
        ``window`` when the host was in neither."""
        best = None
        for name, s, e in self.bench + [sp[:3] for sp in self.spans]:
            if s <= t < e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        if best is None:
            return "window"
        name = best[0]
        return name[len("bench."):] if name.startswith("bench.") else name

    def in_span(self, t: int) -> bool:
        """Whether host time ``t`` lies in any benchmark or program span."""
        if self._all is None:
            self._all = union([sp[1:3] for sp in self.bench + self.spans])
        i = bisect.bisect_right(self._all, (t, float("inf"))) - 1
        return i >= 0 and t < self._all[i][1]

    def cover(self, children: Iterable[str], parent: str) -> Optional[float]:
        """Share of the benchmark span ``parent`` covered by the program's
        spans ``children``."""
        lo, hi = self.window
        par = union(clip([(s, e) for n, s, e in self.bench if n == parent],
                         lo, hi))
        if not par:
            return None
        kids = self._union(children)
        inside = _measure(par) - _measure(_minus(par, kids))
        return inside / _measure(par)


def reduce_program(planes, n_devices: int) -> Program:
    """Reduce ``(plane, [(line, [(event, start ns, dur ns, stats)])])``
    planes to the window's program spans and scoped device operations."""
    window, spans, bench, devices = None, [], [], []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            tail = pname[len(DEVICE_PREFIX):]
            idx = int(tail) if tail.isdigit() else 1 << 30
            by_line = dict(lines)
            devices.append((idx, by_line.get(OPS_LINE, []),
                            sorted((s, s + d, name) for name, s, d, _ in
                                   by_line.get(MODULES_LINE, []))))
            continue
        for _, evs in lines:
            for name, s, d, stats in evs:
                if name == WINDOW:
                    window = (s, s + d)
                elif name.startswith("bench."):
                    bench.append((name, s, s + d))
                elif name.startswith(PREFIX):
                    spans.append((name, s, s + d, stats))
    if window is None:
        raise ValueError("trace holds no bench.window annotation")
    devices = sorted(devices, key=lambda d: d[0])[:n_devices]
    prog = Program(window=window, spans=spans, bench=bench,
                   n_devices=max(1, len(devices)))
    for k, (_, ops, modules) in enumerate(devices):
        starts = [m[0] for m in modules]
        for name, s, d, _ in ops:
            i = bisect.bisect_right(starts, s) - 1
            module = (None if not modules else modules[i][2]
                      if i >= 0 and s < modules[i][1] else "")
            prog.ops.append((k, short_name(name), op_key(name), module,
                             s, s + d))
    return prog


def read_planes(path: str):
    """The xplane's planes: every event of a device's ``XLA Ops`` and
    ``XLA Modules`` lines, and the host's benchmark and program annotations,
    the program's with their stats."""
    from jax.profiler import ProfileData

    out = []
    for p in ProfileData.from_file(path).planes:
        dev = p.name.startswith(DEVICE_PREFIX)
        lines = []
        for ln in p.lines:
            if dev and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for ev in ln.events:
                name = ev.name
                if name.startswith(PREFIX):
                    evs.append((name, ev.start_ns, ev.duration_ns,
                                dict(ev.stats)))
                elif dev or name.startswith("bench."):
                    evs.append((name, ev.start_ns, ev.duration_ns, {}))
            lines.append((ln.name, evs))
        out.append((p.name, lines))
    return out


def attach(ctx, prog: Program) -> Program:
    """Keep ``prog`` as the run's and note what PERF.md reads: the longest
    idle gaps named by the program's spans, the time of the gaps outside
    every span, and how much of the benchmark's spans the program's cover."""
    ctx.program = prog
    notes = ctx.notes
    gaps = ctx.trace.gaps() if ctx.trace.busy else []
    notes["idle_gaps_named"] = [
        [prog.name_at((s + e) // 2), (e - s) * 1e-9]
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]]
    notes["window_gap_s"] = 1e-9 * sum(
        e - s for s, e in gaps if not prog.in_span((s + e) // 2))
    step_parts = ("repro.replay.decide", "repro.replay.learn",
                  "repro.backend.lockstep")
    for key, kids, parent in (
            ("step_cover", step_parts, "bench.ReplayBatch.step"),
            ("decide_cover", ("repro.whatif.price",),
             "bench.SimPolicy.decide"),
            ("lockstep_cover", _BACKEND_PARTS, "bench.run_lockstep"),
            ("batch_cover", _BACKEND_PARTS, "bench.run_batch")):
        v = prog.cover(kids, parent)
        if v is not None:
            notes[key] = v
    return prog


_BACKEND_PARTS = ("repro.backend.host_instances", "repro.events.rows",
                  "repro.events.pack", "repro.events.dispatch",
                  "repro.events.wait")


def load(ctx) -> Optional[Program]:
    """The program's spans and scopes of this run's traced window, read
    once per run; None without a trace or when the newest trace is not this
    run's."""
    if hasattr(ctx, "program") or ctx.trace is None:
        return getattr(ctx, "program", None)
    ctx.program = None
    try:
        path = newest_xplane(TRACES)
    except FileNotFoundError:
        return None
    prog = reduce_program(read_planes(path), max(1, len(ctx.trace.busy)))
    if prog.window != tuple(ctx.trace.window):
        return None
    attach(ctx, prog)
    resolve_scopes(ctx, prog)
    return prog


def resolve_scopes(ctx, prog: Program) -> None:
    """Take the op-name paths from the compiled event programs of the
    window (single device only); then note the scopes' device seconds and
    those of the largest operations."""
    if prog.ops and not prog.hlo_paths:
        from harness.scopes import event_program_paths

        shapes = prog.dispatch_shapes()
        bk = getattr(ctx.driver, "bk", None)
        if not shapes or bk is None or bk.mesh is not None:
            return
        t0 = time.perf_counter()
        prog.hlo_paths = event_program_paths(bk.event_core, shapes)
        ctx.notes["scope_programs"] = len(shapes)
        ctx.notes["scope_compile_s"] = time.perf_counter() - t0
    if not prog.hlo_paths:
        return
    split = prog.scope_split()
    top = sorted(split, key=lambda n: -sum(split[n].values()))[:4]
    ctx.notes["top_ops_by_scope"] = {n: split[n] for n in top}
    ctx.notes["unmatched_op_s"], ctx.notes["events_op_s"] = prog.unmatched()
    for scope in SCOPES:
        ctx.notes[f"{scope}_s"] = prog.scope_seconds(scope)


def share(ctx, names, over: Optional[str] = None, minus=()) -> Optional[float]:
    """Per-layer reader: seconds in spans ``names`` (less ``minus``), in %
    of the window or of the spans ``over``; None when the program wrote no
    spans at all."""
    prog = load(ctx)
    if prog is None or not prog.spans:
        return None
    den = ((prog.window[1] - prog.window[0]) * 1e-9 if over is None
           else prog.seconds([over]))
    if den <= 0:
        return None
    return 100.0 * prog.seconds(names, minus) / den
