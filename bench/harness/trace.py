"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.  In
it, each chip is a plane ``/device:TPU:<i>`` whose ``XLA Ops`` line holds
one event per operation that ran; the host's planes hold the benchmark's
``bench.<span>`` annotations on the same clock.  The window is the
``bench.window`` annotation.

* busy: the union of the operation intervals of a chip inside the window,
  averaged over the chips used;
* idle share: 1 - busy / window;
* kernel time: the summed durations of one kernel's operations;
* breakdown: the operations that took most time, and the longest idle gaps,
  each named by the innermost benchmark span the host was in at its middle.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
#: name fragments of the event kernel's operations: the custom call a
#: Pallas kernel lowers to, or the kernel's own name
EVENT_KERNEL = ("tpu_custom_call", "_loop_kernel", "event_finish")

Interval = Tuple[int, int]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    #: per device: merged busy intervals inside the window (ns)
    busy: List[List[Interval]] = field(default_factory=list)
    #: short operation name -> seconds on the device (summed over devices)
    op_seconds: Dict[str, float] = field(default_factory=dict)
    #: seconds on the device of operations whose full name (the HLO text
    #: the trace carries) holds one of ``EVENT_KERNEL``
    kernel_s: float = 0.0
    #: host annotation (name, start ns, end ns), window excluded
    spans: List[Tuple[str, int, int]] = field(default_factory=list)
    window: Interval = (0, 0)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def gaps(self) -> List[Tuple[int, int]]:
        """Idle gaps of the first device inside the window (ns)."""
        lo, hi = self.window
        out, t = [], lo
        for s, e in (self.busy[0] if self.busy else []):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def host_at(self, t: int) -> str:
        """The innermost benchmark span covering host time ``t``."""
        best: Optional[Tuple[str, int, int]] = None
        for name, s, e in self.spans:
            if s <= t < e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0][len("bench."):] if best else "window"

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.host_at((s + e) // 2), (e - s) * 1e-9]
                              for s, e in gaps]}


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _device_index(plane: str) -> int:
    tail = plane[len(DEVICE_PREFIX):]
    return int(tail) if tail.isdigit() else 1 << 30


def short_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def reduce_planes(planes, n_devices: int) -> Reduced:
    """Reduce ``(name, [(line name, [(event name, start ns, dur ns)])])``
    planes (the shape ``ProfileData`` gives) to the window's numbers."""
    spans = []
    window = None
    devices = []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            ops = [ev for lname, evs in lines if lname == OPS_LINE
                   for ev in evs]
            devices.append((pname, ops))
            continue
        for _, evs in lines:
            for name, s, d in evs:
                if name == WINDOW:
                    window = (s, s + d)
                elif name.startswith("bench."):
                    spans.append((name, s, s + d))
    if window is None:
        raise ValueError("trace holds no bench.window annotation")
    devices = sorted(devices, key=lambda d: _device_index(d[0]))[:n_devices]
    lo, hi = window
    busy, op_s, kernel_s = [], {}, 0.0
    for _, ops in devices:
        busy.append(union(clip([(s, s + d) for _, s, d in ops], lo, hi)))
        for name, s, d in ops:
            if s + d > lo and s < hi:
                secs = (min(s + d, hi) - max(s, lo)) * 1e-9
                key = short_name(name)
                op_s[key] = op_s.get(key, 0.0) + secs
                if any(k in name for k in EVENT_KERNEL):
                    kernel_s += secs
    busy_s = (sum(e - s for b in busy for s, e in b) * 1e-9
              / max(1, len(busy)))
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_s, busy=busy,
                   op_seconds=op_s, kernel_s=kernel_s, spans=spans,
                   window=window)


def reduce(trace_dir: str, n_devices: int = 1) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(trace_dir))
    planes = [(p.name, [(ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                   for ev in ln.events])
                        for ln in p.lines])
              for p in pd.planes]
    return reduce_planes(planes, n_devices)


def idle_share(ctx) -> Optional[float]:
    """Per-layer reader: device idle share of the traced window, in %."""
    if ctx.trace is None or not ctx.trace.busy or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share


