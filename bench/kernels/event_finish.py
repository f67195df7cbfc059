"""Operations and bytes the event kernel's work needs, from its shapes.

The kernel (``kernels/event_loop.py`` in the program) runs, for each lane of
a call, ``count`` chunk steps over the lane's P per-PE finish times.  One
step needs the argmin over P finish times (P - 1 comparisons) and the update
``fin[pe] += h_eff + cost * speed[pe] + bcost`` (one multiply, three adds),
so P + 3 operations.  A step reads the chunk's cost and its forced owner
(4 + 4 bytes); each lane reads its speeds and start times (2 * 4P bytes) and
its h_eff, bcost and count (12 bytes), and writes its P finish times (4P
bytes).  Padding lanes and padded chunk slots are work the algorithm does
not need, and are not counted.
"""

from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np


def ops(counts: Iterable[int], P: int) -> float:
    return float(np.sum(np.asarray(list(counts), np.float64))) * (P + 3)


def bytes_moved(counts: Iterable[int], P: int) -> float:
    c = np.asarray(list(counts), np.float64)
    return float(c.sum() * 8 + len(c) * (12 * P + 12))


def roofline_share(calls: Iterable[Tuple[Iterable[int], int]],
                   kernel_s: float, flops_per_s: float,
                   bytes_per_s: float):
    """(share of the roofline in %, the bound that applies) for calls of
    (per-lane chunk counts, P) that took ``kernel_s`` on the device."""
    t_ops = t_bytes = 0.0
    for counts, P in calls:
        counts = list(counts)
        t_ops += ops(counts, P) / flops_per_s
        t_bytes += bytes_moved(counts, P) / bytes_per_s
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / kernel_s, bound
