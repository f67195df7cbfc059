"""Plain reference of the LB4OMP loop-scheduling simulation
(``lb4omp_paper_grid``).

Everything here is written from the semantics the benchmark holds the program
to, in float64 Python and numpy, one instance at a time:

* the paper's six applications as per-iteration cost patterns on a prefix grid
  (``loop``), with the sizes taken from the configuration file;
* the chunk schedule of each portfolio algorithm (``central_schedule``,
  ``steal_schedule``): exact for the non-adaptive algorithms, the
  telemetry-free recurrences for AWF-B/C/D/E and mAF;
* the self-scheduling event recurrence: each chunk goes to the PE that is
  free first (lowest index on a tie) or to its StaticSteal owner, and that PE's
  finish time grows by ``h_eff + cost * locality * noise * speed + bcost``
  (``event_instance``);
* the closed forms for STATIC and over-budget SS / StaticSteal
  (``closed_instance``), and the LIB of an instance (``lib``).

Random draws follow the counter-based streams the configuration states: for
an instance with fold seed ``s`` and padded schedule length ``K``, the key
``PRNGKey(s)`` splits into (jitter, speed, noise) keys, each drawn with
``jax.random`` on the host's CPU device.

``dtype`` selects the arithmetic of the cost gather and the recurrence:
``float64`` is the reference, ``bfloat16`` the control (the precision one step
below the float32 the configuration states).  Nothing here imports the program.
"""

from __future__ import annotations

import heapq
import math
import zlib
from dataclasses import dataclass
from typing import List, Optional, Tuple

import ml_dtypes
import numpy as np

GRID = 16384
BF16 = np.dtype(ml_dtypes.bfloat16)

#: algorithms whose user chunk parameter sets the size directly (STATIC, SS)
DIRECT = (0, 1)
#: padded schedule lengths; the noise draw of an instance has this length
K_BUCKETS = (256, 1024, 4096, 16384, 65536, 262144)
#: dispatch events one instance may generate before SS / StaticSteal go
#: analytic
EVENT_CAP = 120_000


def k_bucket(n: int) -> int:
    for b in K_BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"schedule length {n} exceeds {K_BUCKETS[-1]}")


# ---------------------------------------------------------------------------
# applications and machines
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Loop:
    name: str
    N: int
    memory_bound: float
    locality_sens: float
    c_loc: int
    unit: float
    grid: Optional[np.ndarray]      # (GRID+1,) float64 cumulative cost

    @property
    def total(self) -> float:
        return self.N * self.unit if self.grid is None else float(self.grid[-1])

    def prefix(self, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x, np.float64)
        if self.grid is None:
            return x * self.unit
        return np.interp(x * (GRID / self.N), np.arange(GRID + 1), self.grid)


@dataclass(frozen=True)
class Machine:
    name: str
    P: int
    h: float
    h_adaptive_mult: float
    h_serial_frac: float
    boundary_cost: float
    dyn_locality: float
    loc_amp: float
    noise_sigma: float
    jitter: float
    speed_spread: float

    def noise_free(self) -> "Machine":
        d = dict(self.__dict__)
        d.update(noise_sigma=0.0, jitter=0.0, speed_spread=0.0)
        return Machine(**d)


def machine(cfg: dict, name: str) -> Machine:
    m = cfg["systems"][name]
    return Machine(name=name, **{k: m[k] for k in Machine.__dataclass_fields__
                                 if k != "name"})


def _grid(pattern: np.ndarray, N: int, unit: float) -> np.ndarray:
    density = pattern / pattern.mean()
    return np.concatenate([[0.0], np.cumsum(density * (N / GRID) * unit)])


def _pattern(app: str, li: int, t: int, T: int) -> Optional[np.ndarray]:
    """Per-bucket relative cost density of loop ``li`` of ``app`` at step t
    (None: uniform).  The imbalance characters of the paper's Table 2."""
    x = np.linspace(0.0, 1.0, GRID)
    frac = t / max(1, T - 1)
    if app == "mandelbrot":            # constant / increasing / decreasing
        centre, width = ((0.35, 0.08), (0.62, 0.05), (0.18, 0.06))[li]
        amp = (6.0, 0.5 + 11.0 * frac, 11.5 - 11.0 * frac)[li]
        return 1.0 + amp * np.exp(-((x - centre) / width) ** 2)
    if app == "tc":                    # degree-sorted power-law head
        i = np.arange(GRID, dtype=np.float64)
        return 1.0 + 120.0 * (i + 1.0) ** -0.7
    if app == "lulesh":                # mild seeded imbalance, 4 loops
        rng = np.random.default_rng(1234)
        pats = [1.0 + 0.12 * rng.random(GRID) for _ in range(4)]
        return pats[li]
    if app == "sphynx":                # clusters drift and sharpen over t
        c1 = 0.3 + 0.25 * frac
        c2 = 0.75 - 0.15 * math.sin(2.0 * math.pi * frac)
        w = 0.18 - 0.10 * frac
        amp = 3.0 + 5.0 * frac + 1.5 * math.sin(6.0 * math.pi * frac)
        return (0.4 + amp * np.exp(-((x - c1) / max(w, 0.03)) ** 2)
                + 0.7 * amp * np.exp(-((x - c2) / 0.12) ** 2))
    if app in ("stream", "hacc"):
        return None
    raise KeyError(f"no cost pattern for application {app!r}")


def loop(cfg: dict, app: str, li: int, t: int) -> Loop:
    a = cfg["apps"][app]
    spec = a["loops"][li]
    pat = _pattern(app, li, t, a["T"])
    return Loop(name=spec["name"], N=a["N"], memory_bound=spec["memory_bound"],
                locality_sens=spec["locality_sens"], c_loc=spec["c_loc"],
                unit=a["unit"],
                grid=None if pat is None else _grid(pat, a["N"], a["unit"]))


def h_eff(m: Machine, alg: int) -> float:
    if alg in (7, 9):                  # AWF-B/D: mutex-protected weights
        return m.h * m.h_adaptive_mult
    if alg in (8, 10, 11):             # AWF-C/E, mAF: atomic path
        return m.h * 2.0
    return m.h


def needs_closed_form(alg: int, N: int, cp: int) -> bool:
    return alg in (1, 5) and N / max(1, cp) > EVENT_CAP


# ---------------------------------------------------------------------------
# chunk schedules
# ---------------------------------------------------------------------------

def central_schedule(alg: int, N: int, P: int, cp: int) -> List[int]:
    """Chunk sizes a central queue hands out, in order."""
    out: List[int] = []
    R = N
    k = 0
    batch_left, batch_cs, batch_r = P, -(-N // (2 * P)), N   # mFAC2 state
    awf_left, awf_cs = 0, 0
    quantum = max(1, N // (P * P * 4))
    if alg == 4:                       # TSS: f = N/2P, linear decrement
        A = 4 * P - (8 * P * P) // (N + 2 * P)
        Am1 = max(1, A - 1)
    while R > 0:
        if alg == 0:
            raw = -(-N // P)
        elif alg == 1:
            raw = 1
        elif alg == 2:                 # GSS
            raw = -(-R // P)
        elif alg == 3:                 # AutoLLVM
            raw = max(quantum, -(-R // (2 * P)))
        elif alg == 4:
            if N < 2 * P:
                raw = 1
            else:
                kk = min(k, Am1)
                raw = max(1, -(-(N * Am1 - kk * (N - 2 * P)) // (2 * P * Am1)))
        elif alg == 6:                 # mFAC2: batches of P chunks
            if batch_left <= 0:
                batch_r -= P * batch_cs
                batch_cs = max(0, -(-batch_r // (2 * P)))
                batch_left = P - 1
            else:
                batch_left -= 1
            raw = max(1, batch_cs)
        elif alg in (7, 9):            # AWF-B/D at unit weights: batched
            if awf_left <= 0:
                awf_cs = -(-R // (2 * P))
                awf_left = P - 1
            else:
                awf_left -= 1
            raw = max(1, awf_cs)
        elif alg in (8, 10):           # AWF-C/E at unit weights
            raw = max(1, -(-R // (2 * P)))
        elif alg == 11:                # mAF at constant mean, zero spread
            raw = min(100, max(1, N // P)) if k == 0 else max(1, R // P)
        else:
            raise ValueError(f"no central schedule for algorithm {alg}")
        c = cp if (alg in DIRECT and cp > 0) else max(raw, max(1, cp))
        c = min(max(c, 1), R)
        out.append(c)
        R -= c
        k += 1
    return out


def steal_schedule(N: int, P: int, cp: int, unit: float, h: float,
                   bcost: float, base_infl: float, amp: float, c_loc: float):
    """StaticSteal replayed noise-free at uniform cost ``unit`` in float32:
    serve own range in quanta; an empty PE steals the back half of the
    richest range.  Returns (starts, sizes, pes, own) lists."""
    f = np.float32
    bounds = np.linspace(0, N, P + 1).round().astype(np.int64)
    lo = bounds[:-1].copy()
    hi = bounds[1:].copy()
    avail = np.zeros(P, np.float32)
    q = max(1, cp)
    unit_, h_, bc_ = f(unit), f(h), f(bcost)
    bi_, amp_, cl_ = f(base_infl), f(amp), f(c_loc)
    starts, sizes, pes, own = [], [], [], []
    R = N
    while R > 0:
        pe = int(np.argmin(avail))
        if lo[pe] >= hi[pe]:
            victim = int(np.argmax(hi - lo))
            vh = hi[victim]
            half = (vh - lo[victim] + 1) // 2
            hi[victim] = vh - half
            lo[pe], hi[pe] = vh - half, vh
        c = int(min(q, hi[pe] - lo[pe]))
        is_own = bool(bounds[pe] <= lo[pe] < bounds[pe + 1])
        locf = f(1.0) if is_own else bi_ + amp_ * cl_ / (f(c) + cl_)
        avail[pe] += h_ + f(c) * unit_ * locf + bc_
        starts.append(int(lo[pe]))
        sizes.append(c)
        pes.append(pe)
        own.append(is_own)
        lo[pe] += c
        R -= c
    return starts, sizes, pes, own


# ---------------------------------------------------------------------------
# random draws
# ---------------------------------------------------------------------------

def draws(seed: int, P: int, K: int, m: Machine):
    """(jitter (P,), speed (P,), noise (K,)) of one instance, float64."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.random.PRNGKey(np.uint32(seed))
        kj, ks, kn = jax.random.split(key, 3)
        jitter = jax.random.uniform(kj, (P,)) * np.float32(m.jitter)
        speed = np.clip(1.0 + np.float32(m.speed_spread)
                        * jax.random.normal(ks, (P,)), 0.8, 1.25)
        noise = np.exp(np.float32(m.noise_sigma)
                       * jax.random.normal(kn, (K,)))
        return (np.asarray(jitter, np.float64), np.asarray(speed, np.float64),
                np.asarray(noise, np.float64))


# ---------------------------------------------------------------------------
# the event recurrence
# ---------------------------------------------------------------------------

def _recurrence(cost, forced, speed, jitter, h, bc, dtype) -> np.ndarray:
    """Per-PE finish times: chunk i goes to ``forced[i]`` or to the PE free
    first (lowest index on a tie); its PE advances by h + cost*speed + bc."""
    P = len(speed)
    if dtype == np.float64:
        fin = np.asarray(jitter, np.float64).copy()
        if forced is None:
            heap = [(fin[p], p) for p in range(P)]
            heapq.heapify(heap)
            for c in cost:
                t, p = heapq.heappop(heap)
                t = t + (h + c * speed[p] + bc)
                fin[p] = t
                heapq.heappush(heap, (t, p))
            return fin
        for c, f in zip(cost, forced):
            p = int(f) if f >= 0 else int(np.argmin(fin))
            fin[p] = fin[p] + (h + c * speed[p] + bc)
        return fin
    # lower-precision control: every value and every operation in ``dtype``
    fin = np.asarray(jitter).astype(dtype)
    spd = np.asarray(speed).astype(dtype)
    h_, bc_ = dtype.type(h), dtype.type(bc)
    cost = np.asarray(cost).astype(dtype)
    for i, c in enumerate(cost):
        f = -1 if forced is None else int(forced[i])
        p = f if f >= 0 else int(np.argmin(fin))
        fin[p] = fin[p] + (h_ + c * spd[p] + bc_)
    return fin.astype(np.float64)


def _schedule(lp: Loop, m: Machine, alg: int, cp: int):
    """(starts, sizes, locality factor, forced owners or None)."""
    ls = lp.locality_sens
    base_infl = 1.0 + ls * m.dyn_locality
    amp = ls * m.loc_amp
    if alg == 5:
        st, sz, pes, own = steal_schedule(
            lp.N, m.P, cp, lp.total / lp.N, m.h,
            lp.memory_bound * m.boundary_cost, base_infl, amp,
            float(lp.c_loc))
        sz = np.asarray(sz, np.int64)
        loc = np.where(own, 1.0, base_infl + amp * lp.c_loc / (sz + lp.c_loc))
        return np.asarray(st, np.int64), sz, loc, np.asarray(pes)
    sz = np.asarray(central_schedule(alg, lp.N, m.P, cp), np.int64)
    st = np.concatenate([[0], np.cumsum(sz)[:-1]])
    return st, sz, base_infl + amp * lp.c_loc / (sz + lp.c_loc), None


def event_instance(lp: Loop, m: Machine, alg: int, cp: int, seed: int,
                   dtype=np.float64) -> Tuple[float, int, float]:
    """(makespan, chunk count, LIB) of one event-loop instance with fold
    seed ``seed``."""
    st, sz, loc, forced = _schedule(lp, m, alg, cp)
    n = len(sz)
    jitter, speed, noise = draws(seed, m.P, k_bucket(n), m)
    if dtype == np.float64:
        raw = lp.prefix(st + sz) - lp.prefix(st)
        cost = raw * loc * noise[:n]
    else:                              # gather from a grid held in dtype
        grid = (lp.grid if lp.grid is not None else
                np.linspace(0.0, lp.total, GRID + 1)).astype(dtype)
        pos_scale = GRID / lp.N

        def pref(x):
            pos = (x * pos_scale).astype(dtype).astype(np.float64)
            i = np.clip(pos.astype(np.int64), 0, GRID - 1)
            lo = grid[i]
            return lo + (pos - i).astype(dtype) * (grid[i + 1] - lo)

        cost = ((pref(st + sz) - pref(st)) * loc.astype(dtype)
                * noise[:n].astype(dtype))
    fin = _recurrence(cost, forced, speed, jitter, h_eff(m, alg),
                      lp.memory_bound * m.boundary_cost, dtype)
    return float(fin.max()), n, lib(fin)


def closed_instance(lp: Loop, m: Machine, alg: int, cp: int,
                    seed: Tuple[int, ...]) -> float:
    """Makespan of a STATIC or over-budget SS/StaticSteal instance, drawn
    from ``numpy.random.default_rng(seed)``."""
    return float(closed_finish(lp, m, alg, cp, seed).max())


def closed_finish(lp: Loop, m: Machine, alg: int, cp: int,
                  seed: Tuple[int, ...]) -> np.ndarray:
    """Per-PE finish times of a closed-form instance."""
    rng = np.random.default_rng(seed)
    P, N, mb = m.P, lp.N, lp.memory_bound
    if alg == 0:
        jitter = rng.uniform(0.0, m.jitter, P)
        speed = np.clip(1.0 + rng.normal(0.0, m.speed_spread, P), 0.8, 1.25)
        if cp <= 0:
            bounds = np.linspace(0, N, P + 1).round().astype(np.int64)
            cost = np.diff(lp.prefix(bounds))
            per_pe = np.ones(P)
            infl = 1.0
        else:
            c = min(cp, N)
            n_chunks = -(-N // c)
            if lp.grid is None and n_chunks > 2_000_000:
                cost = np.full(P, lp.total / P)
                per_pe = np.full(P, n_chunks / P)
            else:
                bounds = np.arange(0, N + c, c, dtype=np.int64)
                bounds[-1] = N
                pe = np.arange(n_chunks) % P
                cost = np.bincount(pe, weights=np.diff(lp.prefix(bounds)),
                                   minlength=P)
                per_pe = np.bincount(pe, minlength=P).astype(np.float64)
            infl = 1.0 + lp.locality_sens * m.loc_amp * (
                lp.c_loc / (cp + lp.c_loc))
        noise = np.exp(rng.normal(0.0, m.noise_sigma * 0.5, P))
        return jitter + cost * infl * speed * noise \
            + mb * m.boundary_cost * per_pe
    if not needs_closed_form(alg, N, cp):
        raise ValueError("instance is not a closed form")
    c = max(1, cp)
    n_chunks = -(-N // c)
    h = h_eff(m, alg)
    ls = lp.locality_sens
    work = lp.total * (1.0 + ls * (m.dyn_locality
                                   + m.loc_amp * lp.c_loc / (c + lp.c_loc)))
    if alg == 1:
        overhead = max(n_chunks * (h + mb * m.boundary_cost) / P,
                       n_chunks * h * m.h_serial_frac)
    else:
        overhead = n_chunks * (h * 0.6 + mb * m.boundary_cost) / P
    base = work / P + overhead
    jitter = rng.uniform(0.0, m.jitter, P)
    speed = np.clip(1.0 + rng.normal(0.0, m.speed_spread, P), 0.8, 1.25)
    noise = np.exp(rng.normal(0.0, m.noise_sigma * 0.3, P))
    tail = rng.uniform(0.0, 1.0, P) * (work / n_chunks + h)
    return jitter.mean() + base * speed * noise + tail


def lib(fin: np.ndarray) -> float:
    """Percent load imbalance (the paper's Eq. 8): (1 - mean / max) * 100."""
    mx = float(np.max(fin))
    return (1.0 - float(np.mean(fin)) / mx) * 100.0 if mx > 0.0 else 0.0


def chunk_param(mode: str, N: int, P: int) -> int:
    """The chunk parameter of a chunk mode: 0 (each algorithm's default) or
    expChunk, the golden-ratio point of the curve N / (2^i P)."""
    if mode == "default":
        return 0
    k_max = math.log2(max(2.0, N / P))
    phi = (1.0 + math.sqrt(5.0)) / 2.0
    i = max(1, round((1.0 - 1.0 / phi) * k_max))
    return max(1, int(N // (2 ** i * P)))


def fold_seed(seed: Tuple[int, ...]) -> int:
    """The stateless seed of a seed tuple: crc32 of its int64 bytes."""
    return zlib.crc32(np.asarray(seed, dtype=np.int64).tobytes())


def instance(lp: Loop, m: Machine, alg: int, cp: int, seed, dtype=np.float64
             ) -> float:
    """Makespan of any instance; ``seed`` is the instance's seed tuple."""
    if alg == 0 or needs_closed_form(alg, lp.N, cp):
        return closed_instance(lp, m, alg, cp, seed)
    return event_instance(lp, m, alg, cp, fold_seed(seed), dtype)[0]


def rel_gap(value: float, ref: float) -> float:
    return abs(value - ref) / max(abs(ref), 1e-30)
