"""Batched vmapped JAX simulation backend — campaign-scale sweeps in a
handful of jitted calls.

Where the reference engine steps one Python event loop per instance, this
backend evaluates *whole batches* of instances — (algorithm x chunk-mode x
rep x time-step) — at once:

1.  Chunk schedules are precomputed through ``repro.core.jaxsched``
    (non-adaptive algorithms exactly; AWF-*/mAF via their telemetry-free
    surrogate recurrences; StaticSteal via the quantum-serving replay that
    yields explicit (start, size, pe) triples) and cached by
    (alg, N, P, chunk_param) — one schedule serves every rep and time-step
    (LRU-bounded so long campaign processes stay flat).
2.  Everything data-parallel runs in ONE vectorized precompute shared by
    every event core: gathered linear interpolation over the stacked prefix
    grids (device upload cached per profile stack), locality inflation, and
    the counter-based jitter/speed/log-normal-noise draws.  The
    interpolation runs only over the (rows x segment) tiles of the padded
    (B, K) batch that some lane's chunk count reaches; every other slot is
    0.0, as the dense formula gives there.
3.  The sequential event loop itself is a minimal pluggable core
    ``(eff_costs, forced, count) -> finish`` with two interchangeable
    implementations: the vmapped ``lax.while_loop`` reference (argmin
    assignment, exactly the reference heap policy: one entry per PE, ties
    to the lowest index) and the Pallas kernel
    (``repro.kernels.event_loop``), selected via the ``kernel=``
    constructor argument / the ``REPRO_EVENT_CORE`` env var.  The Pallas
    core is bit-identical to the while-loop core in interpret mode
    (``tests/test_event_kernel.py``).  ``run_batch``, ``run_lockstep``,
    ``what_if_wave`` and ``what_if_routes`` all route through the selected
    core.

4.  On a multi-device host every batched lane dimension — ``run_batch`` /
    ``run_lockstep`` instances and the serving what-if candidate rows —
    executes under ``jax.shard_map`` over the campaign mesh's ``data``
    axis (``launch.mesh.campaign_mesh`` + ``distributed.sharding`` lane
    specs): lanes are embarrassingly parallel, so each device runs the
    identical per-lane computation on its shard and the results are
    bit-identical to the single-device path (lane counts are padded to the
    mesh extent and masked with ``count == 0``; ``tests/test_shard.py``).
    ``data_parallel=`` / ``REPRO_DATA_PARALLEL`` clamp the mesh.  The host
    side is double-buffered (``async_dispatch=`` / ``REPRO_ASYNC_DISPATCH``):
    ragged-to-padded packing of dispatch t+1 overlaps the device executing
    dispatch t.

STATIC and over-``EVENT_CAP`` SS/StaticSteal instances are delegated to the
reference closed forms with the *same* numpy rng streams, so those results
are bit-identical to the Python backend.  Event-loop instances draw their
jitter/speed/noise from counter-based JAX streams folded statelessly from
the campaign's crc32 seed tuples — reproducible across processes, batch
orders and event cores, but a *different* (equally valid) noise realization
than numpy.

Accuracy contract (see tests/test_backends.py): noise-free, the chunk
sequences and makespans match the Python backend exactly for the
non-adaptive algorithms and StaticSteal on uniform loops; the adaptive
family follows its constant-telemetry surrogate — faithful when per-chunk
rates are homogeneous, approximate under strong noise/imbalance.  Serving
what-ifs gather their per-chunk request costs from the float64 host prefix
(exact integer indexing) before the float32 device recurrence, so large
request totals no longer lose precision against the float64 closed-form
STATIC branch.
"""

from __future__ import annotations

import functools
import math
import os
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as Pspec

from ...core.jaxsched import (chunk_schedule, staticsteal_schedule,
                              weighted_adaptive_schedule)
from ...core.portfolio import ADAPTIVE_SET
from ...distributed.sharding import lane_count, lane_spec, pad_lanes
from ...launch.mesh import campaign_mesh
from ...tracing import span
from ..workloads import profile_digest as _profile_digest
from ..workloads import stack_prefix_grids
from .base import (BatchResult, InstancePerturb, InstanceSpec, LockstepRequest,
                   SimBackend, combined_pe_scale, needs_closed_form,
                   sigma_scale_of)
from .python import InstanceResult, _h_eff, run_instance as _py_run_instance

#: lax.while_loop buffer buckets for schedule length (powers of four keep
#: jit recompiles bounded); the last bucket must exceed EVENT_CAP plus
#: StaticSteal's steal-split slack.
_K_BUCKETS = (256, 1024, 4096, 16384, 65536, 262144)
#: max elements per (B, K) device array in one call (~16 MB float32)
_MAX_ELEMS = 1 << 22
#: the precompute's tile: lanes (the f32 sublane tile) x chunk segment
_TILE_ROWS = 8
_TILE_SEG = 512     # the event kernel's segment (kernels/event_loop.py)

#: env var naming the default sequential event core
EVENT_CORE_ENV = "REPRO_EVENT_CORE"
EVENT_CORES = ("while_loop", "pallas")
#: env var clamping the campaign mesh's data axis (lanes shard over it);
#: unset means "all local devices", 1 disables sharding entirely
DATA_PARALLEL_ENV = "REPRO_DATA_PARALLEL"
#: env var toggling double-buffered async dispatch ("0" restores the
#: synchronous pack -> dispatch -> drain loop)
ASYNC_DISPATCH_ENV = "REPRO_ASYNC_DISPATCH"
#: env var toggling the weighted adaptive surrogates under perturbed /
#: heterogeneous PE speeds ("0" keeps the weights-at-1 recurrences — the
#: A/B knob for the two-pass fidelity benchmarks)
ADAPTIVE_REWEIGHT_ENV = "REPRO_ADAPTIVE_REWEIGHT"


def _next_bucket(n: int) -> int:
    for b in _K_BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"schedule length {n} exceeds largest bucket")


def _pow2_rows(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


def _tile_shape(B: int, K: int) -> Tuple[int, int]:
    """(rows, seg) of one precompute tile of a (B, K) lane block: both
    divide the block's sides."""
    return math.gcd(B, _TILE_ROWS), math.gcd(K, _TILE_SEG)


def _live_slots(lens: np.ndarray, B: int, K: int, shards: int = 1) -> int:
    """Slots of a (B, K) dispatch inside the precompute's live tiles, its
    first ``len(lens)`` lanes holding ``lens`` chunks and the rest none;
    each of ``shards`` equal lane blocks is tiled on its own."""
    rows, seg = _tile_shape(B // shards, K)
    top = np.zeros(B, np.int64)
    top[:len(lens)] = lens
    return int((-(-top.reshape(-1, rows).max(axis=1) // seg)).sum()
               ) * rows * seg


def resolve_event_core(kernel: Optional[str] = None) -> str:
    """Resolve the sequential event core: explicit ``kernel=`` argument,
    else ``REPRO_EVENT_CORE``, else the platform default (``"auto"``):
    the Mosaic-compiled Pallas kernel on TPU, the vmapped
    ``lax.while_loop`` reference on CPU, where Pallas only interprets (the
    policy lives in ``kernels.ops.preferred_event_core``)."""
    name = (kernel or os.environ.get(EVENT_CORE_ENV) or "auto").lower()
    if name == "auto":
        from ...kernels.ops import preferred_event_core
        return preferred_event_core()
    if name not in EVENT_CORES:
        raise ValueError(f"unknown event core {name!r}; "
                         f"available: ['auto', *{list(EVENT_CORES)}]")
    return name


def resolve_data_parallel(data_parallel: Optional[int] = None) -> int:
    """Resolve the campaign mesh's data extent: explicit argument, else
    ``REPRO_DATA_PARALLEL``, else every local device.  Always clamped to
    the local device count (``make_host_mesh`` clamps again on its side)."""
    if data_parallel is None:
        env = os.environ.get(DATA_PARALLEL_ENV)
        data_parallel = int(env) if env else len(jax.devices())
    if data_parallel < 1:
        raise ValueError(f"data_parallel must be >= 1, got {data_parallel}")
    return min(data_parallel, len(jax.devices()))


def resolve_async_dispatch(async_dispatch: Optional[bool] = None) -> bool:
    if async_dispatch is None:
        return os.environ.get(ASYNC_DISPATCH_ENV, "1") != "0"
    return bool(async_dispatch)


def resolve_adaptive_reweight(adaptive_reweight: Optional[bool] = None
                              ) -> bool:
    if adaptive_reweight is None:
        return os.environ.get(ADAPTIVE_REWEIGHT_ENV, "1") != "0"
    return bool(adaptive_reweight)


class _LRU:
    """Tiny LRU mapping bounding the process-wide caches (schedules, steal
    replays, device-resident grid stacks) of the singleton backend.  Every
    cache counts its hits and misses; the trace reports those of the
    schedule and steal caches (``repro.events.rows``), and the grid cache's
    are kept for a debugger alone."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()
        self.hits = self.misses = 0

    def get(self, key, default=None):
        try:
            self._d.move_to_end(key)
        except KeyError:
            self.misses += 1
            return default
        self.hits += 1
        return self._d[key]

    def put(self, key, value) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)

    def __len__(self) -> int:
        return len(self._d)


# ---------------------------------------------------------------------------
# jitted cores (module-level so the compile cache is shared across backends)
# ---------------------------------------------------------------------------

def _core_while(eff, speed, jitter, h_eff, bcost, forced, count):
    """Reference sequential core: vmapped ``lax.while_loop`` over per-PE
    finish times — argmin assignment (ties to the lowest index), forced-PE
    rows for StaticSteal.  The accuracy oracle every other core must match
    bit-for-bit: ``fin[pe] += h_eff + eff[i] * speed[pe] + bcost``."""

    def one(eff, speed, jitter, h_eff, bc, forced, cnt):
        def body(carry):
            i, fin = carry
            pe = jnp.where(forced[i] >= 0, forced[i], jnp.argmin(fin))
            fin = fin.at[pe].add(h_eff + eff[i] * speed[pe] + bc)
            return i + 1, fin

        _, fin = lax.while_loop(lambda c: c[0] < cnt, body,
                                (jnp.asarray(0, jnp.int32), jitter))
        return fin

    return jax.vmap(one)(eff, speed, jitter, h_eff, bcost, forced, count)


def _core_finish(core: str, eff, speed, jitter, h_eff, bcost, forced,
                 count):
    """Dispatch to the selected sequential core (``core`` is static).

    The Pallas path goes through ``kernels.ops`` so the interpret-on-CPU /
    Mosaic-on-TPU policy stays in one place."""
    if core == "pallas":
        from ...kernels.ops import event_finish
        return event_finish(eff, speed, jitter, h_eff, bcost, forced, count)
    return _core_while(eff, speed, jitter, h_eff, bcost, forced, count)


def _effective_costs(grids, gs, grid_id, starts, sizes, loc, noise, count):
    """Per-chunk effective costs (B, K): the prefix-grid interpolation of
    each chunk's work, times its locality inflation and noise.

    Only the (rows x seg) tiles that some lane of their row block reaches
    (``count``) are computed, one tile per loop step; every other slot
    holds no chunk (starts = sizes = loc = 0), where the formula gives
    0.0, so the result is the dense one bit for bit.

    The operations go under the scope ``precompute``, the loop itself does
    not: a device trace shows a while loop as one operation around those
    of its body, which would count the body twice."""
    B, K = starts.shape
    G = grids.shape[1] - 1
    rows, seg = _tile_shape(B, K)
    with jax.named_scope("precompute"):
        n = (count.reshape(-1, rows).max(axis=1) + seg - 1) // seg
        ends = jnp.cumsum(n)
        tiles, zeros = ends[-1], jnp.zeros((B, K), jnp.float32)

    def tile(t, eff):
        with jax.named_scope("precompute"):
            blk = jnp.sum(ends <= t).astype(jnp.int32)
            r0 = blk * rows
            c0 = (t - ends[blk] + n[blk]) * seg
            cut = lambda x: lax.dynamic_slice(x, (r0, c0), (rows, seg))
            gid = lax.dynamic_slice(grid_id, (r0,), (rows,))[:, None]
            g = lax.dynamic_slice(gs, (r0,), (rows,))[:, None]

            def pref(x):
                pos = x.astype(jnp.float32) * g
                i = jnp.clip(pos.astype(jnp.int32), 0, G - 1)
                lo = grids[gid, i]
                return lo + (pos - i) * (grids[gid, i + 1] - lo)

            st, sz = cut(starts), cut(sizes)
            out = (pref(st + sz) - pref(st)) * cut(loc) * cut(noise)
            return lax.dynamic_update_slice(eff, out, (r0, c0))

    return lax.fori_loop(0, tiles, tile, zeros)


def _batched_events_impl(P: int, core: str, grids, grid_id, inv_n, starts,
                         sizes, loc, count, forced, seeds, h_eff, bcost,
                         pe_mult, sig_scale, sigma, jitter_max,
                         speed_spread):
    """Batched event loop: shared data-parallel precompute + one sequential
    core call.

    grids (S, G+1) f32; per-lane arrays: grid_id (B,), inv_n (B,),
    starts/sizes (B, K) i32, loc (B, K) f32, count (B,), forced (B, K) i32
    (-1 = argmin assignment), seeds (B,) u32, h_eff/bcost (B,),
    pe_mult (B, P) f32 per-PE execution-time multipliers and sig_scale (B,)
    f32 noise-sigma scales (the perturbation-injection lanes — all-1.0 rows
    are exact IEEE no-ops, so unperturbed lanes stay bit-identical and the
    event cores never see perturbation state).
    Returns (makespan (B,), lib (B,), finish (B, P)).
    """
    G = grids.shape[1] - 1
    K = starts.shape[1]

    def draws(seed, ss):
        key = jax.random.PRNGKey(seed)
        kj, ks, kn = jax.random.split(key, 3)
        jitter = jax.random.uniform(kj, (P,)) * jitter_max
        speed = jnp.clip(1.0 + speed_spread * jax.random.normal(ks, (P,)),
                         0.8, 1.25)
        noise = jnp.exp((sigma * ss) * jax.random.normal(kn, (K,)))
        return jitter, speed, noise

    # the scopes name the device operations in a profile; metadata only
    with jax.named_scope("precompute"):
        jitter, speed, noise = jax.vmap(draws)(seeds, sig_scale)
        # perturbation / heterogeneity enters HERE, in the shared
        # precompute — upstream of every event core, so while_loop and
        # Pallas stay identical
        speed = speed * pe_mult
        gs = G * inv_n
    eff = _effective_costs(grids, gs, grid_id, starts, sizes, loc, noise,
                           count)
    with jax.named_scope("event_core"):
        fin = _core_finish(core, eff, speed, jitter, h_eff, bcost, forced,
                           count)
        mk = fin.max(axis=1)
        lib = jnp.where(mk > 0.0, (1.0 - fin.mean(axis=1) / mk) * 100.0,
                        0.0)
    return mk, lib, fin


def _wave_eval_impl(R: int, core: str, eff, count, forced, init_avail, h):
    """Batched what-if over precomputed per-chunk request costs.

    eff (A, K) f32 — gathered host-side from the float64 cost prefix with
    exact integer indexing, so no interpolation and no float32 prefix
    cancellation; init_avail (R,) busy offsets shared by every candidate.
    Runs the same sequential core as the campaign path (unit speeds, zero
    jitter beyond the busy offsets)."""
    A = eff.shape[0]
    speed = jnp.ones((A, R), jnp.float32)
    jitter = jnp.broadcast_to(init_avail.astype(jnp.float32), (A, R))
    h_eff = jnp.full((A,), h, jnp.float32)
    bc = jnp.zeros((A,), jnp.float32)
    fin = _core_finish(core, eff, speed, jitter, h_eff, bc, forced, count)
    return fin.max(axis=1)


def _route_eval_impl(R: int, core: str, eff, count, forced, init_avails, h):
    """Fleet variant of :func:`_wave_eval_impl`: every candidate row carries
    its OWN (R,) busy-offset vector (rows span replica groups with distinct
    busy-states, not just algorithms over one wave), so ``init_avails`` is
    (A, R) instead of a shared broadcast."""
    A = eff.shape[0]
    speed = jnp.ones((A, R), jnp.float32)
    jitter = init_avails.astype(jnp.float32)
    h_eff = jnp.full((A,), h, jnp.float32)
    bc = jnp.zeros((A,), jnp.float32)
    fin = _core_finish(core, eff, speed, jitter, h_eff, bc, forced, count)
    return fin.max(axis=1)


# donate_argnums was evaluated for both cores and rejected: donation only
# pays when an output can alias a donated input, and every output here —
# mk/lib (B,), finish (B, P), wave makespans (A,) — is orders of magnitude
# smaller than the (B, K) schedule buffers, so donation would be a no-op
# that warns per compiled shape on every platform.
_batched_events = jax.jit(_batched_events_impl, static_argnums=(0, 1))
_wave_eval = jax.jit(_wave_eval_impl, static_argnums=(0, 1))
_route_eval = jax.jit(_route_eval_impl, static_argnums=(0, 1))


# ---------------------------------------------------------------------------
# mesh-sharded cores
# ---------------------------------------------------------------------------
#
# Lanes are embarrassingly parallel over the leading batch axis, so every
# jitted core also exists shard_map'd over the campaign mesh's ``data``
# axis: each device runs the identical per-lane computation on its B/ndev
# shard, no collectives anywhere, and per-lane arithmetic (including the
# counter-based noise draws folded from per-lane seeds) is untouched — the
# sharded results are bit-identical to the single-device path by
# construction.  Callers pad the lane axis to a multiple of the mesh's data
# extent with ``count == 0`` rows and slice the padding off host-side.
# Builders are cached per (mesh, statics) so each compiled executable is
# reused across dispatches exactly like the unsharded jits.

@functools.lru_cache(maxsize=32)
def _sharded_events(mesh, P: int, core: str):
    lane, rep = lane_spec(mesh), Pspec()
    fn = jax.shard_map(functools.partial(_batched_events_impl, P, core),
                       mesh=mesh,
                       in_specs=(rep,) + (lane,) * 12 + (rep,) * 3,
                       out_specs=(lane, lane, lane),
                       check_vma=False)   # no replicated outputs/collectives
    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _sharded_wave(mesh, R: int, core: str):
    lane, rep = lane_spec(mesh), Pspec()
    fn = jax.shard_map(functools.partial(_wave_eval_impl, R, core),
                       mesh=mesh,
                       in_specs=(lane, lane, lane, rep, rep),
                       out_specs=lane, check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _sharded_route(mesh, R: int, core: str):
    lane, rep = lane_spec(mesh), Pspec()
    fn = jax.shard_map(functools.partial(_route_eval_impl, R, core),
                       mesh=mesh,
                       in_specs=(lane, lane, lane, lane, rep),
                       out_specs=lane, check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# backend
# ---------------------------------------------------------------------------

class JaxBatchedBackend(SimBackend):
    """Campaign-scale batched engine (see module docstring).

    ``kernel`` selects the sequential event core (``"while_loop"`` /
    ``"pallas"`` / ``"auto"``); ``None`` resolves ``REPRO_EVENT_CORE`` at
    construction time (backends are process-wide singletons).

    ``data_parallel`` sets the campaign mesh's data extent (``None``
    resolves ``REPRO_DATA_PARALLEL``, defaulting to every local device):
    with more than one device the batched lane dimension of every core —
    ``run_batch`` / ``run_lockstep`` instances and what-if candidate rows —
    executes under ``shard_map``, bit-identical to the single-device path
    (lanes padded to the mesh extent, padding masked by ``count == 0``).

    ``async_dispatch`` (``None`` resolves ``REPRO_ASYNC_DISPATCH``, default
    on) double-buffers the host side: the ragged-to-padded packing of batch
    t+1 overlaps the device executing batch t.
    """

    name = "jax"

    def __init__(self, kernel: Optional[str] = None,
                 data_parallel: Optional[int] = None,
                 async_dispatch: Optional[bool] = None,
                 adaptive_reweight: Optional[bool] = None):
        self.event_core = resolve_event_core(kernel)
        if self.event_core != "while_loop":
            self.name = f"jax-{self.event_core}"
        self.data_parallel = resolve_data_parallel(data_parallel)
        self.mesh = (campaign_mesh(self.data_parallel)
                     if self.data_parallel > 1 else None)
        self.async_dispatch = resolve_async_dispatch(async_dispatch)
        # weighted adaptive surrogates under non-uniform PE speeds (the
        # two-pass scheme's second pass); "off" keeps the weights-at-1
        # recurrences for fidelity A/B comparisons
        self.adaptive_reweight = resolve_adaptive_reweight(adaptive_reweight)
        # (alg, N, P, cp) -> sizes ndarray, for central-queue algorithms
        self._sched_cache = _LRU(512)
        # StaticSteal replays keyed additionally by the cost/locality params
        self._steal_cache = _LRU(128)
        # profile-stack digest -> padded device-resident (Sp, G+1) grids
        self._grids_cache = _LRU(4)

    # ---- mesh dispatch -----------------------------------------------------

    @property
    def _shards(self) -> int:
        return 1 if self.mesh is None else lane_count(self.mesh)

    def _pad_rows(self, n: int) -> int:
        """Lane-axis padding: the power-of-two row bucket (compile-cache
        friendly), rounded up to a multiple of the mesh's data extent so
        ``shard_map`` splits it evenly."""
        rows = _pow2_rows(n)
        return pad_lanes(rows, self.mesh) if self.mesh is not None else rows

    def _events_call(self, P: int, *args):
        if self.mesh is None:
            return _batched_events(P, self.event_core, *args)
        return _sharded_events(self.mesh, P, self.event_core)(*args)

    def _wave_call(self, R: int, *args):
        if self.mesh is None:
            return _wave_eval(R, self.event_core, *args)
        return _sharded_wave(self.mesh, R, self.event_core)(*args)

    def _route_call(self, R: int, *args):
        if self.mesh is None:
            return _route_eval(R, self.event_core, *args)
        return _sharded_route(self.mesh, R, self.event_core)(*args)

    # ---- schedule precompute ---------------------------------------------

    def _central_schedule(self, alg: int, N: int, P: int, cp: int,
                          cache: bool = True) -> np.ndarray:
        key = (alg, N, P, cp)
        hit = self._sched_cache.get(key)
        if hit is not None:
            return hit
        guess = -(-N // max(1, cp)) if alg == 1 else 256
        mc = _next_bucket(min(guess, _K_BUCKETS[-1]))
        with span("sched.build", kind=0):
            while True:
                sizes, count = chunk_schedule(alg, N, P, cp, max_chunks=mc)
                # slice host-side: eager jnp slicing compiles per output
                # shape
                sizes = np.asarray(sizes, dtype=np.int64)[: int(count)]
                if sizes.sum() == N or mc >= _K_BUCKETS[-1]:
                    break
                mc = _next_bucket(mc + 1)   # truncated: retry wider buffer
        if sizes.sum() != N:
            raise RuntimeError(
                f"schedule truncated: alg={alg} N={N} P={P} cp={cp}")
        if cache:
            self._sched_cache.put(key, sizes)
        return sizes

    def _steal_schedule(self, N: int, P: int, cp: int, profile, system,
                        cache: bool = True):
        unit = profile.total / N
        key = (N, P, cp, round(unit, 18), round(profile.locality_sens, 6),
               profile.c_loc, round(profile.memory_bound, 6), system.name)
        hit = self._steal_cache.get(key)
        if hit is not None:
            return hit
        ls = profile.locality_sens
        mc = _next_bucket(min(-(-N // max(1, cp)) + 8 * P * 34,
                              _K_BUCKETS[-1]))
        with span("sched.build", kind=1):
            while True:
                starts, sizes, pes, own, count = staticsteal_schedule(
                    N, P, cp, max_chunks=mc, unit=unit, h=system.h,
                    bcost=profile.memory_bound * system.boundary_cost,
                    base_infl=1.0 + ls * system.dyn_locality,
                    amp=ls * system.loc_amp, c_loc=float(profile.c_loc))
                count = int(count)
                sizes_np = np.asarray(sizes, dtype=np.int64)[:count]
                if sizes_np.sum() == N or mc >= _K_BUCKETS[-1]:
                    break
                mc = _next_bucket(mc + 1)
            if sizes_np.sum() != N:
                raise RuntimeError(f"steal schedule truncated: N={N} P={P}")
            out = (np.asarray(starts, np.int32)[:count],
                   sizes_np.astype(np.int32),
                   np.asarray(pes, np.int32)[:count],
                   np.asarray(own)[:count])
        if cache:
            self._steal_cache.put(key, out)
        return out

    def _weighted_schedule(self, alg: int, N: int, P: int, cp: int,
                           scale: np.ndarray):
        """Weighted adaptive schedule under a non-uniform PE-speed vector
        (the two-pass re-estimation: weights are the converged mean-1
        inverse speeds).  Cached under a 5-tuple key — the clean 4-tuple
        ``(alg, N, P, cp)`` entries can never collide with it, so perturbed
        lanes never poison unperturbed ones (test-enforced)."""
        w = 1.0 / scale
        w *= P / w.sum()
        wkey = tuple(np.round(w, 9))
        key = (alg, N, P, cp, wkey)
        hit = self._sched_cache.get(key)
        if hit is None:
            with span("sched.build", kind=2):
                hit = weighted_adaptive_schedule(alg, N, P, cp, w)
            self._sched_cache.put(key, hit)
        return hit

    def _event_rows(self, spec: InstanceSpec, profile, system):
        """(starts, sizes, loc, forced) numpy rows for one event instance."""
        N, P = profile.N, system.P
        ls = profile.locality_sens
        base_infl = 1.0 + ls * system.dyn_locality
        amp = ls * system.loc_amp
        c_loc = profile.c_loc
        if spec.alg == 5:
            starts, sizes, pes, own = self._steal_schedule(
                N, P, spec.chunk_param, profile, system)
            loc = np.where(own, 1.0,
                           base_infl + amp * c_loc / (sizes + c_loc))
            return starts, sizes, loc.astype(np.float32), pes
        scale = combined_pe_scale(system, spec.perturb)
        if (self.adaptive_reweight and spec.alg in ADAPTIVE_SET
                and scale is not None and not np.all(scale == 1.0)):
            sizes, pes = self._weighted_schedule(
                spec.alg, N, P, spec.chunk_param, scale)
            starts = np.concatenate(
                [[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
            loc = (base_infl + amp * c_loc / (sizes + c_loc)).astype(
                np.float32)
            return starts, sizes.astype(np.int32), loc, pes
        sizes = self._central_schedule(spec.alg, N, P, spec.chunk_param)
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
        loc = (base_infl + amp * c_loc / (sizes + c_loc)).astype(np.float32)
        return starts, sizes.astype(np.int32), loc, None

    def _grids_dev(self, profiles):
        """Device-resident padded grid stack, cached by profile content.

        The profile axis is padded to a power-of-two row bucket: a
        different number of (t, loop) rows must not recompile the jitted
        cores (padding rows are never gathered — grid_id only points at
        real profiles).  Caching keys on per-profile content digests, so
        lockstep replays that rebuild equal ``LoopProfile`` objects every
        time step still hit the same upload.
        """
        key = tuple(_profile_digest(p) for p in profiles)
        hit = self._grids_cache.get(key)
        if hit is not None:
            return hit
        grids = stack_prefix_grids(profiles)
        Sp = _pow2_rows(len(profiles))
        if Sp > len(profiles):
            grids = np.vstack([grids, np.zeros((Sp - len(profiles),
                                                grids.shape[1]), np.float32)])
        dev = jnp.asarray(grids)
        self._grids_cache.put(key, dev)
        return dev

    # ---- batch execution --------------------------------------------------

    def run_batch(self, profiles: Sequence, system,
                  specs: Sequence[InstanceSpec]) -> BatchResult:
        B = len(specs)
        lt = np.zeros(B)
        lib = np.zeros(B)
        nc = np.zeros(B, np.int64)
        event_ids: List[int] = []
        with span("backend.batch", instances=B):
            with span("backend.host_instances") as sp:
                for i, s in enumerate(specs):
                    profile = profiles[s.profile_id]
                    if s.alg == 0 or needs_closed_form(s.alg, profile.N,
                                                       s.chunk_param):
                        rng = np.random.default_rng(s.seed)
                        r = _py_run_instance(profile, system, s.alg,
                                             s.chunk_param, rng,
                                             perturb=s.perturb)
                        lt[i], lib[i], nc[i] = r.loop_time, r.lib, r.n_chunks
                    else:
                        event_ids.append(i)
                sp.set_metadata(closed=B - len(event_ids),
                                event=len(event_ids))
            if event_ids:
                mks, libs, _, counts = self._run_events(
                    profiles, system, [specs[i] for i in event_ids])
                for j, i in enumerate(event_ids):
                    lt[i], lib[i], nc[i] = mks[j], libs[j], counts[j]
        return BatchResult(loop_time=lt, lib=lib, n_chunks=nc)

    def _run_events(self, profiles, system, specs):
        """Evaluate event-loop instances; returns (mk, lib, finish, count)
        arrays in spec order."""
        P = system.P
        B = len(specs)
        mk = np.zeros(B)
        lb = np.zeros(B)
        fin = np.zeros((B, P))
        sched, steal = self._sched_cache, self._steal_cache
        hits0 = (sched.hits, sched.misses, steal.hits, steal.misses)
        with span("events.rows") as sp:
            grids_dev = self._grids_dev(profiles)
            rows = [self._event_rows(s, profiles[s.profile_id], system)
                    for s in specs]
            counts = np.array([len(r[1]) for r in rows], np.int32)

            # per-spec scalar lanes (gathered per bucket below)
            gid_all = np.fromiter((s.profile_id for s in specs), np.int32, B)
            inv_all = np.fromiter((1.0 / profiles[s.profile_id].N
                                   for s in specs), np.float32, B)
            seed_all = np.fromiter((s.fold_seed() for s in specs),
                                   np.uint32, B)
            h_all = np.fromiter((_h_eff(system, s.alg) for s in specs),
                                np.float32, B)
            bc_all = np.fromiter(
                (profiles[s.profile_id].memory_bound * system.boundary_cost
                 for s in specs), np.float32, B)
            # perturbation lanes: per-PE multipliers and sigma scales (rows
            # stay exactly 1.0 for clean lanes — IEEE-identity multiplies
            # downstream)
            pm_all = np.ones((B, P), np.float32)
            ss_all = np.ones(B, np.float32)
            for i, s in enumerate(specs):
                scale = combined_pe_scale(system, s.perturb)
                if scale is not None:
                    pm_all[i] = scale
                ss_all[i] = sigma_scale_of(s.perturb)

            by_bucket: Dict[int, List[int]] = {}
            for i, c in enumerate(counts):
                by_bucket.setdefault(_next_bucket(int(c)), []).append(i)
            sp.set_metadata(sched_hits=sched.hits - hits0[0],
                            sched_misses=sched.misses - hits0[1],
                            steal_hits=steal.hits - hits0[2],
                            steal_misses=steal.misses - hits0[3])

        def packed():
            """Host-side ragged-to-padded assembly, one yielded batch per
            dispatch.  A generator so the async loop below interleaves the
            packing of batch t+1 with the device executing batch t."""
            for K, ids in sorted(by_bucket.items()):
                # per-device row budget: a mesh holds shards x _MAX_ELEMS
                max_rows = max(8, (_MAX_ELEMS // K) * self._shards)
                for off in range(0, len(ids), max_rows):
                    sub = np.asarray(ids[off:off + max_rows])
                    n = len(sub)
                    Bp = self._pad_rows(n)
                    lens = counts[sub]
                    with span("events.pack", K=K, rows=Bp, real=n,
                              chunks=int(lens.sum()),
                              live=_live_slots(lens, Bp, K, self._shards)):
                        lanes = pack(sub, lens, Bp, K)
                    yield sub, lanes

        def pack(sub, lens, Bp, K):
            # ragged-to-padded assembly: one boolean scatter per field
            # instead of per-row element-wise packing loops
            n = len(sub)
            mask = np.arange(K, dtype=np.int32)[None, :] < lens[:, None]
            starts = np.zeros((Bp, K), np.int32)
            sizes = np.zeros((Bp, K), np.int32)
            loc = np.zeros((Bp, K), np.float32)
            forced = np.full((Bp, K), -1, np.int32)
            starts[:n][mask] = np.concatenate([rows[i][0] for i in sub])
            sizes[:n][mask] = np.concatenate([rows[i][1] for i in sub])
            loc[:n][mask] = np.concatenate([rows[i][2] for i in sub])
            forced[:n][mask] = np.concatenate(
                [rows[i][3] if rows[i][3] is not None
                 else np.full(lens[j], -1, np.int32)
                 for j, i in enumerate(sub)])
            gid = np.zeros(Bp, np.int32)
            inv_n = np.ones(Bp, np.float32)
            cnt = np.zeros(Bp, np.int32)
            seeds = np.zeros(Bp, np.uint32)
            h_eff = np.zeros(Bp, np.float32)
            bcost = np.zeros(Bp, np.float32)
            pe_mult = np.ones((Bp, P), np.float32)
            sscale = np.ones(Bp, np.float32)
            gid[:n] = gid_all[sub]
            inv_n[:n] = inv_all[sub]
            cnt[:n] = lens
            seeds[:n] = seed_all[sub]
            h_eff[:n] = h_all[sub]
            bcost[:n] = bc_all[sub]
            pe_mult[:n] = pm_all[sub]
            sscale[:n] = ss_all[sub]
            return (gid, inv_n, starts, sizes, loc, cnt, forced, seeds, h_eff,
                    bcost, pe_mult, sscale)

        def drain(sub, res):
            n = len(sub)
            with span("events.wait", rows=res[0].shape[0]):
                m, l, f = (np.asarray(x) for x in res)
            mk[sub], lb[sub], fin[sub] = m[:n], l[:n], f[:n]

        # double-buffered async dispatch: jax dispatch is asynchronous, so
        # holding exactly one in-flight batch lets the packing of batch t+1
        # (numpy, host) overlap the device executing batch t; draining after
        # the NEXT dispatch keeps one buffer's latency hidden.  Buffers are
        # donation-safe by construction: each dispatch packs fresh host
        # arrays, nothing aliases an in-flight device buffer (donation
        # itself stays rejected — see the note above the jitted cores).
        pending = None
        for sub, lanes in packed():
            with span("events.dispatch", P=P, K=lanes[2].shape[1],
                      rows=lanes[2].shape[0], grid_rows=grids_dev.shape[0],
                      grid_cols=grids_dev.shape[1]):
                res = self._events_call(
                    P, grids_dev, *lanes,
                    np.float32(system.noise_sigma), np.float32(system.jitter),
                    np.float32(system.speed_spread))
            if not self.async_dispatch:
                drain(sub, res)
                continue
            if pending is not None:
                drain(*pending)
            pending = (sub, res)
        if pending is not None:
            drain(*pending)
        return mk, lb, fin, counts

    def run_lockstep(self, profiles: Sequence, system,
                     requests: Sequence[LockstepRequest]) -> BatchResult:
        """One lockstep replay step as a single batched device call.

        Per request the lane rng is consumed exactly like the sequential
        ``run_instance`` path would at the same stream position: STATIC and
        over-cap SS/StaticSteal instances run the reference closed forms on
        the lane rng directly, every event-loop instance draws one integer
        as its stateless fold seed.  All event instances across all lanes
        then execute as one ``_run_events`` batch — results are bit-identical
        to sequential JAX replays because each lane's noise depends only on
        its fold seed, never on batch order or size.
        """
        B = len(requests)
        lt = np.zeros(B)
        lib = np.zeros(B)
        nc = np.zeros(B, np.int64)
        event_ids: List[int] = []
        specs: List[InstanceSpec] = []
        with span("backend.lockstep", instances=B):
            with span("backend.host_instances") as sp:
                for i, q in enumerate(requests):
                    profile = profiles[q.profile_id]
                    if q.alg == 0 or needs_closed_form(q.alg, profile.N,
                                                       q.chunk_param):
                        r = _py_run_instance(profile, system, q.alg,
                                             q.chunk_param, q.rng,
                                             perturb=q.perturb)
                        lt[i], lib[i], nc[i] = r.loop_time, r.lib, r.n_chunks
                    else:
                        seed = (int(q.rng.integers(0, 2**31 - 1)),)
                        specs.append(InstanceSpec(
                            profile_id=q.profile_id, alg=q.alg,
                            chunk_param=q.chunk_param, seed=seed,
                            perturb=q.perturb))
                        event_ids.append(i)
                sp.set_metadata(closed=B - len(specs), event=len(specs))
            if specs:
                mks, libs, _, counts = self._run_events(profiles, system,
                                                        specs)
                for j, i in enumerate(event_ids):
                    lt[i], lib[i], nc[i] = mks[j], libs[j], counts[j]
        return BatchResult(loop_time=lt, lib=lib, n_chunks=nc)

    # ---- single instance (selector path) ----------------------------------

    def run_instance(self, profile, system, alg: int, chunk_param: int,
                     rng, record_chunks: bool = False,
                     perturb: Optional[InstancePerturb] = None
                     ) -> InstanceResult:
        if alg == 0 or needs_closed_form(alg, profile.N, chunk_param):
            return _py_run_instance(profile, system, alg, chunk_param, rng,
                                    record_chunks, perturb)
        # stateless fold seed drawn from the caller's stream so repeated
        # calls stay reproducible AND distinct
        seed = (int(rng.integers(0, 2**31 - 1)),)
        spec = InstanceSpec(profile_id=0, alg=alg, chunk_param=chunk_param,
                            seed=seed, perturb=perturb)
        mk, lib, fin, counts = self._run_events([profile], system, [spec])
        sizes = None
        if record_chunks:
            _, sz, _, _ = self._event_rows(spec, profile, system)
            sizes = [int(c) for c in sz]
        return InstanceResult(loop_time=float(mk[0]), finish=fin[0],
                              n_chunks=int(counts[0]), chunk_sizes=sizes)

    # ---- serving what-if ---------------------------------------------------

    def what_if_wave(self, prefix: np.ndarray, n_replicas: int,
                     init_avail: np.ndarray, h: float, fixed: float,
                     algs: Sequence[int], chunk_param: int = 0
                     ) -> np.ndarray:
        N = len(prefix) - 1
        R = n_replicas
        out = np.zeros(len(algs))
        prefix = np.asarray(prefix, dtype=np.float64)
        batched: List[Tuple[int, np.ndarray, np.ndarray,
                            Optional[np.ndarray]]] = []
        for k, alg in enumerate(algs):
            if alg == 0 and chunk_param <= 0:
                bounds = np.linspace(0, N, R + 1).round().astype(int)
                free = np.asarray(init_avail, dtype=np.float64).copy()
                nonempty = np.diff(bounds) > 0
                free[: R] += np.diff(prefix[bounds]) + fixed * nonempty
                out[k] = free.max()
                continue
            # cache=False: wave sizes and mean costs drift per dispatch, so
            # online what-ifs would fill the process-wide caches with
            # never-reused entries
            if alg == 5:
                unit = float(prefix[-1] - prefix[0]) / max(N, 1)
                st, sz, pes, _ = self._steal_schedule(
                    N, R, chunk_param, _UniformStub(N, unit), _NoLocStub(),
                    cache=False)
                batched.append((k, st.astype(np.int64), sz, pes))
            else:
                sz = self._central_schedule(alg, N, R, chunk_param,
                                            cache=False)
                st = np.concatenate([[0], np.cumsum(sz)[:-1]])
                batched.append((k, st, sz.astype(np.int32), None))
        if batched:
            # per-chunk costs gathered from the float64 prefix host-side
            # (exact integer indexing): the float32 rounding then happens on
            # the small per-chunk values, not on the large cumulative totals.
            # Schedule slots are padded to a power-of-two bucket so online
            # what-ifs with drifting wave sizes never recompile _wave_eval.
            K = _pow2_rows(max(len(b[2]) for b in batched))
            A = len(batched)
            # candidate rows shard over the mesh's data axis: pad to its
            # extent with count==0 rows (masked, sliced off below)
            Ap = pad_lanes(A, self.mesh) if self.mesh is not None else A
            eff = np.zeros((Ap, K), np.float32)
            forced = np.full((Ap, K), -1, np.int32)
            cnt = np.zeros(Ap, np.int32)
            for j, (_, st, sz, pes) in enumerate(batched):
                n = len(sz)
                eff[j, :n] = prefix[st + sz] - prefix[st]
                cnt[j] = n
                if pes is not None:
                    forced[j, :n] = pes
            mks = np.asarray(self._wave_call(
                R, eff, cnt, forced,
                np.asarray(init_avail, np.float32), np.float32(h + fixed)))
            for j, (k, *_rest) in enumerate(batched):
                out[k] = mks[j]
        return out

    def what_if_routes(self, prefixes: Sequence[np.ndarray],
                       n_replicas: int,
                       init_avails: Sequence[np.ndarray], h: float,
                       fixed: float,
                       cands: Sequence[Tuple[int, int, int]]) -> np.ndarray:
        """Every (slot, alg, chunk) candidate row of a fleet routing
        decision in ONE ``_route_eval`` call — the rows differ in busy-state
        as well as schedule, so each carries its own (R,) offset vector.
        STATIC default-chunk rows take the float64 closed form host-side,
        exactly like :meth:`what_if_wave`."""
        R = n_replicas
        prefixes = [np.asarray(p, dtype=np.float64) for p in prefixes]
        avails = [np.asarray(a, dtype=np.float64) for a in init_avails]
        out = np.zeros(len(cands))
        batched: List[Tuple[int, int, np.ndarray, np.ndarray,
                            Optional[np.ndarray]]] = []
        for i, (slot, alg, cp) in enumerate(cands):
            prefix = prefixes[slot]
            N = len(prefix) - 1
            if N <= 0:
                out[i] = avails[slot].max() if len(avails[slot]) else 0.0
                continue
            if alg == 0 and cp <= 0:
                bounds = np.linspace(0, N, R + 1).round().astype(int)
                free = avails[slot].copy()
                nonempty = np.diff(bounds) > 0
                free[: R] += np.diff(prefix[bounds]) + fixed * nonempty
                out[i] = free.max()
                continue
            if alg == 5:
                # steal cache keys include the per-wave unit cost, so it
                # would never hit — skip it
                unit = float(prefix[-1] - prefix[0]) / max(N, 1)
                st, sz, pes, _ = self._steal_schedule(
                    N, R, cp, _UniformStub(N, unit), _NoLocStub(),
                    cache=False)
                batched.append((i, slot, st.astype(np.int64), sz, pes))
            else:
                # cache=True (unlike what_if_wave): a saturated fleet
                # dispatches quota-sized shards wave after wave, so the
                # (alg, N, P, cp) keys DO repeat; the LRU bound caps the
                # drifting-size tail
                sz = self._central_schedule(alg, N, R, cp)
                st = np.concatenate([[0], np.cumsum(sz)[:-1]])
                batched.append((i, slot, st, sz.astype(np.int32), None))
        if batched:
            K = _pow2_rows(max(len(b[3]) for b in batched))
            A = len(batched)
            Ap = pad_lanes(A, self.mesh) if self.mesh is not None else A
            eff = np.zeros((Ap, K), np.float32)
            forced = np.full((Ap, K), -1, np.int32)
            cnt = np.zeros(Ap, np.int32)
            av = np.zeros((Ap, R), np.float32)
            for j, (_, slot, st, sz, pes) in enumerate(batched):
                n = len(sz)
                prefix = prefixes[slot]
                eff[j, :n] = prefix[st + sz] - prefix[st]
                cnt[j] = n
                av[j] = avails[slot]
                if pes is not None:
                    forced[j, :n] = pes
            mks = np.asarray(self._route_call(
                R, eff, cnt, forced, av, np.float32(h + fixed)))
            for j, (i, *_rest) in enumerate(batched):
                out[i] = mks[j]
        return out


class _UniformStub:
    """Minimal profile stand-in for serving what-if StaticSteal replays."""

    def __init__(self, N, unit):
        self.N, self.unit = N, unit
        self.total = N * unit
        self.locality_sens = 0.0
        self.c_loc = 64
        self.memory_bound = 0.0


class _NoLocStub:
    name = "wave"
    h = 0.0
    boundary_cost = 0.0
    dyn_locality = 0.0
    loc_amp = 0.0
