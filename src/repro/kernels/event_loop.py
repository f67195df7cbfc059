"""OpenMP self-scheduling event loop — Pallas TPU kernel.

The batched simulation backend's hot path is a sequential recurrence: for
each dispatched chunk, assign it to the earliest-available PE (or to its
pre-assigned owner for StaticSteal) and advance that PE's finish time.
``lax.while_loop`` runs one XLA loop iteration per chunk; this kernel runs
the whole recurrence inside one kernel launch instead.

Layout is lanes-last, so every block is tile-aligned for Mosaic: the
wrapper transposes the ``(B, K)`` chunk arrays to ``(K, B)`` and pads the
lane axis B to a multiple of 128 with ``count == 0`` lanes.  The grid is
``(B // 128, K // seg)`` with the chunk-segment axis innermost
(sequential): each step streams a ``(seg, 128)`` block of effective costs
and forced owners, and the ``(P, 128)`` per-PE finish times of 128 lanes
live in VMEM scratch across segments.  One chunk step runs for all 128
lanes at once:

* argmin over the PE (sublane) axis as the minimum followed by the first
  PE index holding it — the same tie rule as ``jnp.argmin``;
* the update as a one-hot masked add in place of a dynamic scatter; a lane
  whose chunks are exhausted is masked and keeps its finish times.

The trip count of a segment is the largest chunk count among its 128 lanes
(scalar-prefetched per lane block), rounded up to the 8-row sublane tile;
segments past every lane's count cost one skipped ``pl.when``.

Accuracy contract (``tests/test_event_kernel.py``): the kernel is
**bit-identical in interpret mode** to the vmapped ``lax.while_loop``
reference core in ``repro.sim.backends.jax_batched`` — per chunk and lane
the op sequence ``fin[pe] += h_eff + eff[i] * speed[pe] + bcost`` (argmin
ties to the lowest PE index) is replicated exactly.  Effective costs,
including the prefix-grid gather and every random draw, come from the
backend's shared XLA precompute, so both cores see the same inputs.  The
entry point takes an explicit ``interpret`` flag; the platform policy
(interpret on CPU, Mosaic-compiled on TPU) lives in ``kernels/ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: default chunk-segment length; divides every K bucket the backend pads to
DEFAULT_SEG = 512
#: lanes per kernel block (the vreg lane width)
LANES = 128
#: chunk rows per unrolled group (the f32 sublane tile)
ROWS = 8


def _loop_kernel(nmax_ref, eff_ref, forced_ref, speed_ref, jit_ref, hb_ref,
                 cnt_ref, out_ref, fin_scr, *, seg: int, n_seg: int):
    bi, si = pl.program_id(0), pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        fin_scr[...] = jit_ref[...]

    n = jnp.clip(nmax_ref[bi] - si * seg, 0, seg)

    @pl.when(n > 0)     # segments past every lane's chunk count touch nothing
    def _run():
        P = fin_scr.shape[0]
        pe_id = lax.broadcasted_iota(jnp.int32, fin_scr.shape, 0)
        speed = speed_ref[...]
        h_eff, bc = hb_ref[0:1, :], hb_ref[1:2, :]
        left = cnt_ref[...] - si * seg      # (1, LANES) chunks still owed

        def group(g, fin):
            base = pl.multiple_of(g * ROWS, ROWS)
            eff = eff_ref[pl.ds(base, ROWS), :]
            forced = forced_ref[pl.ds(base, ROWS), :]
            for r in range(ROWS):
                low = jnp.min(fin, axis=0, keepdims=True)
                first = jnp.min(jnp.where(fin == low, pe_id, P), axis=0,
                                keepdims=True)
                f = forced[r:r + 1, :]
                pe = jnp.where(f >= 0, f, first)
                hit = (pe_id == pe) & (base + r < left)
                fin = jnp.where(hit, fin + (h_eff + eff[r:r + 1, :] * speed
                                            + bc), fin)
            return fin

        fin_scr[...] = lax.fori_loop(0, (n + ROWS - 1) // ROWS, group,
                                     fin_scr[...])

    @pl.when(si == n_seg - 1)
    def _emit():
        out_ref[...] = fin_scr[...]


def _seg_for(K: int, seg: int) -> int:
    seg = min(seg, K)
    if K % seg or seg % ROWS:
        raise ValueError(f"segment {seg} must divide padded length {K} "
                         f"and be a multiple of {ROWS}")
    return seg


@functools.partial(jax.jit, static_argnames=("seg", "interpret"))
def event_finish(eff, speed, jitter, h_eff, bcost, forced, count, *,
                 seg: int = DEFAULT_SEG, interpret: bool = False):
    """Sequential assignment core over precomputed effective chunk costs.

    eff (B, K) f32, speed/jitter (B, P) f32, h_eff/bcost (B,) f32,
    forced (B, K) i32 (-1 = argmin assignment), count (B,) i32.
    Returns finish (B, P) f32.
    """
    B, K = eff.shape
    P = speed.shape[1]
    seg = _seg_for(K, seg)
    n_seg = K // seg
    Bp = -(-B // LANES) * LANES
    pad = ((0, Bp - B), (0, 0))
    lanes_last = lambda x, v=0: jnp.pad(x, pad, constant_values=v).T
    count = jnp.pad(count.astype(jnp.int32), (0, Bp - B))
    nmax = count.reshape(Bp // LANES, LANES).max(axis=1)
    chunk = pl.BlockSpec((seg, LANES), lambda bi, si, nmax: (si, bi))
    lane = lambda rows: pl.BlockSpec((rows, LANES),
                                     lambda bi, si, nmax: (0, bi))
    kernel = functools.partial(_loop_kernel, seg=seg, n_seg=n_seg)
    fin = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                          # per-block nmax
            grid=(Bp // LANES, n_seg),
            in_specs=[chunk,                                # eff
                      chunk,                                # forced
                      lane(P),                              # speed
                      lane(P),                              # jitter
                      lane(2),                              # h_eff, bcost
                      lane(1)],                             # count
            out_specs=lane(P),
            scratch_shapes=[pltpu.VMEM((P, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((P, Bp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(nmax, lanes_last(eff), lanes_last(forced, -1), lanes_last(speed),
      lanes_last(jitter), lanes_last(jnp.stack([h_eff, bcost], axis=1)),
      count.reshape(1, Bp))
    return fin.T[:B]
