"""Pallas TPU fused DDPM denoise-update kernels.

The p_sample update  x_{t-1} = (x_t − c_eps·ε̂)/√ar + keep·σ·z  is executed
T times per generated image — the paper's inner loop.  Unfused it is 4 HBM
round-trips of the image tensor; the kernels here fuse it into one read of
(x_t, ε̂, z) + one write, with the scalar coefficients staged in SMEM.

:func:`traj_masked_step` is the serving engine's whole tick as ONE program:
per-lane coefficient gather from an SMEM (rows, C) table by (clamped)
per-lane COLUMN, the update, the reference sampler's post-step clip, and
the active-lane select — collapsing the jnp chain gather→step→clip→where
(≈4+ HBM round-trips of the slot array) into a single read of (x, ε̂, z) +
one write.  Inactive lanes pass through bit-unchanged, including
out-of-range columns.  Columns index TRAJECTORY positions
(``repro.diffusion.sampler``): the table's rows are the canonical (c_eps,
ar, sigma, keep) pair coefficients, so a strided DDIM tick and the dense
DDPM tick are the SAME kernel — several trajectories concatenate
column-wise into one table and heterogeneous lanes just gather different
columns.  :func:`ddpm_step` (every sample stepped, no clip) and
:func:`ddpm_masked_step` (timestep-indexed, col = T - t over the dense
ancestral table) are thin views of the same kernel.

The kernel computes the jnp reference's expression
(``StepBackend.index_step``): ``(x - c_eps·ε̂) / sqrt(ar) + keep·σ·z``.

Layout: each lane's pixels are tiled as (rows, 128) f32 (zero-padded up to
whole blocks); grid = (lanes, rows / R) over (None, R, 128) VMEM blocks, R
a multiple of the sublane tile (8 for f32) or the whole lane.  The (S, 2)
lane meta and the (rows, C) table are whole-array SMEM operands indexed by
``pl.program_id(0)`` — the block shapes the v5e compiler accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.diffusion.schedule import ancestral_pair_coefs
from repro.kernels import pallas_call

_LANE = 128            # minor (lane) dimension of a TPU vreg


def _tiling(d: int, dtype, block: int):
    """(rows, R) for one lane of ``d`` pixels: the lane is laid out as
    (rows, 128) and blocked R rows at a time.  ``block`` (elements) is
    rounded to whole sublane tiles; a lane shorter than one block is ONE
    full-extent block, otherwise rows pad up to a multiple of R."""
    sub = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    rows = -(-d // _LANE)
    r = max(sub, (block // _LANE) // sub * sub)
    if rows <= r:
        return rows, rows
    return -(-rows // r) * r, r


def _to_tiles(a, rows: int):
    """(S, ...) -> (S, rows, 128), zero-padding each lane's pixels."""
    flat = a.reshape(a.shape[0], -1)
    pad = rows * _LANE - flat.shape[1]
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(a.shape[0], rows, _LANE)


def ddpm_step_coefs(sched, t):
    """Per-sample canonical coefficients (c_eps, ar, sigma, keep) for the
    dense pair (t, t-1): (B,) -> (B, 4) f32 — the rows
    :func:`ddpm_step` reads."""
    return ancestral_pair_coefs(sched, t).T


def ddpm_step(x_t, eps_hat, noise, coefs, *, block: int = 4096):
    """Fused denoise update of every sample.  x_t/eps_hat/noise: (B, ...);
    coefs: (B, 4) canonical rows (:func:`ddpm_step_coefs`).  No clip —
    the caller's StepBackend owns it."""
    b = x_t.shape[0]
    return traj_masked_step(x_t, jnp.arange(b, dtype=jnp.int32), eps_hat,
                            noise, jnp.ones((b,), bool), coefs.T, clip=0.0,
                            block=block)


# ---------------------------------------------------------------------------
# fused masked tick: gather + step + clip + active-select in one program
# ---------------------------------------------------------------------------
def masked_step_tables(sched) -> jnp.ndarray:
    """(4, T) canonical coefficient table for the DENSE ancestral chain,
    column j holding the trajectory-position-j step (timestep t = T - j):
    rows (c_eps, ar, sigma, keep) — see ``repro.diffusion.schedule``.
    Long-lived callers (the serving engine) build their table(s) ONCE and
    pass them to every tick, hoisting the per-step coefficient recompute
    out of the hot loop entirely.  Strided trajectories build theirs via
    ``repro.diffusion.sampler.Sampler.tables`` — same layout, same kernel.
    """
    t = jnp.arange(sched.T, 0, -1, dtype=jnp.int32)
    return ancestral_pair_coefs(sched, t)


def masked_step_bytes(x, C: int, *, block: int = 4096,
                      rows: int = 4) -> int:
    """HBM bytes the fused masked kernel advertises to XLA (its
    ``pl.CostEstimate``): one read of (x, ε̂, z) + one write of the output
    — accounting the tile padding the kernel actually streams — plus the
    SMEM-staged (rows, C) table and per-lane (S, 2) meta ints.  ``rows``
    is 4 for the bare (c_eps, ar, sigma, keep) table and 5 when the menu
    carries the classifier-free-guidance row (the kernel stages whatever
    it is handed; the update only reads rows 0-3)."""
    s = x.shape[0]
    tile_rows, _ = _tiling(x.size // s, x.dtype, block)
    dp = tile_rows * _LANE
    return 4 * s * dp * x.dtype.itemsize + rows * C * 4 + s * 2 * 4


def lane_meta(cols, active, C: int) -> jnp.ndarray:
    """(S, 2) i32 SMEM meta block — (clamped column, active flag) per lane
    — the only per-tick scalars :func:`traj_masked_step` stages.  Split out
    so callers scanning the kernel (the serving engine runs k ticks per
    dispatch under ``lax.scan``) can see the scan invariant at the seam:
    everything else the kernel reads (the (rows, C) table, block geometry,
    clip) is a trace-time constant, so the whole k-tick window lowers to
    ONE Pallas program re-entered k times with fresh (meta, x, ε̂, z) —
    no per-tick retrace, no per-tick recompile.  Inactive lanes pass x
    through bit-unchanged, which is the done-latching the scan relies on:
    a lane whose ``active`` drops mid-window carries its cut tensor
    bitwise to the scan boundary."""
    col_safe = jnp.clip(cols, 0, C - 1)
    return jnp.stack([col_safe, active.astype(jnp.int32)], axis=-1)


def _masked_step_kernel(meta_ref, tab_ref, x_ref, eps_ref, noise_ref, o_ref,
                        *, clip):
    """meta: (S, 2) i32 = (col_safe, active) per lane, SMEM; tab: (rows,
    C) f32 SMEM (rows 0-3 = c_eps, ar, sigma, keep; any further rows —
    e.g. the guidance row — are combine metadata consumed BEFORE this
    kernel and merely ride along); x/eps/noise/o: (R, 128) VMEM blocks of
    lane ``program_id(0)``."""
    lane = pl.program_id(0)
    act = meta_ref[lane, 1]

    @pl.when(act == 0)
    def _hold():                 # inactive lanes: the input bit-for-bit
        o_ref[...] = x_ref[...]

    @pl.when(act != 0)
    def _step():
        col = meta_ref[lane, 0]
        c_eps = tab_ref[0, col]
        ar = tab_ref[1, col]
        sigma = tab_ref[2, col]
        keep = tab_ref[3, col]
        x = x_ref[...].astype(jnp.float32)
        eps = eps_ref[...].astype(jnp.float32)
        z = noise_ref[...].astype(jnp.float32)
        # round c_eps·ε̂ before the subtraction, as the reference's separate
        # multiply does: a multiply feeding a subtract may otherwise be
        # contracted into one FMA (XLA:CPU does, where the interpreter
        # runs), an ulp that the divide by sqrt(ar) amplifies near
        # cancellation.  The select is an identity (NaN stays NaN).
        prod = c_eps * eps
        prod = jnp.where(prod == prod, prod, jnp.nan)
        sqrt_ar = jnp.sqrt(jnp.full(x.shape, ar, jnp.float32))
        new = (x - prod) / sqrt_ar + keep * sigma * z
        if clip:
            new = jnp.clip(new, -clip, clip)
        o_ref[...] = new.astype(o_ref.dtype)


def traj_masked_step(x, cols, eps_hat, noise, active, tables, *,
                     clip: float = 3.0, block: int = 4096):
    """Fused masked trajectory tick over a slot array.

    x/eps_hat/noise: (S, ...); cols: (S,) int32 per-lane table column (ANY
    value — clamped into [0, C) so idle lanes gather in-range entries);
    active: (S,) bool; tables: canonical (rows, C) coefficient table —
    (4, C) bare or (5, C) with the guidance row, which the update ignores
    (the ε̂-combine happens before this kernel, so guided and unguided
    lanes run the SAME program).  Per lane: where active, x <-
    clip(step(x, cols), ±clip); otherwise x passes through bit-unchanged.
    Where the column's keep flag is 0 (σ == 0 — e.g. the final trajectory
    step) the noise term is dropped, matching ``ddpm.p_sample``'s
    deterministic last step.  ``block`` is the pixels per grid step,
    rounded to whole (sublane, 128) tiles.
    """
    s = x.shape[0]
    n_rows, C = tables.shape
    meta = lane_meta(cols, active, C)
    rows, r = _tiling(x.size // s, x.dtype, block)
    tile = pl.BlockSpec((None, r, _LANE), lambda i, j: (i, j, 0))
    out = pallas_call(
        functools.partial(_masked_step_kernel, clip=float(clip)),
        grid=(s, rows // r),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((s, rows, _LANE), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=7 * s * rows * _LANE, transcendentals=0,
            bytes_accessed=masked_step_bytes(x, C, block=block,
                                             rows=n_rows)),
    )(meta, tables, _to_tiles(x, rows),
      _to_tiles(eps_hat, rows), _to_tiles(noise, rows))
    out = out.reshape(s, -1)
    if out.shape[1] != x.size // s:             # drop the tile padding
        out = out[:, :x.size // s]
    return out.reshape(x.shape)


def ddpm_masked_step(x, t, eps_hat, noise, active, tables, *,
                     clip: float = 3.0, block: int = 4096):
    """Timestep-indexed view of :func:`traj_masked_step` over the dense
    ancestral table (``masked_step_tables``): per-lane t in {1..T} (ANY
    value — clamped) maps to column T - t.  Kept as the serving-era API;
    the engine itself now steps trajectory columns directly.
    """
    T = tables.shape[1]
    cols = T - jnp.clip(t, 1, T)
    return traj_masked_step(x, cols, eps_hat, noise, active, tables,
                            clip=clip, block=block)
