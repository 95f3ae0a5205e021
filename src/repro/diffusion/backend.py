"""StepBackend: who executes one denoise tick.

Every hot loop in this repo — ``ddpm.sample_range``, the CollaFuse split
samplers, and the serving engine's masked per-slot tick — bottoms out in the
same primitive: one reverse-diffusion update x_t -> x_{t-1}, the reference
sampler's post-step clip, and (on slot arrays) the active-lane select.  A
:class:`StepBackend` owns all three, so callers thread ONE object (or its
registry name) instead of copy-pasting kernel-selection booleans through
every layer, and every future step variant (DDIM, guidance, quantized
iterates) plugs in as a new registered backend.

Registered backends:

``"jnp"``            pure-jnp reference: ``ddpm.p_sample`` + clip (+ where).
``"pallas"``         Pallas fused update kernel (``kernels/ddpm_step.py``),
                     clip and active-select still in jnp.
``"pallas_masked"``  ONE fused Pallas program for the whole masked tick:
                     per-lane schedule gather from SMEM by (clamped) t,
                     update, clip, and active-lane select in a single read
                     of (x, eps_hat, noise) + one write.

All backends agree numerically on active lanes (the Pallas kernels compute
the identical f32 expression), and
``masked_step`` with ``active=ones`` is bitwise ``step`` for every backend.
Inactive lanes always pass through bit-unchanged, even at out-of-range t.

Two step contracts per backend:

* timestep-indexed (``step`` / ``masked_step``): the dense DDPM chain,
  per-sample t in {1..T} — the original seam.
* trajectory-indexed (``index_step`` / ``masked_index_step``): per-sample
  COLUMNS into a canonical (5, C) coefficient table (c_eps, ar, sigma,
  keep, guidance w) built by ``repro.diffusion.sampler`` — one column per
  trajectory position, so strided DDIM and dense DDPM ticks are the same
  program.  ``guided_masked_index_step`` puts the classifier-free
  ε̂-combine over cond+uncond lane pairs in front of the same fused step,
  so guided traffic is STILL that one program.  The dense ancestral table
  makes ``index_step`` bitwise ``step`` on the jnp backend.

The Pallas backends run compiled Mosaic where the program is lowered for a
TPU and the Pallas interpreter elsewhere (``repro.kernels.pallas_call``).
``get_backend(None)`` picks the platform's path: ``"pallas_masked"`` on a
TPU, ``"jnp"`` elsewhere.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# Row index of the guidance-scale row in the canonical coefficient table
# (rows 0-3 = c_eps, ar, sigma, keep drive the update; row 4 = the
# classifier-free guidance scale w of the column's sampler).  Defined here
# — the root of the diffusion import graph — and re-exported by
# ``repro.diffusion.sampler``, which builds the tables.
GUIDANCE_ROW = 4
N_TABLE_ROWS = 5


class StepBackend:
    """Owns the denoise update, the post-step clip, and the active select.

    ``step(sched, x, t, eps_hat, noise, clip=...)`` advances every sample;
    ``masked_step(..., active, tables=...)`` advances a slot array with
    heterogeneous per-lane timesteps: lanes where ``active`` step (t is
    clamped into {1..T} first so retired/empty lanes index in-range schedule
    entries), inactive lanes pass through bit-unchanged.  ``tables`` lets a
    caller with a long-lived schedule (the serving engine) hoist the
    per-step coefficient-table build out of the tick; backends that do not
    consume tables ignore it.
    """

    name: str = "abstract"

    def step(self, sched, x, t, eps_hat, noise, *, clip: float = 3.0):
        raise NotImplementedError

    def masked_step(self, sched, x, t, eps_hat, noise, active, *,
                    clip: float = 3.0, tables=None):
        del tables                       # only the fused backend stages them
        t_safe = jnp.clip(t, 1, sched.T)
        x_new = self.step(sched, x, t_safe, eps_hat, noise, clip=clip)
        m = active.reshape(active.shape + (1,) * (x.ndim - active.ndim))
        return jnp.where(m, x_new, x)

    # -- trajectory-indexed steps (repro.diffusion.sampler) ---------------
    # ``tables`` is a canonical (4, C) coefficient table (c_eps, ar, sigma,
    # keep) — one column per trajectory position (possibly several
    # trajectories concatenated; the serving engine does this).  ``cols``
    # is the per-sample column.  The base implementation is the pure-jnp
    # reference: for dense ancestral tables it reproduces ``ddpm.p_sample``
    # + clip bit-for-bit (same gathered values, same expression tree).
    def index_step(self, x, cols, eps_hat, noise, tables, *,
                   clip: float = 3.0):
        def row(r):
            v = tables[r, cols]
            return v.reshape(v.shape + (1,) * (x.ndim - v.ndim))
        mean = (x - row(0) * eps_hat) / jnp.sqrt(row(1))
        x_new = mean + row(3) * row(2) * noise
        if clip:
            x_new = jnp.clip(x_new, -clip, clip)
        return x_new

    def masked_index_step(self, x, cols, eps_hat, noise, active, tables, *,
                          clip: float = 3.0):
        """Masked trajectory tick: active lanes execute their column's
        step, inactive lanes pass through bit-unchanged (cols clamped
        in-range first, so retired/empty lanes may carry junk)."""
        cols_safe = jnp.clip(cols, 0, tables.shape[1] - 1)
        x_new = self.index_step(x, cols_safe, eps_hat, noise, tables,
                                clip=clip)
        m = active.reshape(active.shape + (1,) * (x.ndim - active.ndim))
        return jnp.where(m, x_new, x)

    def guided_masked_index_step(self, x, cols, eps_hat, noise, active,
                                 pair, cond, tables, *, clip: float = 3.0):
        """Masked trajectory tick with the classifier-free-guidance
        ε̂-combine in front of it.

        Guided requests occupy a LANE PAIR: a primary lane (``cond`` True,
        model saw the request label) and a shadow lane (``cond`` False,
        model saw the null label); ``pair`` holds each lane's partner
        index (its own index for unguided lanes).  Per lane the combine is
        ``ε̂ = ε̂_u + w·(ε̂_c − ε̂_u)`` with w gathered from the table's
        :data:`GUIDANCE_ROW` by the lane's column, and the shadow lane
        borrows the primary's noise draw — both members of a pair step to
        bit-identical x, so retire/ownership logic can read either.

        The combine happens BEFORE :meth:`masked_index_step`, so mixed
        guided/unguided traffic still bottoms out in ONE fused step
        program.  Unpaired lanes (``pair == lane``) and w == 0 columns
        take their raw / unconditional ε̂ through a select, making the
        w=0 guided path and every unguided lane bitwise identical to the
        plain :meth:`masked_index_step` tick.
        """
        if tables.shape[0] <= GUIDANCE_ROW:      # bare 4-row table: no
            return self.masked_index_step(       # guidance data to gather
                x, cols, eps_hat, noise, active, tables, clip=clip)
        nb = (1,) * (x.ndim - 1)
        cols_safe = jnp.clip(cols, 0, tables.shape[1] - 1)
        w = tables[GUIDANCE_ROW, cols_safe].reshape((-1,) + nb)
        c = cond.reshape((-1,) + nb)
        eps_p = eps_hat[pair]
        eps_c = jnp.where(c, eps_hat, eps_p)
        eps_u = jnp.where(c, eps_p, eps_hat)
        solo = (pair == jnp.arange(x.shape[0])).reshape((-1,) + nb)
        eps = jnp.where(solo | (w == 0.0), eps_u,
                        eps_u + w * (eps_c - eps_u))
        z = jnp.where(c, noise, noise[pair])
        return self.masked_index_step(x, cols, eps, z, active, tables,
                                      clip=clip)


class LaneSharded(StepBackend):
    """``inner``'s masked trajectory tick under ``shard_map`` over the lane
    axis of a mesh: each device steps its own block of lanes.  XLA cannot
    partition a Mosaic kernel, so a slot array sharded over a mesh reaches
    the fused tick this way; lanes are independent, so no backend loses
    anything by it.  Only :meth:`masked_index_step` (and the guided tick
    built on it) is sharded — what the serving engine's scan window runs.
    """

    def __init__(self, inner: StepBackend, mesh, lane_axes):
        self.inner = inner
        self.name = inner.name
        self.mesh = mesh
        self.lane = P(lane_axes)

    def masked_index_step(self, x, cols, eps_hat, noise, active, tables, *,
                          clip: float = 3.0):
        lane = self.lane
        return jax.shard_map(
            functools.partial(self.inner.masked_index_step, clip=clip),
            mesh=self.mesh, in_specs=(lane,) * 5 + (P(),), out_specs=lane,
            check_vma=False)(x, cols, eps_hat, noise, active, tables)


def make_lane_tick(apply_fn: Callable, masked_index: Callable, kmax: int,
                   image_shape, conditional: bool = False) -> Callable:
    """Build the SCAN-COMPATIBLE masked lane tick every hot loop shares.

    One tick of a slot array walking heterogeneous trajectories:

        x, pos, key, done = lane_tick(params, menu, x, pos, key, end,
                                      traj, gate, y, pair, cond)

    ``menu`` is the trajectory-menu state, a dict of ARRAYS traced at call
    time (not closed over as constants): ``tables`` — the (5, C)
    concatenated coefficient table gathered per-lane by column (rows 0-3
    the step coefficients, row ``GUIDANCE_ROW`` the column's guidance
    scale) — ``offsets`` — each trajectory's first column — and
    ``ts_pad`` — the (n_menu, kmax) padded timestep rows the model
    conditions on.  Passing the menu as data is what makes DYNAMIC
    sampler registration retrace-free: the serving engine preallocates
    spare columns/rows (``EngineConfig.spare_columns``), writes an ad-hoc
    trajectory's coefficients into them with one device scatter, and
    every jitted program built on this tick keeps its cache (shapes never
    change — asserted via jit cache sizes in ``benchmarks.run --only
    hetero_packing``).

    ``gate`` is the caller's liveness mask (engine: the slot's ``active``
    flag; finisher: the padding-lane ``valid`` flag).  A lane steps only
    while ``gate & (pos < end)``; once ``pos`` reaches ``end`` the lane
    HOLDS ``x``, ``pos`` and ``key`` bitwise (the masked-select / Pallas
    passthrough), which is exactly the done-latching ``lax.scan`` needs:
    the carry is a fixed point after the lane finishes, so running k ticks
    per dispatch and retiring at the scan boundary reads the same ``x`` the
    lane had at its cut — bit-for-bit, at any k.

    ``y``/``pair``/``cond`` are the conditional-serving lane state: the
    per-lane class label fed to a ``conditional`` model (the null label
    for unguided and shadow lanes), the partner-lane index of a guided
    cond+uncond pair (own index when unguided), and the primary-lane
    flag.  One model dispatch covers both members of every pair — the
    ε̂-combine and the shadow lane's noise borrow happen in
    ``masked_index`` (the StepBackend's ``guided_masked_index_step``
    partial, minus ``tables``) so the step itself stays one fused
    program.  With every lane unpaired the tick is bitwise the old
    unguided tick.

    The function is pure in (carry, params, menu), so it traces once
    whether the caller wraps it in ``lax.scan`` (the engine's k-tick
    window), ``lax.fori_loop`` (the client finisher) or calls it
    directly.  ``conditional`` engines call ``apply_fn(params, x, t, y)``;
    unconditional ones keep the classic 3-arg convention.

    The model call, the noise draw and the step run under the named scopes
    ``unet``, ``noise`` and ``step``: every program built on the tick
    carries them in its ops' ``op_name`` metadata, which a profile reads
    to split device time by layer.  Scopes are metadata only; outputs are
    unchanged.
    """
    def lane_tick(params, menu, x, pos, key, end, traj, gate, y, pair,
                  cond):
        stepping = gate & (pos < end)
        pos_c = jnp.clip(pos, 0, kmax - 1)
        t_lane = menu["ts_pad"][traj, pos_c]  # model conditions on t
        with jax.named_scope("unet"):
            if conditional:
                eps_hat = apply_fn(params, x, t_lane, y)
            else:
                eps_hat = apply_fn(params, x, t_lane)
        with jax.named_scope("noise"):
            ks = jax.vmap(jax.random.split)(key)
            k_next, k_n = ks[:, 0], ks[:, 1]
            noise = jax.vmap(
                lambda k: jax.random.normal(k, image_shape, jnp.float32))(k_n)
        cols = menu["offsets"][traj] + pos_c
        with jax.named_scope("step"):
            x = masked_index(x, cols, eps_hat, noise, stepping, pair, cond,
                             tables=menu["tables"])
        pos = jnp.where(stepping, pos + 1, pos)
        key = jnp.where(stepping[:, None], k_next, key)
        done = stepping & (pos >= end)        # x now holds the cut tensor
        return x, pos, key, done
    return lane_tick


_REGISTRY: Dict[str, StepBackend] = {}

BackendLike = Optional[Union[str, StepBackend]]


def register(cls):
    """Class decorator: instantiate and expose under ``cls.name``."""
    _REGISTRY[cls.name] = cls()
    return cls


def get_backend(spec: BackendLike = None) -> StepBackend:
    """Resolve a backend name (or pass an instance through).  None = the
    default platform's step path: the compiled fused tick
    (``"pallas_masked"``) on a TPU, the jnp reference elsewhere."""
    if spec is None:
        spec = "pallas_masked" if jax.default_backend() == "tpu" else "jnp"
    if isinstance(spec, StepBackend):
        return spec
    try:
        return _REGISTRY[spec]
    except KeyError:
        raise ValueError(f"unknown step backend {spec!r}; "
                         f"available: {available()}") from None


def available():
    return sorted(_REGISTRY)


@register
class JnpStepBackend(StepBackend):
    """Pure-jnp reference path (XLA decides all fusion)."""

    name = "jnp"

    def step(self, sched, x, t, eps_hat, noise, *, clip: float = 3.0):
        from repro.diffusion import ddpm               # import cycle: lazy
        x = ddpm.p_sample(sched, x, t, eps_hat, noise)
        if clip:
            x = jnp.clip(x, -clip, clip)
        return x


@register
class PallasStepBackend(StepBackend):
    """Pallas fused update; clip + masked select stay in jnp."""

    name = "pallas"

    def step(self, sched, x, t, eps_hat, noise, *, clip: float = 3.0):
        from repro.kernels import ops as kops
        x = kops.ddpm_step(sched, x, t, eps_hat, noise)
        if clip:
            x = jnp.clip(x, -clip, clip)
        return x

    def index_step(self, x, cols, eps_hat, noise, tables, *,
                   clip: float = 3.0):
        from repro.kernels import ops as kops
        x = kops.ddpm_index_step(x, cols, eps_hat, noise, tables)
        if clip:
            x = jnp.clip(x, -clip, clip)
        return x


@register
class PallasMaskedStepBackend(StepBackend):
    """ONE fused Pallas program per tick: SMEM schedule gather by per-lane
    t, update, clip, and active select in a single read+write of the slot
    array (collapsing the jnp chain's ~4+ HBM round-trips — gated ≥2x fewer
    bytes in ``benchmarks.run --only masked_step``)."""

    name = "pallas_masked"

    def step(self, sched, x, t, eps_hat, noise, *, clip: float = 3.0):
        ones = jnp.ones((x.shape[0],), bool)
        return self.masked_step(sched, x, t, eps_hat, noise, ones, clip=clip)

    def masked_step(self, sched, x, t, eps_hat, noise, active, *,
                    clip: float = 3.0, tables=None):
        from repro.kernels import ops as kops
        return kops.ddpm_masked_step(sched, x, t, eps_hat, noise, active,
                                     clip=clip, tables=tables)

    def index_step(self, x, cols, eps_hat, noise, tables, *,
                   clip: float = 3.0):
        ones = jnp.ones((x.shape[0],), bool)
        return self.masked_index_step(x, cols, eps_hat, noise, ones, tables,
                                      clip=clip)

    def masked_index_step(self, x, cols, eps_hat, noise, active, tables, *,
                          clip: float = 3.0):
        from repro.kernels import ops as kops
        return kops.traj_masked_step(x, cols, eps_hat, noise, active, tables,
                                     clip=clip)
