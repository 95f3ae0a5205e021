"""jit'd public wrappers around the Pallas kernels.

Each kernel runs compiled (Mosaic) where the program is lowered for a TPU
and in the Pallas interpreter elsewhere (``repro.kernels.pallas_call``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ddpm_step as _ddpm
from repro.kernels import flash_attention as _fa
from repro.kernels import ssm_scan as _ssm


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv)


@functools.partial(jax.jit, static_argnames=("chunk", "head_block"))
def ssm_scan(x, dt, a, bm, cm, *, chunk: int = 128, head_block: int = 8):
    return _ssm.ssm_scan(x, dt, a, bm, cm, chunk=chunk,
                         head_block=head_block)


@jax.jit
def ddpm_step(sched, x_t, t, eps_hat, noise):
    """Fused denoise update; drop-in for diffusion.ddpm.p_sample.

    ``sched`` is a :class:`~repro.diffusion.schedule.DiffusionSchedule`
    (a registered pytree, so it traces like any other argument).
    """
    coefs = _ddpm.ddpm_step_coefs(sched, t)
    return _ddpm.ddpm_step(x_t, eps_hat, noise, coefs)


@functools.partial(jax.jit, static_argnames=("clip",))
def ddpm_masked_step(sched, x_t, t, eps_hat, noise, active, *,
                     clip: float = 3.0, tables=None):
    """Fused masked tick: SMEM schedule gather by per-lane t + update +
    clip + active-lane select in ONE pallas program (the serving engine's
    per-tick hot loop).  Pass ``tables=masked_step_tables(sched)`` to reuse
    a prebuilt coefficient table across ticks."""
    if tables is None:
        tables = _ddpm.masked_step_tables(sched)
    return _ddpm.ddpm_masked_step(x_t, t, eps_hat, noise, active, tables,
                                  clip=clip)


@functools.partial(jax.jit, static_argnames=("clip",))
def traj_masked_step(x, cols, eps_hat, noise, active, tables, *,
                     clip: float = 3.0):
    """Fused masked TRAJECTORY tick: per-lane column gather from a
    canonical (4, C) coefficient table (``sampler.Sampler.tables`` /
    ``masked_step_tables``) + update + clip + active select in ONE pallas
    program — strided DDIM and dense DDPM lanes share the kernel."""
    return _ddpm.traj_masked_step(x, cols, eps_hat, noise, active, tables,
                                  clip=clip)


@jax.jit
def ddpm_index_step(x, cols, eps_hat, noise, tables):
    """Fused trajectory step for every sample (no mask, no clip): the
    per-sample column's (c_eps, ar, σ, keep) gathered in SMEM."""
    ones = jnp.ones((x.shape[0],), bool)
    return _ddpm.traj_masked_step(x, cols, eps_hat, noise, ones, tables,
                                  clip=0.0)
