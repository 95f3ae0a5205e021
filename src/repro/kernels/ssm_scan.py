"""Pallas TPU Mamba2 (SSD) chunked scan kernel.

Grid ``(batch, head_blocks, chunks)`` — chunks innermost/sequential; the
per-(batch, head-block) SSM state ``(h_blk, N, P)`` lives in VMEM scratch and
carries across chunk steps, exactly the recurrent structure the paper-family
SSD algorithm prescribes, but tiled for the MXU:

* intra-chunk: the (L × L) decay-weighted score matrix is a dense matmul pair
  (C·Bᵀ then ·X) — MXU work with L = 128 tiles;
* inter-chunk: state read + rank-N update, again matmuls.

VMEM working set at L=128, h_blk=8, N=64, P=64:
x tile 128·8·64·4 B = 256 KB, decay tensor 128·128·8·4 B = 512 KB,
state 8·64·64·4 B = 128 KB — comfortably inside 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    """One (batch, head-block, chunk) program.

    x_ref: (L, hb, P); dt_ref: (L, hb); a_ref: (hb,);
    b_ref/c_ref: (L, N); y_ref: (L, hb, P); state scratch: (hb, N, P) f32.
    """
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)                   # (L, hb, P)
    dt = dt_ref[...].astype(jnp.float32)                 # (L, hb)
    a = a_ref[...].astype(jnp.float32)                   # (hb,)
    bm = b_ref[...].astype(jnp.float32)                  # (L, N)
    cm = c_ref[...].astype(jnp.float32)                  # (L, N)
    l = x.shape[0]

    dta = dt * a[None, :]                                # (L, hb)
    cum = jnp.cumsum(dta, axis=0)                        # inclusive
    # intra-chunk decay matrix  M[t, s, h] = exp(cum_t - cum_s) · 1[s <= t]
    seg = cum[:, None, :] - cum[None, :, :]              # (L, L, hb)
    tri = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    m = jnp.where(tri[:, :, None], jnp.exp(seg), 0.0)
    g = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, L)
    w = g[:, :, None] * m * dt[None, :, :]               # (t, s, hb)
    y = jnp.einsum("tsh,shp->thp", w, x)                 # (L, hb, P)
    # inter-chunk contribution from the carried state
    state = state_ref[...]                               # (hb, N, P)
    y = y + jnp.einsum("tn,hnp->thp", cm, state) * \
        jnp.exp(cum)[:, :, None]
    y_ref[...] = y.astype(y_ref.dtype)
    # state update to the end of this chunk
    decay_end = jnp.exp(cum[l - 1:l, :] - cum)           # (L, hb)
    upd = jnp.einsum("sn,shp->hnp", bm, x * (dt * decay_end)[:, :, None])
    state_ref[...] = state * jnp.exp(cum[l - 1])[:, None, None] + upd


def ssm_scan(x, dt, a, bm, cm, *, chunk: int = 128, head_block: int = 8):
    """Chunked SSD scan.

    x: (B, S, nh, P) head inputs; dt: (B, S, nh) softplus'd step sizes;
    a: (nh,) negative decay rates; bm, cm: (B, S, N) input/output projections
    (n_groups=1).  Returns y: (B, S, nh, P) — state-space mixing only (gating,
    D-skip, normalization stay in the caller).
    """
    b, s, nh, p = x.shape
    n = bm.shape[-1]
    chunk = min(chunk, s)
    head_block = min(head_block, nh)
    assert s % chunk == 0 and nh % head_block == 0
    grid = (b, nh // head_block, s // chunk)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, head_block, p),
                         lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((None, chunk, head_block),
                         lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec((head_block,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((None, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((None, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
        ],
        out_specs=pl.BlockSpec((None, chunk, head_block, p),
                               lambda ib, ih, ic: (ib, ic, ih, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, nh, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((head_block, n, p), jnp.float32)],
    )(x, dt, a, bm, cm)
