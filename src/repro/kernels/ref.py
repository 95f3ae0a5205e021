"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

These are the *mathematical definitions* — naive materialized attention,
step-by-step SSM recurrence, direct p_sample formula — deliberately written
without the tiling/streaming structure of the kernels.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  softmax_scale=None):
    """Materialized softmax attention with GQA.  Shapes as flash_attention."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, sq, kvh, g, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    s = jnp.where(ok[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def ssm_scan_ref(x, dt, a, bm, cm):
    """Stepwise SSM recurrence (the SSD definition, O(S) sequential):

        h_t = exp(dt_t · a) · h_{t-1} + dt_t · x_t ⊗ b_t
        y_t = c_t · h_t
    """
    b, s, nh, p = x.shape
    n = bm.shape[-1]

    def step(state, inp):
        xt, dtt, bt, ct = inp                       # (b,nh,p),(b,nh),(b,n),(b,n)
        decay = jnp.exp(dtt * a[None, :])           # (b, nh)
        upd = jnp.einsum("bn,bhp->bhnp", bt, xt * dtt[..., None])
        state = state * decay[:, :, None, None] + upd
        y = jnp.einsum("bn,bhnp->bhp", ct, state)
        return state, y

    state0 = jnp.zeros((b, nh, n, p), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(bm, 1, 0).astype(jnp.float32),
          jnp.moveaxis(cm, 1, 0).astype(jnp.float32))
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)    # (B, S, nh, P)


def ddpm_step_ref(x_t, eps_hat, noise, coefs):
    """Direct p_sample with precomputed per-sample canonical coefs (B, 4)
    = (c_eps, ar, sigma, keep)."""
    b = x_t.shape[0]
    shape = (b,) + (1,) * (x_t.ndim - 1)
    c_eps = coefs[:, 0].reshape(shape)
    ar = coefs[:, 1].reshape(shape)
    sigma = coefs[:, 2].reshape(shape)
    keep = coefs[:, 3].reshape(shape)
    x = x_t.astype(jnp.float32)
    mean = (x - c_eps * eps_hat.astype(jnp.float32)) / jnp.sqrt(ar)
    return (mean + keep * sigma * noise.astype(jnp.float32)).astype(x_t.dtype)
