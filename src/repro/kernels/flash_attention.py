"""Pallas TPU flash attention (causal / sliding-window, GQA).

TPU-native design (DESIGN.md §3.3):
* Grid ``(batch·kv_head, q_blocks, kv_blocks)`` — the KV axis is the
  innermost (sequential) grid dimension, so K/V stream through VMEM one
  ``(block_kv, hd)`` tile at a time; online-softmax state (m, l, acc) lives in
  VMEM **scratch** that persists across the kv grid steps of a fixed
  (batch, q-block) program.
* Block shapes are MXU-aligned (128 on the contraction/lane dims).  VMEM
  working set ≈ Q tile (bq·G·hd) + K,V tiles (2·bk·hd) + acc (bq·G·hd f32)
  ≈ 128·8·128·(2+4) B ≈ 0.8 MB at G=8 — far inside the ~16 MB budget, for ANY
  sequence length (32k prefill included).
* Causal / sliding-window handled per-block: out-of-range KV blocks are
  skipped with ``pl.when`` (no compute issued), partially-masked blocks apply
  an iota mask.

Validated on CPU in the Pallas interpreter against ``kernels/ref.py``; the
same kernel lowers to Mosaic on TPU (``repro.kernels.pallas_call``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call

NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, causal, window, block_q, block_kv, n_kv):
    """Program for one (batch·kv-head, q-block, kv-block) grid point.

    q_ref: (block_q, G, hd); k_ref/v_ref: (block_kv, hd);
    scratch: m/l (block_q·G,), acc (block_q·G, hd) — persist across kv steps.
    """
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    q_lo = iq * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_kv
    k_hi = k_lo + block_kv - 1

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level visibility (static per (iq, ik) only when not traced; both
    # are traced program ids -> dynamic predicate)
    visible = jnp.asarray(True)
    if causal:
        visible &= k_lo <= q_hi
    if window:
        visible &= k_hi >= q_lo - window + 1

    @pl.when(visible)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale          # (bq, G, hd)
        bq, g, hd = q.shape
        q2 = q.reshape(bq * g, hd)
        k_blk = k_ref[...].astype(jnp.float32)              # (bk, hd)
        v_blk = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q2, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (bq, g, block_kv), 0).reshape(bq * g, block_kv)
        kpos = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (bq * g, block_kv), 1)
        ok = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            ok &= kpos <= qpos
        if window:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_prev * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        bq, g, hd = q_ref.shape
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)[:, None]
        o_ref[...] = out.reshape(bq, g, hd).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    softmax_scale=None):
    """q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd).  Returns (B, Sq, H, hd).
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0
    n_kv = skv // block_kv

    # fold (B, KV-head) into the leading grid axis
    qg = q.reshape(b, sq, kvh, g, hd).transpose(0, 2, 1, 3, 4) \
          .reshape(b * kvh, sq, g, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)

    grid = (b * kvh, sq // block_q, n_kv)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, n_kv=n_kv)
    out = pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, g, hd),
                         lambda ib, iq, ik: (ib, iq, 0, 0)),
            pl.BlockSpec((None, block_kv, hd),
                         lambda ib, iq, ik: (ib, ik, 0)),
            pl.BlockSpec((None, block_kv, hd),
                         lambda ib, iq, ik: (ib, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, g, hd),
                               lambda ib, iq, ik: (ib, iq, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, sq, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q * g,), jnp.float32),
            pltpu.VMEM((block_q * g,), jnp.float32),
            pltpu.VMEM((block_q * g, hd), jnp.float32),
        ],
    )(qg, kg, vg)
    return out.reshape(b, kvh, sq, g, hd).transpose(0, 2, 1, 3, 4) \
              .reshape(b, sq, h, hd)
