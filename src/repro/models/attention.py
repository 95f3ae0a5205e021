"""Attention: GQA + MLA, blockwise (flash-style) full/sliding-window, KV caches.

Design notes (see DESIGN.md §6):

* Train/prefill attention is **blockwise with python-level chunk loops** and an
  online-softmax accumulator.  Python loops (not ``lax.scan``) keep XLA's
  ``cost_analysis`` FLOP counts exact, bound peak memory to one
  ``(q_chunk × kv_chunk)`` score block, and let causal / sliding-window block
  skipping remove work at trace time.
* Decode attention is a single-query einsum over the cache (full) or over the
  ring-buffered window (sliding window).
* MLA (DeepSeek-V2) keeps the compressed ``c_kv`` as the decode cache and uses
  the weight-absorption trick so per-step cost is O(H·(r+rope)·T).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import (
    ShardCtx, apply_mrope, apply_rope, dense_init, shard, split_keys)

NEG_INF = -2.0 ** 30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------
def attention_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.attn_type == "mla":
        return mla_init(key, cfg, dtype)
    k1, k2, k3, k4 = split_keys(key, 4)
    return {
        "wq": dense_init(k1, (d, h, hd), d, dtype=dtype),
        "wk": dense_init(k2, (d, kv, hd), d, dtype=dtype),
        "wv": dense_init(k3, (d, kv, hd), d, dtype=dtype),
        "wo": dense_init(k4, (h, hd, d), h * hd, dtype=dtype),
    }


def mla_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h = cfg.d_model, cfg.n_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    nope, rope_d, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = split_keys(key, 7)
    p = {
        # kv compression: d -> r (content) and d -> rope_d (shared rope key)
        "w_dkv": dense_init(ks[0], (d, r), d, dtype=dtype),
        "w_krope": dense_init(ks[1], (d, rope_d), d, dtype=dtype),
        "w_uk": dense_init(ks[2], (r, h, nope), r, dtype=dtype),
        "w_uv": dense_init(ks[3], (r, h, vh), r, dtype=dtype),
        "wo": dense_init(ks[4], (h, vh, d), h * vh, dtype=dtype),
    }
    if qr:
        p["w_dq"] = dense_init(ks[5], (d, qr), d, dtype=dtype)
        p["w_uq"] = dense_init(ks[6], (qr, h, nope + rope_d), qr, dtype=dtype)
    else:
        p["wq"] = dense_init(ks[5], (d, h, nope + rope_d), d, dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention core
# ---------------------------------------------------------------------------
def _chunk_sizes(s_q: int, s_kv: int) -> tuple[int, int]:
    qc = min(s_q, 2048)
    kc = min(s_kv, 2048)
    return qc, kc


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_offset: int = 0, softmax_scale: Optional[float] = None):
    """q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd) with H % KV == 0.

    ``q_offset``: absolute position of q[0] relative to k[0] (prefill: 0 when
    Sq == Skv).  Returns (B,Sq,H,hd).
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    qc, kc = _chunk_sizes(sq, skv)
    n_q, n_kv = sq // qc, skv // kc
    assert n_q * qc == sq and n_kv * kc == skv, (sq, skv, qc, kc)

    qg = q.reshape(b, sq, kvh, g, hd)
    outs = []
    for iq in range(n_q):
        q_blk = qg[:, iq * qc:(iq + 1) * qc]                   # (B,qc,KV,G,hd)
        q_lo = q_offset + iq * qc
        q_hi = q_lo + qc - 1
        m = jnp.full((b, kvh, g, qc), NEG_INF, jnp.float32)
        l = jnp.zeros((b, kvh, g, qc), jnp.float32)
        acc = jnp.zeros((b, kvh, g, qc, hd), jnp.float32)
        for ik in range(n_kv):
            k_lo = ik * kc
            k_hi = k_lo + kc - 1
            if causal and k_lo > q_hi:
                continue                                        # fully masked
            if window and k_hi < q_lo - window + 1:
                continue                                        # outside window
            k_blk = k[:, k_lo:k_lo + kc]                        # (B,kc,KV,hd)
            v_blk = v[:, k_lo:k_lo + kc]
            s = jnp.einsum("bqkgd,btkd->bkgqt", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            need_mask = (causal and k_hi > q_lo) or (
                window and k_lo < q_hi - window + 1)
            if need_mask:
                qpos = q_lo + jnp.arange(qc)[:, None]
                kpos = k_lo + jnp.arange(kc)[None, :]
                ok = jnp.ones((qc, kc), bool)
                if causal:
                    ok &= kpos <= qpos
                if window:
                    ok &= kpos > qpos - window
                s = jnp.where(ok[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p_ = jnp.exp(s - m_new[..., None])
            l = l * alpha + p_.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p_.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            m = m_new
        out = acc / jnp.maximum(l[..., None], 1e-37)
        outs.append(jnp.transpose(out, (0, 3, 1, 2, 4)))        # (B,qc,KV,G,hd)
    o = jnp.concatenate(outs, axis=1).reshape(b, sq, h, hd)
    return o.astype(q.dtype)


def _blockwise_dyn(q, k, v, q_offset, *, causal: bool, window: int = 0,
                   softmax_scale: Optional[float] = None):
    """Online-softmax attention with a TRACED q_offset (for use inside
    shard_map where the offset is ``axis_index * sq_local``).  No static
    block skipping — every kv block is computed with a dynamic mask.
    q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd)."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    kc = min(skv, 2048)
    n_kv = skv // kc
    assert n_kv * kc == skv, (skv, kc)
    qg = q.reshape(b, sq, kvh, g, hd)
    qpos = q_offset + jnp.arange(sq)
    m = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kvh, g, sq), jnp.float32)
    acc = jnp.zeros((b, kvh, g, sq, hd), jnp.float32)
    for ik in range(n_kv):
        k_blk = k[:, ik * kc:(ik + 1) * kc]
        v_blk = v[:, ik * kc:(ik + 1) * kc]
        s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k_blk,
                       preferred_element_type=jnp.float32) * scale
        kpos = ik * kc + jnp.arange(kc)
        ok = jnp.ones((sq, kc), bool)
        if causal:
            ok &= kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p_ = jnp.exp(s - m_new[..., None])
        l = l * alpha + p_.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqt,btkd->bkgqd", p_.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        m = m_new
    out = acc / jnp.maximum(l[..., None], 1e-37)
    o = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, sq, h, hd)
    return o.astype(q.dtype)


def qshard_attention(q, k, v, ctx: ShardCtx, *, causal: bool = True,
                     window: int = 0):
    """Sequence-parallel attention: shard q's sequence dim over the model
    axis (k, v replicated), each device computing its own q stripe.

    This is the §Perf lever for architectures whose head count does not
    divide the model axis (qwen2-vl 12H, minicpm 36H): the baseline
    replicates the whole S×S attention on every model-axis device; this
    computes 1/model_size of it per device at the cost of losing static
    causal block skipping inside the stripe (dynamic masks instead).
    """
    axis = ctx.model_axis
    bs = ctx.resolve("batch")
    sq = q.shape[1]
    n = ctx.model_size
    assert sq % n == 0, (sq, n)

    def local(qs, ks, vs):
        idx = jax.lax.axis_index(axis)
        off = idx * (sq // n)
        return _blockwise_dyn(qs, ks, vs, off, causal=causal, window=window)

    # the body uses axis_index, which the replication checker can't type
    return jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(bs, axis), P(bs), P(bs)),
        out_specs=P(bs, axis), check_vma=False)(q, k, v)


def decode_attention(q, k_cache, v_cache, valid_len=None,
                     softmax_scale: Optional[float] = None):
    """Single-step attention.  q: (B,1,H,hd); caches: (B,T,KV,hd).

    ``valid_len``: optional scalar/array — cache positions >= valid_len are
    masked (None = whole cache valid, the steady-state dry-run case).
    """
    b, _, h, hd = q.shape
    t, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, kvh, g, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if valid_len is not None:
        mask = jnp.arange(t)[None, None, None, :] < valid_len
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------
def _positions_default(b, s, offset=0):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None] + offset, (b, s))


def gqa_forward(x, p, cfg: ModelConfig, ctx: ShardCtx, *,
                positions=None, window: int = 0, kernel: str = "jnp"):
    """Full (train/prefill) GQA self-attention.  x: (B,S,d)."""
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"], preferred_element_type=jnp.float32)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"], preferred_element_type=jnp.float32)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    q = shard(q.astype(x.dtype), ctx, "batch", None, "model", None)
    k = shard(k.astype(x.dtype), ctx, "batch", None, "model", None)
    v = shard(v, ctx, "batch", None, "model", None)
    if positions is None:
        positions = _positions_default(b, s)
    if cfg.mrope_sections:
        if positions.ndim == 2:                       # plain ids -> 3 equal streams
            positions = jnp.broadcast_to(positions[None], (3, b, s))
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    use_qshard = (ctx.seq_shard_attn and ctx.mesh is not None and
                  q.shape[2] % ctx.model_size != 0 and
                  s % ctx.model_size == 0)
    if use_qshard:
        # §Perf lever: heads don't divide the model axis — shard the q
        # sequence stripe instead of replicating the whole attention.
        q = shard(q, ctx, "batch", "model", None, None)
        o = qshard_attention(q, k, v, ctx, causal=True, window=window)
        o = shard(o, ctx, "batch", "model", None, None)
    elif kernel == "pallas":
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, causal=True, window=window)
    else:
        o = blockwise_attention(q, k, v, causal=True, window=window)
    o = shard(o, ctx, "batch", None, "model", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                     preferred_element_type=x.dtype)  # TP partial-sum
    # all-reduce in the activation dtype (bf16 on production configs):
    # halves the dominant f32[B,S,d] collective (EXPERIMENTS §Perf C.3)
    return out.astype(x.dtype)


def gqa_init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, cache_len, kv, hd), dtype),
        "v": jnp.zeros((batch, cache_len, kv, hd), dtype),
    }


def gqa_decode(x, p, cache, pos, cfg: ModelConfig, ctx: ShardCtx, *,
               window: int = 0):
    """One decode step.  x: (B,1,d); pos: scalar int32 absolute position.

    Full attention: cache length T == seq_len, written at index pos.
    Sliding window: cache length T == window (ring buffer), index pos % window.
    Returns (out, new_cache).
    """
    b = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[None, None], (b, 1))
    if cfg.mrope_sections:
        pos3 = jnp.broadcast_to(posb[None], (3, b, 1))
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, posb, cfg.rope_theta)
        k = apply_rope(k, posb, cfg.rope_theta)
    t = cache["k"].shape[1]
    slot = (pos % t) if window else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    valid = jnp.minimum(jnp.asarray(pos, jnp.int32) + 1, t)
    o = decode_attention(q, k_cache, v_cache, valid_len=valid)
    o = shard(o, ctx, "batch", None, "model", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                     preferred_element_type=x.dtype)  # TP partial-sum
    # all-reduce in the activation dtype (bf16 on production configs):
    # halves the dominant f32[B,S,d] collective (EXPERIMENTS §Perf C.3)
    return out.astype(x.dtype), {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA module (DeepSeek-V2)
# ---------------------------------------------------------------------------
def _mla_q(x, p, cfg):
    if "w_dq" in p:
        cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"],
                       preferred_element_type=jnp.float32)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"],
                       preferred_element_type=jnp.float32)
    return q.astype(x.dtype)          # (B,S,H, nope+rope)


def mla_forward(x, p, cfg: ModelConfig, ctx: ShardCtx, *,
                positions=None, window: int = 0, kernel: str = "jnp"):
    """Train/prefill MLA attention: expand compressed KV to per-head K/V."""
    b, s, d = x.shape
    nope, rope_d, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions is None:
        positions = _positions_default(b, s)
    q = _mla_q(x, p, cfg)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"],
                      preferred_element_type=jnp.float32).astype(x.dtype)
    k_rope = jnp.einsum("bsd,dk->bsk", x, p["w_krope"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = cfg.n_heads
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope_d))], axis=-1)
    q_full = shard(q_full, ctx, "batch", None, "model", None)
    k_full = shard(k_full, ctx, "batch", None, "model", None)
    v = shard(v, ctx, "batch", None, "model", None)
    scale = 1.0 / math.sqrt(nope + rope_d)
    # pad v's head dim up to qk dim so the blockwise core can share shapes
    o = blockwise_attention(q_full, k_full,
                            jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                                        (0, nope + rope_d - vh))),
                            causal=True, window=window, softmax_scale=scale)
    o = o[..., :vh]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                     preferred_element_type=x.dtype)  # TP partial-sum
    # all-reduce in the activation dtype (bf16 on production configs):
    # halves the dominant f32[B,S,d] collective (EXPERIMENTS §Perf C.3)
    return out.astype(x.dtype)


def mla_init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    return {
        "c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype),
    }


def mla_decode(x, p, cache, pos, cfg: ModelConfig, ctx: ShardCtx, *,
               window: int = 0):
    """Absorbed-weight MLA decode: score against compressed c_kv directly."""
    b = x.shape[0]
    nope, rope_d, vh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h, r = cfg.n_heads, cfg.kv_lora_rank
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[None, None], (b, 1))
    q = _mla_q(x, p, cfg)                                   # (B,1,H,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, posb, cfg.rope_theta)
    # absorb W_uk into the query:  q_c = q_nope @ W_uk  -> (B,1,H,r)
    q_c = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    c_kv_new = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"],
                          preferred_element_type=jnp.float32).astype(x.dtype)
    k_rope_new = jnp.einsum("bsd,dk->bsk", x, p["w_krope"],
                            preferred_element_type=jnp.float32).astype(x.dtype)
    k_rope_new = apply_rope(k_rope_new[:, :, None, :], posb,
                            cfg.rope_theta)[:, :, 0, :]
    t = cache["c_kv"].shape[1]
    slot = (pos % t) if window else pos
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv_new, slot, 1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope_new, slot, 1)
    scale = 1.0 / math.sqrt(nope + rope_d)
    s = (jnp.einsum("bshr,btr->bhst", q_c, c_kv, preferred_element_type=jnp.float32)
         + jnp.einsum("bshk,btk->bhst", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * scale
    valid = jnp.minimum(jnp.asarray(pos, jnp.int32) + 1, t)
    s = jnp.where(jnp.arange(t)[None, None, None, :] < valid, s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    # attend in compressed space then up-project through W_uv
    o_c = jnp.einsum("bhst,btr->bshr", pr.astype(x.dtype), c_kv,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    o = jnp.einsum("bshr,rhk->bshk", o_c, p["w_uv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                     preferred_element_type=x.dtype)  # TP partial-sum
    # all-reduce in the activation dtype (bf16 on production configs):
    # halves the dominant f32[B,S,d] collective (EXPERIMENTS §Perf C.3)
    return out.astype(x.dtype), {"c_kv": c_kv, "k_rope": k_rope}


# ---------------------------------------------------------------------------
# Cross-attention (musicgen conditioning)
# ---------------------------------------------------------------------------
def cross_attention_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    k1, k2, k3, k4 = split_keys(key, 4)
    return {
        "wq": dense_init(k1, (d, h, hd), d, dtype=dtype),
        "wk": dense_init(k2, (d, h, hd), d, dtype=dtype),
        "wv": dense_init(k3, (d, h, hd), d, dtype=dtype),
        "wo": dense_init(k4, (h, hd, d), h * hd, dtype=dtype),
    }


def cross_attention(x, cond, p, cfg: ModelConfig, ctx: ShardCtx):
    """x: (B,S,d) queries; cond: (B,C,d) keys/values (no rope, no mask)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("bcd,dhk->bchk", cond, p["wk"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bcd,dhk->bchk", cond, p["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = jnp.einsum("bshk,bchk->bhsc", q, k,
                   preferred_element_type=jnp.float32) * scale
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhsc,bchk->bshk", pr.astype(x.dtype), v,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                     preferred_element_type=x.dtype)  # TP partial-sum
    # all-reduce in the activation dtype (bf16 on production configs):
    # halves the dominant f32[B,S,d] collective (EXPERIMENTS §Perf C.3)
    return out.astype(x.dtype)
