"""Mixture-of-Experts layer: top-k router + expert-parallel dispatch.

Two execution paths, selected by ``ShardCtx``:

* **EP path** (mesh present): GShard-style capacity dispatch under
  ``jax.shard_map``.  Experts are sharded over the ``model`` axis; tokens enter
  sharded over ``(batch_axes..., model)`` and are exchanged with two
  ``all_to_all`` collectives (dispatch + return).  This makes the collective
  schedule explicit in HLO — the roofline parser reads it — instead of relying
  on SPMD propagation of a one-hot einsum (which would inflate FLOPs by
  ~E/top_k).
* **Decode EP path**: when the per-shard token count is smaller than the
  expert-parallel degree (decode steps), tokens stay replicated over the model
  axis, every shard computes only its local experts' contribution, and a
  single ``psum`` over the model axis combines — the standard small-batch EP
  schedule.
* **Dense fallback** (no mesh): same capacity dispatch math on one device —
  used by smoke tests and the CollaFuse CPU demo.

Router aux (load-balance) loss follows Switch Transformer: ``E * Σ_e f_e·p_e``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import ShardCtx, dense_init, split_keys


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), d, dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), d, dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, f), d, dtype=dtype),
        "w_down": dense_init(ks[3], (e, f, d), f, dtype=dtype),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k1, k2, k3 = split_keys(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, (d, fs), d, dtype=dtype),
            "w_up": dense_init(k2, (d, fs), d, dtype=dtype),
            "w_down": dense_init(k3, (fs, d), fs, dtype=dtype),
        }
    return p


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------
def router_topk(x_flat, w_router, top_k: int):
    """x_flat: (N, d) -> (probs (N,k), idx (N,k) int32, aux_loss scalar)."""
    logits = jnp.einsum("nd,de->ne", x_flat.astype(jnp.float32), w_router)
    probs = jax.nn.softmax(logits, axis=-1)                   # (N, E)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    e = logits.shape[-1]
    # Switch aux loss: fraction of tokens routed to e × mean router prob of e
    assign = jnp.zeros((x_flat.shape[0], e), jnp.float32)
    assign = assign.at[jnp.arange(x_flat.shape[0])[:, None], top_i].add(1.0)
    f_e = assign.mean(axis=0) / top_k
    p_e = probs.mean(axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return top_p, top_i.astype(jnp.int32), aux


def _capacity(n_tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    return max(1, int(math.ceil(n_tokens * top_k * cf / n_experts)))


def _dispatch_indices(top_i, n_experts: int, capacity: int):
    """Compute per-assignment slot positions with capacity dropping.

    top_i: (N, k).  Returns (pos (N,k) int32 in [0,capacity], keep (N,k) bool).
    Position is the running count of earlier assignments to the same expert
    (row-major over (token, k) — the Switch/t5x convention).
    """
    n, k = top_i.shape
    flat = top_i.reshape(-1)                                   # (N*k,)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # (N*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot             # exclusive
    pos = jnp.take_along_axis(pos_in_e, flat[:, None], axis=1)[:, 0]
    keep = pos < capacity
    return pos.reshape(n, k).astype(jnp.int32), keep.reshape(n, k)


def _expert_ffn(xs, w_gate, w_up, w_down):
    """xs: (E_local, C, d); weights (E_local, d, f) / (E_local, f, d)."""
    h = jnp.einsum("ecd,edf->ecf", xs, w_gate, preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", xs, w_up, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(h) * u).astype(xs.dtype)
    return jnp.einsum("ecf,efd->ecd", h, w_down,
                      preferred_element_type=jnp.float32).astype(xs.dtype)


def _scatter_dispatch(x_flat, top_i, top_p, pos, keep, n_experts, capacity):
    """Build (E, C, d) buffer; returns buffer + combine metadata."""
    n, k = top_i.shape
    buf = jnp.zeros((n_experts, capacity, x_flat.shape[-1]), x_flat.dtype)
    tok_idx = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
    e_flat = jnp.where(keep, top_i, 0).reshape(-1)
    p_flat = jnp.where(keep, pos, 0).reshape(-1)
    w_flat = jnp.where(keep, 1.0, 0.0).reshape(-1).astype(x_flat.dtype)
    buf = buf.at[e_flat, p_flat].add(
        x_flat[tok_idx.reshape(-1)] * w_flat[:, None])
    return buf


def _gather_combine(buf, top_i, top_p, pos, keep):
    """buf: (E, C, d) expert outputs -> (N, d) weighted combine."""
    n, k = top_i.shape
    e_flat = jnp.where(keep, top_i, 0).reshape(-1)
    p_flat = jnp.where(keep, pos, 0).reshape(-1)
    out = buf[e_flat, p_flat].reshape(n, k, -1)                # (N,k,d)
    w = (top_p * keep).astype(buf.dtype)                       # dropped -> 0
    return jnp.einsum("nkd,nk->nd", out, w, preferred_element_type=jnp.float32
                      ).astype(buf.dtype)


# ---------------------------------------------------------------------------
# Single-device / per-shard core
# ---------------------------------------------------------------------------
def _moe_local(x_flat, p, cfg: ModelConfig, capacity: int):
    top_p, top_i, aux = router_topk(x_flat, p["router"], cfg.top_k)
    pos, keep = _dispatch_indices(top_i, cfg.n_experts, capacity)
    buf = _scatter_dispatch(x_flat, top_i, top_p, pos, keep,
                            cfg.n_experts, capacity)
    buf = _expert_ffn(buf, p["w_gate"], p["w_up"], p["w_down"])
    out = _gather_combine(buf, top_i, top_p, pos, keep)
    return out, aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path
# ---------------------------------------------------------------------------
def _moe_ep_body(x_local, router_w, w_gate, w_up, w_down, *,
                 cfg: ModelConfig, ep: int, model_axis: str):
    """Runs per (data×model) shard.  x_local: (N_local, d); expert weights are
    the LOCAL slices (E_local, ...)."""
    n_local, d = x_local.shape
    e = cfg.n_experts
    e_local = e // ep
    cap = _capacity(n_local, cfg.top_k, e, cfg.capacity_factor)
    top_p, top_i, aux = router_topk(x_local, router_w, cfg.top_k)
    pos, keep = _dispatch_indices(top_i, e, cap)
    buf = _scatter_dispatch(x_local, top_i, top_p, pos, keep, e, cap)
    # (E, C, d) -> (ep, E_local, C, d) -> exchange so shard m holds its experts'
    # tokens from every source shard: result dim0 indexes the source shard.
    buf = buf.reshape(ep, e_local, cap, d)
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=0)
    xs = buf.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, d)
    ys = _expert_ffn(xs, w_gate, w_up, w_down)
    ys = ys.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3)
    ys = jax.lax.all_to_all(ys, model_axis, split_axis=0, concat_axis=0)
    out = _gather_combine(ys.reshape(e, cap, d), top_i, top_p, pos, keep)
    aux = jax.lax.pmean(aux, model_axis)
    return out, aux


def _moe_ep_replicated_body(x_rep, router_w, w_gate, w_up, w_down, *,
                            cfg: ModelConfig, ep: int, model_axis: str,
                            shard_idx):
    """Decode path: tokens replicated over model axis; each shard computes its
    local experts' contribution; psum combines."""
    n, d = x_rep.shape
    e = cfg.n_experts
    e_local = e // ep
    cap = _capacity(n, cfg.top_k, e, cfg.capacity_factor)
    top_p, top_i, aux = router_topk(x_rep, router_w, cfg.top_k)
    pos, keep = _dispatch_indices(top_i, e, cap)
    # keep only assignments owned by this shard
    lo = shard_idx * e_local
    mine = (top_i >= lo) & (top_i < lo + e_local)
    keep_local = keep & mine
    top_i_local = jnp.where(mine, top_i - lo, 0)
    buf = _scatter_dispatch(x_rep, top_i_local, top_p, pos, keep_local,
                            e_local, cap)
    buf = _expert_ffn(buf, w_gate, w_up, w_down)
    out = _gather_combine(buf, top_i_local, top_p, pos, keep_local)
    out = jax.lax.psum(out, model_axis)
    return out, aux


def _shared_expert(x, p):
    h = jnp.einsum("nd,df->nf", x, p["w_gate"], preferred_element_type=jnp.float32)
    u = jnp.einsum("nd,df->nf", x, p["w_up"], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(h) * u).astype(x.dtype)
    # TP partial-sum all-reduce in the activation dtype (§Perf C.3)
    return jnp.einsum("nf,fd->nd", h, p["w_down"],
                      preferred_element_type=x.dtype).astype(x.dtype)


def moe_forward(x, p, cfg: ModelConfig, ctx: ShardCtx) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B,S,d), aux loss scalar)."""
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    ep = ctx.model_size
    if ctx.mesh is None or ep == 1 or cfg.n_experts % ep != 0:
        cap = _capacity(b * s, cfg.top_k, cfg.n_experts, cfg.capacity_factor)
        out, aux = _moe_local(x_flat, p, cfg, cap)
    else:
        n_tok = b * s
        shards_all = ctx.data_size * ep
        if n_tok % shards_all == 0 and n_tok // shards_all >= ep:
            # big-batch path: tokens sharded over (batch, model), all_to_all EP
            body = jax.shard_map(
                lambda xf, rw, wg, wu, wd: _moe_ep_body(
                    xf, rw, wg, wu, wd, cfg=cfg, ep=ep,
                    model_axis=ctx.model_axis),
                mesh=ctx.mesh,
                in_specs=(P((*ctx.batch_axes, ctx.model_axis), None),
                          P(None, None),
                          P(ctx.model_axis, None, None),
                          P(ctx.model_axis, None, None),
                          P(ctx.model_axis, None, None)),
                out_specs=(P((*ctx.batch_axes, ctx.model_axis), None), P()),
                check_vma=False)
        else:
            # decode path: tokens sharded over batch axes when divisible
            # (replicated over model); fully replicated for tiny batches
            # (e.g. long_500k's global batch of 1)
            def repl_body(xf, rw, wg, wu, wd):
                idx = jax.lax.axis_index(ctx.model_axis)
                return _moe_ep_replicated_body(
                    xf, rw, wg, wu, wd, cfg=cfg, ep=ep,
                    model_axis=ctx.model_axis, shard_idx=idx)
            tok_spec = (ctx.batch_axes if len(ctx.batch_axes) > 1
                        else ctx.batch_axes[0])
            if n_tok % ctx.data_size != 0:
                tok_spec = None
            body = jax.shard_map(
                repl_body,
                mesh=ctx.mesh,
                in_specs=(P(tok_spec, None),
                          P(None, None),
                          P(ctx.model_axis, None, None),
                          P(ctx.model_axis, None, None),
                          P(ctx.model_axis, None, None)),
                out_specs=(P(tok_spec, None), P()),
                check_vma=False)
        out, aux = body(x_flat, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if "shared" in p:
        out = out + _shared_expert(x_flat, p["shared"])
    return out.reshape(b, s, d), aux
