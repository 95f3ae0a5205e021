"""Shared layers: norms, RoPE / M-RoPE, SwiGLU, embeddings, losses, ShardCtx.

All parameters are plain nested dicts of jnp arrays.  Matmuls accumulate in
float32 via ``preferred_element_type`` regardless of the storage dtype.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Sharding context
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Carries the mesh + logical axis names into model code.

    ``None`` mesh = single-device mode (smoke tests): all constraints no-op and
    MoE uses its dense-dispatch fallback.
    """

    mesh: Optional[jax.sharding.Mesh] = None
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    # ---- perf levers (EXPERIMENTS.md §Perf) ----
    # shard the q sequence dim over the model axis when n_heads does not
    # divide it (instead of replicating attention model_size times)
    seq_shard_attn: bool = False
    # shard the decode KV cache over its sequence dim (flash-decoding style;
    # SPMD inserts the partial-softmax combine collectives)
    cache_seq_shard: bool = False

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def data_size(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n

    def resolve(self, dim):
        """Map a logical dim tag to mesh axes."""
        if dim is None:
            return None
        if dim == "batch":
            return self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]
        if dim == "model":
            return self.model_axis
        return dim

    def spec(self, *dims) -> P:
        return P(*[self.resolve(d) for d in dims])

    def sharding(self, *dims) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(*dims))


def shard(x: jax.Array, ctx: ShardCtx, *dims) -> jax.Array:
    """with_sharding_constraint if a mesh is present, else identity.

    ``dims`` uses logical tags: "batch", "model", axis names, or None.  A dim
    tagged "model" is only constrained when its size divides the model axis.
    """
    if ctx.mesh is None:
        return x
    resolved = []
    for i, d in enumerate(dims):
        if d == "model" and x.shape[i] % ctx.model_size != 0:
            resolved.append(None)          # non-divisible: replicate
        else:
            resolved.append(ctx.resolve(d))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*resolved)))


# ---------------------------------------------------------------------------
# Initialization helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, in_axis_size: Optional[int] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = fan_in ** -0.5
    return (std * jax.random.truncated_normal(key, -3, 3, shape)).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(x, p, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def groupnorm(x, scale, bias, groups, eps=1e-5):
    """GroupNorm over the channel (last) axis; x: (..., C)."""
    dt = x.dtype
    *lead, c = x.shape
    x = x.astype(jnp.float32).reshape(*lead, groups, c // groups)
    mean = x.mean(axis=tuple(range(1, x.ndim - 2)) + (x.ndim - 1,), keepdims=True)
    var = x.var(axis=tuple(range(1, x.ndim - 2)) + (x.ndim - 1,), keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x.reshape(*lead, c)
    return (x * scale + bias).astype(dt)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)                     # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs       # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Sequence[int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, hd); positions: (3, B, S) — (temporal, height, width) ids.
    ``sections`` splits the hd/2 frequency bands among the three position
    streams (sum(sections) == hd // 2).
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_frequencies(x.shape[-1], theta)                     # (half,)
    # pick, per frequency band, which positional stream drives it
    section_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=half)
    pos_sel = positions.astype(jnp.float32)[section_id]              # (half, B, S)
    angles = jnp.moveaxis(pos_sel, 0, -1) * freqs                    # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def mlp_init(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2, k3 = split_keys(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype=dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype=dtype),
        "w_down": dense_init(k3, (d_ff, d_model), d_ff, dtype=dtype),
    }


def mlp(x, p, ctx: ShardCtx):
    # gate/up in the activation dtype: their TRANSPOSE (grad_x) dots contract
    # over the sharded d_ff dim and all-reduce — keep those bf16 (§Perf C.4)
    h = jnp.einsum("bsd,df->bsf", x, p["w_gate"],
                   preferred_element_type=x.dtype)
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"],
                   preferred_element_type=x.dtype)
    h = jax.nn.silu(h.astype(jnp.float32)) * u.astype(jnp.float32)
    h = shard(h.astype(x.dtype), ctx, "batch", None, "model")
    # TP partial-sum all-reduce in the activation dtype (bf16 on production
    # configs) — halves the dominant f32[B,S,d] collective (§Perf C.3)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"],
                     preferred_element_type=x.dtype)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------
def embed_init(key, vocab, d_model, tie: bool, dtype=jnp.float32):
    k1, k2 = split_keys(key, 2)
    p = {"embedding": dense_init(k1, (vocab, d_model), d_model, dtype=dtype)}
    if not tie:
        p["lm_head"] = dense_init(k2, (d_model, vocab), d_model, dtype=dtype)
    return p


def embed(tokens, p, ctx: ShardCtx):
    out = jnp.take(p["embedding"], tokens, axis=0)
    return shard(out, ctx, "batch", None, None)


def unembed(x, p, ctx: ShardCtx):
    w = p.get("lm_head")
    if w is None:
        w = p["embedding"].T
    # logits in the activation dtype; CE upcasts to f32 for the logsumexp.
    # grad_x of this einsum contracts over the sharded vocab dim — keeping
    # it bf16 halves that all-reduce (§Perf C.4)
    logits = jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=x.dtype)
    return shard(logits, ctx, "batch", None, "model")


def softmax_cross_entropy(logits, labels):
    """logits: (B,S,V); labels: (B,S) int32.  Mean over all tokens.
    Computed in f32 regardless of the logits' storage dtype."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
