"""DDPM U-Net — the CollaFuse paper's backbone (§4).

ResNet blocks for down/up-sampling, self-attention at configured resolutions,
sinusoidal time embedding.  NHWC layout, pure JAX (this model runs at demo
scale on CPU for the faithful reproduction; the assigned transformer
architectures cover the production-mesh path).
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp

from repro.configs.base import UNetConfig
from repro.models.layers import dense_init, split_keys


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------
def conv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    fan_in = kh * kw * cin
    w = dense_init(key, (kh, kw, cin, cout), fan_in, dtype=dtype)
    return {"w": w, "bias": jnp.zeros((cout,), dtype)}


def conv(x, p, stride: int = 1):
    out = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return out + p["bias"]


def gn_init(c, dtype=jnp.float32):
    return {"g_scale": jnp.ones((c,), dtype), "g_bias": jnp.zeros((c,), dtype)}


def gn(x, p, groups):
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
    return (xg.reshape(b, h, w, c) * p["g_scale"] + p["g_bias"]).astype(x.dtype)


def time_embedding(t, dim):
    """Sinusoidal embedding of integer timesteps t: (B,) -> (B, dim)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------
def resblock_init(key, cin, cout, time_dim, groups, dtype=jnp.float32):
    k1, k2, k3, k4 = split_keys(key, 4)
    p = {
        "norm1": gn_init(cin, dtype),
        "conv1": conv_init(k1, 3, 3, cin, cout, dtype),
        "time_proj": {"w": dense_init(k2, (time_dim, cout), time_dim, dtype=dtype),
                      "bias": jnp.zeros((cout,), dtype)},
        "norm2": gn_init(cout, dtype),
        "conv2": conv_init(k3, 3, 3, cout, cout, dtype),
    }
    if cin != cout:
        p["skip"] = conv_init(k4, 1, 1, cin, cout, dtype)
    return p


def resblock(x, temb, p, groups):
    h = conv(jax.nn.silu(gn(x, p["norm1"], groups)), p["conv1"])
    h = h + (temb @ p["time_proj"]["w"] + p["time_proj"]["bias"])[:, None, None, :]
    h = conv(jax.nn.silu(gn(h, p["norm2"], groups)), p["conv2"])
    skip = conv(x, p["skip"]) if "skip" in p else x
    return h + skip


def attnblock_init(key, c, dtype=jnp.float32):
    k1, k2 = split_keys(key, 2)
    return {
        "norm": gn_init(c, dtype),
        "qkv": conv_init(k1, 1, 1, c, 3 * c, dtype),
        "out": conv_init(k2, 1, 1, c, c, dtype),
    }


@jax.named_scope("attn")
def attnblock(x, p, groups):
    b, h, w, c = x.shape
    qkv = conv(gn(x, p["norm"], groups), p["qkv"]).reshape(b, h * w, 3, c)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bic,bjc->bij", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(c)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bij,bjc->bic", a.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return x + conv(o.reshape(b, h, w, c), p["out"])


# ---------------------------------------------------------------------------
# full U-Net
# ---------------------------------------------------------------------------
def init_params(key, cfg: UNetConfig):
    dtype = jnp.dtype(cfg.dtype)
    ks = iter(split_keys(key, 256))
    ch = cfg.base_channels
    td = cfg.time_dim
    p = {
        "time_mlp1": {"w": dense_init(next(ks), (td, td), td, dtype=dtype),
                      "bias": jnp.zeros((td,), dtype)},
        "time_mlp2": {"w": dense_init(next(ks), (td, td), td, dtype=dtype),
                      "bias": jnp.zeros((td,), dtype)},
        "conv_in": conv_init(next(ks), 3, 3, cfg.in_channels, ch, dtype),
    }
    if cfg.num_classes:
        # class-conditioning table added to the time embedding; the LAST
        # row (index num_classes) is the null label — the uncond branch of
        # classifier-free guidance and the label-dropout target
        p["label_emb"] = dense_init(next(ks), (cfg.num_classes + 1, td),
                                    td, dtype=dtype)
    res = cfg.image_size
    chans = [ch]
    cur = ch
    downs = []
    for li, mult in enumerate(cfg.channel_mults):
        cout = ch * mult
        stage = {"res": [], "attn": []}
        for _ in range(cfg.n_res_blocks):
            stage["res"].append(resblock_init(next(ks), cur, cout, td,
                                              cfg.norm_groups, dtype))
            cur = cout
            stage["attn"].append(
                attnblock_init(next(ks), cur, dtype)
                if res in cfg.attn_resolutions else None)
            chans.append(cur)
        if li < len(cfg.channel_mults) - 1:
            stage["down"] = conv_init(next(ks), 3, 3, cur, cur, dtype)
            chans.append(cur)
            res //= 2
        downs.append(stage)
    p["downs"] = downs
    p["mid"] = {
        "res1": resblock_init(next(ks), cur, cur, td, cfg.norm_groups, dtype),
        "attn": attnblock_init(next(ks), cur, dtype),
        "res2": resblock_init(next(ks), cur, cur, td, cfg.norm_groups, dtype),
    }
    ups = []
    for li, mult in list(enumerate(cfg.channel_mults))[::-1]:
        cout = ch * mult
        stage = {"res": [], "attn": []}
        for _ in range(cfg.n_res_blocks + 1):
            skip = chans.pop()
            stage["res"].append(resblock_init(next(ks), cur + skip, cout, td,
                                              cfg.norm_groups, dtype))
            cur = cout
            stage["attn"].append(
                attnblock_init(next(ks), cur, dtype)
                if res in cfg.attn_resolutions else None)
        if li > 0:
            stage["up"] = conv_init(next(ks), 3, 3, cur, cur, dtype)
            res *= 2
        ups.append(stage)
    p["ups"] = ups
    p["norm_out"] = gn_init(cur, dtype)
    p["conv_out"] = conv_init(next(ks), 3, 3, cur, cfg.in_channels, dtype)
    return p


def forward(params, x, t, cfg: UNetConfig, y=None):
    """x: (B,H,W,C) noised image; t: (B,) int timesteps -> eps_hat.

    ``y``: (B,) int class labels when ``cfg.num_classes`` > 0 — the label
    embedding (null row = ``num_classes``) is added to the time embedding,
    so the uncond branch of classifier-free guidance is just the null
    label.  ``y=None`` on a conditional config conditions on the null
    label everywhere (the unguided/uncond path)."""
    g = cfg.norm_groups
    temb = time_embedding(t, cfg.time_dim)
    temb = jax.nn.silu(temb @ params["time_mlp1"]["w"] +
                       params["time_mlp1"]["bias"])
    temb = temb @ params["time_mlp2"]["w"] + params["time_mlp2"]["bias"]
    if cfg.num_classes:
        if y is None:
            y = jnp.full(x.shape[:1], cfg.num_classes, jnp.int32)
        yc = jnp.clip(y.astype(jnp.int32), 0, cfg.num_classes)
        temb = temb + params["label_emb"][yc]

    h = conv(x, params["conv_in"])
    skips = [h]
    for li, stage in enumerate(params["downs"]):
        for rb, ab in zip(stage["res"], stage["attn"]):
            h = resblock(h, temb, rb, g)
            if ab is not None:
                h = attnblock(h, ab, g)
            skips.append(h)
        if "down" in stage:
            h = conv(h, stage["down"], stride=2)
            skips.append(h)
    h = resblock(h, temb, params["mid"]["res1"], g)
    h = attnblock(h, params["mid"]["attn"], g)
    h = resblock(h, temb, params["mid"]["res2"], g)
    for stage in params["ups"]:
        for rb, ab in zip(stage["res"], stage["attn"]):
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = resblock(h, temb, rb, g)
            if ab is not None:
                h = attnblock(h, ab, g)
        if "up" in stage:
            b, hh, ww, c = h.shape
            h = jax.image.resize(h, (b, hh * 2, ww * 2, c), "nearest")
            h = conv(h, stage["up"])
    h = jax.nn.silu(gn(h, params["norm_out"], g))
    return conv(h, params["conv_out"])
