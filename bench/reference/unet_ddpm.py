"""Plain reference for the U-Net DDPM configurations: the model, the
variance schedules, the DDPM/DDIM pair coefficients and one lane's
split-sampling chain, in straightforward ``jax.numpy`` and float32.

It imports nothing of the system under test.  Its weights come from its
own :func:`init_params`, which follows the same key discipline as the
served model's initialiser, so one seed gives both the same numbers.  A
lane is replayed alone at batch 1, one jitted step per trajectory
position, with the serving engine's key derivation:

    lane key  = fold_in(request key, image index)
    k_init, k_srv, k_cli = split(lane key, 3)
    x_T       = normal(k_init, image shape)
    each step: k, k_n = split(k); z = normal(k_n, image shape)
    x_prev    = clip((x - c_eps * eps) / sqrt(ar) + keep * sigma * z, ±3)

Server steps run positions [0, cut) under the server weights from k_srv;
client steps run [cut, K) under the request's client weights from k_cli.

A configuration whose ``model`` has ``num_classes`` > 0 is class
conditional: a table of ``num_classes + 1`` label embeddings (the last
row is the null label) is added to the time embedding.  A guided server
step is classifier-free guidance (Ho & Salimans, arXiv:2207.12598) in the
GLIDE/DiT form, w = 1 being the conditional model alone:

    eps = eps_u + w * (eps_c - eps_u)
    eps_c = eps(x, t, label), eps_u = eps(x, t, null label)

with the step's noise drawn from the lane's own key chain.  The protocol
as this system serves it departs from plain guided sampling in two
places, both of the protocol and not of the model: an unguided request's
steps, and every client step of any request, run unguided on the null
label (the client finishes on its private model without the request's
label).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def _dense_init(key, shape, fan_in):
    std = fan_in ** -0.5
    return (std * jax.random.truncated_normal(key, -3, 3, shape)).astype(
        jnp.float32)


def _conv_init(key, kh, kw, cin, cout):
    return {"w": _dense_init(key, (kh, kw, cin, cout), kh * kw * cin),
            "bias": jnp.zeros((cout,), jnp.float32)}


def _gn_init(c):
    return {"g_scale": jnp.ones((c,), jnp.float32),
            "g_bias": jnp.zeros((c,), jnp.float32)}


def _res_init(key, cin, cout, td):
    k1, k2, k3, k4 = list(jax.random.split(key, 4))
    p = {"norm1": _gn_init(cin),
         "conv1": _conv_init(k1, 3, 3, cin, cout),
         "time_proj": {"w": _dense_init(k2, (td, cout), td),
                       "bias": jnp.zeros((cout,), jnp.float32)},
         "norm2": _gn_init(cout),
         "conv2": _conv_init(k3, 3, 3, cout, cout)}
    if cin != cout:
        p["skip"] = _conv_init(k4, 1, 1, cin, cout)
    return p


def _attn_init(key, c):
    k1, k2 = list(jax.random.split(key, 2))
    return {"norm": _gn_init(c), "qkv": _conv_init(k1, 1, 1, c, 3 * c),
            "out": _conv_init(k2, 1, 1, c, c)}


def init_params(key, m: dict):
    """Weights of the U-Net described by the configuration's ``model``."""
    ks = iter(list(jax.random.split(key, 256)))
    ch, td = m["base_channels"], m["time_dim"]
    p = {"time_mlp1": {"w": _dense_init(next(ks), (td, td), td),
                       "bias": jnp.zeros((td,), jnp.float32)},
         "time_mlp2": {"w": _dense_init(next(ks), (td, td), td),
                       "bias": jnp.zeros((td,), jnp.float32)},
         "conv_in": _conv_init(next(ks), 3, 3, m["in_channels"], ch)}
    if m.get("num_classes"):
        p["label_emb"] = _dense_init(next(ks), (m["num_classes"] + 1, td),
                                     td)
    res, cur, chans = m["image_size"], ch, [ch]
    mults = m["channel_mults"]
    downs = []
    for li, mult in enumerate(mults):
        stage = {"res": [], "attn": []}
        for _ in range(m["n_res_blocks"]):
            stage["res"].append(_res_init(next(ks), cur, ch * mult, td))
            cur = ch * mult
            stage["attn"].append(_attn_init(next(ks), cur)
                                 if res in m["attn_resolutions"] else None)
            chans.append(cur)
        if li < len(mults) - 1:
            stage["down"] = _conv_init(next(ks), 3, 3, cur, cur)
            chans.append(cur)
            res //= 2
        downs.append(stage)
    p["downs"] = downs
    p["mid"] = {"res1": _res_init(next(ks), cur, cur, td),
                "attn": _attn_init(next(ks), cur),
                "res2": _res_init(next(ks), cur, cur, td)}
    ups = []
    for li, mult in list(enumerate(mults))[::-1]:
        stage = {"res": [], "attn": []}
        for _ in range(m["n_res_blocks"] + 1):
            skip = chans.pop()
            stage["res"].append(_res_init(next(ks), cur + skip, ch * mult,
                                          td))
            cur = ch * mult
            stage["attn"].append(_attn_init(next(ks), cur)
                                 if res in m["attn_resolutions"] else None)
        if li > 0:
            stage["up"] = _conv_init(next(ks), 3, 3, cur, cur)
            res *= 2
        ups.append(stage)
    p["ups"] = ups
    p["norm_out"] = _gn_init(cur)
    p["conv_out"] = _conv_init(next(ks), 3, 3, cur, m["in_channels"])
    return p


def _conv(x, p, stride=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["bias"]


def _gn(x, p, groups):
    b, h, w, c = x.shape
    xg = x.reshape(b, h, w, groups, c // groups)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
    return xg.reshape(b, h, w, c) * p["g_scale"] + p["g_bias"]


def _res(x, temb, p, g):
    h = _conv(jax.nn.silu(_gn(x, p["norm1"], g)), p["conv1"])
    h = h + (temb @ p["time_proj"]["w"] + p["time_proj"]["bias"])[
        :, None, None, :]
    h = _conv(jax.nn.silu(_gn(h, p["norm2"], g)), p["conv2"])
    return h + (_conv(x, p["skip"]) if "skip" in p else x)


def _attn(x, p, g):
    b, h, w, c = x.shape
    qkv = _conv(_gn(x, p["norm"], g), p["qkv"]).reshape(b, h * w, 3, c)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    a = jax.nn.softmax(jnp.einsum("bic,bjc->bij", q, k) / math.sqrt(c),
                       axis=-1)
    o = jnp.einsum("bij,bjc->bic", a, v)
    return x + _conv(o.reshape(b, h, w, c), p["out"])


def forward(params, x, t, m: dict, y=None):
    """ε̂ for images x (B, H, W, C) at integer timesteps t (B,), under
    class labels y (B,) where the model is conditional (None: the null
    label)."""
    g, td = m["norm_groups"], m["time_dim"]
    half = td // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    temb = jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)
    temb = jax.nn.silu(temb @ params["time_mlp1"]["w"]
                       + params["time_mlp1"]["bias"])
    temb = temb @ params["time_mlp2"]["w"] + params["time_mlp2"]["bias"]
    nc = m.get("num_classes", 0)
    if nc:
        if y is None:
            y = jnp.full(x.shape[:1], nc, jnp.int32)
        temb = temb + params["label_emb"][y]
    h = _conv(x, params["conv_in"])
    skips = [h]
    for stage in params["downs"]:
        for rb, ab in zip(stage["res"], stage["attn"]):
            h = _res(h, temb, rb, g)
            if ab is not None:
                h = _attn(h, ab, g)
            skips.append(h)
        if "down" in stage:
            h = _conv(h, stage["down"], stride=2)
            skips.append(h)
    h = _res(h, temb, params["mid"]["res1"], g)
    h = _attn(h, params["mid"]["attn"], g)
    h = _res(h, temb, params["mid"]["res2"], g)
    for stage in params["ups"]:
        for rb, ab in zip(stage["res"], stage["attn"]):
            h = _res(jnp.concatenate([h, skips.pop()], axis=-1), temb, rb, g)
            if ab is not None:
                h = _attn(h, ab, g)
        if "up" in stage:
            b, hh, ww, c = h.shape
            h = jax.image.resize(h, (b, 2 * hh, 2 * ww, c), "nearest")
            h = _conv(h, stage["up"])
    h = jax.nn.silu(_gn(h, params["norm_out"], g))
    return _conv(h, params["conv_out"])


# ---------------------------------------------------------------------------
# schedules, trajectories and pair coefficients
# ---------------------------------------------------------------------------
def schedule(name: str, T: int) -> dict:
    """float32 arrays of the schedule, index t-1 holding timestep t:
    cosine (Nichol & Dhariwal, s = 0.008) or linear (Ho et al., β from
    1e-4 to 0.02 over T = 1000, the range scaled by 1000/T otherwise)."""
    if name == "cosine":
        s = 0.008
        steps = np.arange(T + 1, dtype=np.float64) / T
        f = np.cos((steps + s) / (1 + s) * np.pi / 2) ** 2
        ab = f / f[0]
        betas = np.clip(1.0 - ab[1:] / ab[:-1], 0.0, 0.999)
    elif name == "linear":
        scale = 1000.0 / T
        betas = np.linspace(scale * 1e-4, min(scale * 0.02, 0.999), T,
                            dtype=np.float64)
    else:
        raise ValueError(f"unknown schedule {name!r}")
    alphas = 1.0 - betas
    ab = np.cumprod(alphas)
    ab_prev = np.concatenate([[1.0], ab[:-1]])
    post = betas * (1.0 - ab_prev) / (1.0 - ab)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return {"betas": f32(betas), "alphas": f32(alphas), "alpha_bar": f32(ab),
            "sqrt_1m_ab": f32(np.sqrt(1.0 - ab)), "post_var": f32(post)}


def timesteps(T: int, family: str, num_steps: int):
    """The trajectory: dense {T..1}, or num_steps timesteps spread evenly
    over {1..T} (endpoints included), decreasing."""
    k = num_steps or T
    if k >= T:
        return list(range(T, 0, -1))
    if k == 1:
        return [T]
    ts = np.unique(np.round(np.linspace(1, T, k)).astype(int))
    return [int(t) for t in ts[::-1]]


def cut_position(ts, T: int, cut_ratio: float) -> int:
    """Position whose timestep is nearest t_split = round(c·T); ties go
    to the earlier (noisier) position."""
    t_split = int(round(cut_ratio * T))
    occupied = list(ts) + [0]
    return int(np.argmin([abs(t - t_split) for t in occupied]))


def coefficients(sch: dict, ts, family: str, eta: float) -> jnp.ndarray:
    """(K, 4) rows (c_eps, ar, sigma, keep) of each trajectory position."""
    t = jnp.asarray(ts, jnp.int32)
    dense = list(ts) == list(range(len(ts), 0, -1))
    if family == "ddpm" or (eta == 1.0 and dense):
        i = t - 1
        c_eps = sch["betas"][i] / sch["sqrt_1m_ab"][i]
        ar = sch["alphas"][i]
        sigma = jnp.sqrt(sch["post_var"][i])
        keep = (t > 1).astype(jnp.float32)
    else:
        tp = jnp.asarray(list(ts[1:]) + [0], jnp.int32)

        def ab_at(u):
            return jnp.where(u >= 1, sch["alpha_bar"][jnp.clip(u, 1, None)
                                                      - 1], 1.0)
        ab_t, ab_p = ab_at(t), ab_at(tp)
        sig2 = (eta ** 2) * (1.0 - ab_p) / (1.0 - ab_t) * (1.0 - ab_t / ab_p)
        sigma = jnp.sqrt(sig2)
        ar = ab_t / ab_p
        c_eps = (jnp.sqrt(1.0 - ab_t) - jnp.sqrt(ar)
                 * jnp.sqrt(jnp.clip(1.0 - ab_p - sig2, 0.0, None)))
        keep = (sigma > 0).astype(jnp.float32)
    return jnp.stack([c_eps, ar, sigma, keep], axis=1)


# ---------------------------------------------------------------------------
# one lane's chain, one jitted step per position
# ---------------------------------------------------------------------------
def _eps(params, x, t, y, w, m):
    """ε̂ of one step: the model under label y (None: unconditional), or
    the guided combine at weight w with the null label as the uncond
    branch, both branches in one forward."""
    if w is None:
        return forward(params, x, t, m, y)
    null = jnp.full_like(y, m["num_classes"])
    e = forward(params, jnp.concatenate([x, x]), jnp.concatenate([t, t]),
                m, jnp.concatenate([y, null]))
    eps_c, eps_u = e[:1], e[1:]
    return eps_u + w * (eps_c - eps_u)


@functools.partial(jax.jit, static_argnames=("m", "clip"))
def _step(params, x, t, y, w, key, coef, m, clip):
    eps = _eps(params, x, t, y, w, dict(m))
    key, k_n = jax.random.split(key)
    z = jax.random.normal(k_n, x.shape[1:], jnp.float32)[None]
    c_eps, ar, sigma, keep = coef[0], coef[1], coef[2], coef[3]
    x = (x - c_eps * eps) / jnp.sqrt(ar) + keep * sigma * z
    return jnp.clip(x, -clip, clip), key


@jax.jit
def _lane_start(req_key, image, shape_probe):
    k_init, k_srv, k_cli = jax.random.split(
        jax.random.fold_in(req_key, image), 3)
    return (jax.random.normal(k_init, shape_probe.shape, jnp.float32),
            k_srv, k_cli)


def replay_lane(server_params, client_params, req_key, image: int,
                m: dict, ts, coefs, cut: int, clip: float, *,
                label=None, guidance=None):
    """(x_c, x_0) of image ``image`` of a request, replayed alone.  On a
    conditional model, ``guidance`` (a weight, or None for an unguided
    request) guides the server steps towards ``label``; every other step
    runs on the null label."""
    frozen = tuple((k, tuple(v) if isinstance(v, list) else v)
                   for k, v in sorted(m.items()))
    shape = (m["image_size"], m["image_size"], m["in_channels"])
    nc = m.get("num_classes", 0)
    null = jnp.asarray([nc], jnp.int32) if nc else None
    if guidance is None:
        y_srv, w = null, None
    else:
        y_srv, w = jnp.asarray([label], jnp.int32), jnp.float32(guidance)
    x, k_srv, k_cli = _lane_start(jnp.asarray(req_key), image,
                                  jnp.zeros(shape, jnp.float32))
    x = x[None]
    key = k_srv
    for pos in range(len(ts)):
        if pos == cut:
            x_c = x
            key = k_cli
        t = jnp.asarray([ts[pos]], jnp.int32)
        if pos < cut:
            x, key = _step(server_params, x, t, y_srv, w, key, coefs[pos],
                           frozen, clip)
        else:
            x, key = _step(client_params, x, t, null, None, key,
                           coefs[pos], frozen, clip)
    if cut == len(ts):
        x_c = x
    return np.asarray(x_c[0]), np.asarray(x[0])
