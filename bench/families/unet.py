"""The U-Net family: the program's ``repro.models.unet``, built from a
configuration's ``model`` block (the fields of ``UNetConfig``).

A family adapter is the one place where the harness meets a model
architecture.  A configuration names it with ``"family": "<name>"``
(``"unet"`` where the key is absent) and the harness loads
``bench/families/<name>.py``, which gives:

    image_shape(model)   -> (H, W, C) of one image
    init(model)          -> jittable ``key -> params``, the program's own
                            public initialiser
    apply_fn(model)      -> what ``EngineConfig.apply_fn`` takes:
                            ``(params, x, t)``, or ``(params, x, t, y)``
                            where ``num_classes(model) > 0``; returns ε̂
    num_classes(model)   -> class labels the model takes (0: none; label
                            ``num_classes`` is the null label)
    forward_flops(model) -> model operations of one image's forward,
                            counted from shapes by the benchmark
"""
from __future__ import annotations

import functools

from benchlib.yardsticks import unet_forward_flops
from repro.configs.base import UNetConfig
from repro.models import unet


def _cfg(model: dict) -> UNetConfig:
    return UNetConfig(**{k: tuple(v) if isinstance(v, list) else v
                         for k, v in model.items()})


def image_shape(model: dict) -> tuple:
    return (model["image_size"], model["image_size"], model["in_channels"])


def init(model: dict):
    return functools.partial(unet.init_params, cfg=_cfg(model))


def apply_fn(model: dict):
    cfg = _cfg(model)
    if cfg.num_classes:
        def apply(params, x, t, y):
            return unet.forward(params, x, t, cfg, y)
    else:
        def apply(params, x, t):
            return unet.forward(params, x, t, cfg)
    return apply


def num_classes(model: dict) -> int:
    return model.get("num_classes", 0)


def forward_flops(model: dict) -> int:
    return unet_forward_flops(model)
