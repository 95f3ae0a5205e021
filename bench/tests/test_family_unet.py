"""The U-Net family adapter gives what the harness built before it had
families: the same weights, bit for bit, and the same operations a
forward, for both U-Net configurations."""
import functools

import jax
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from benchlib import spec
from benchlib.cell import weight_keys

# unet_forward_flops of each configuration as it stood before the family
FLOPS = {"paper_unet": 87_641_105_408, "ddpm_cifar10": 11_619_899_392}


@pytest.mark.parametrize("name", sorted(FLOPS))
def test_unet_family_gives_the_weights_and_flops_it_gave_before(name):
    from repro.configs.base import UNetConfig
    from repro.models import unet
    config = spec.load_json(spec.BENCH / "configs" / f"{name}.json")
    family, m = spec.family(config), config["model"]
    assert family.forward_flops(m) == FLOPS[name]
    assert family.num_classes(m) == 0
    assert tuple(family.image_shape(m)) == (m["image_size"],
                                            m["image_size"],
                                            m["in_channels"])
    cfg = UNetConfig(**{k: tuple(v) if isinstance(v, list) else v
                        for k, v in m.items()})
    key = weight_keys(6_000_000_101, 4)[0]
    before = jax.jit(functools.partial(unet.init_params, cfg=cfg))(key)
    after = jax.jit(family.init(m))(key)
    assert jax.tree.structure(before) == jax.tree.structure(after)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
