"""Pallas TPU kernels for the paper's hot loop (the fused denoise tick) and
the transformer blocks.  Every kernel launches through :func:`pallas_call`,
which picks compiled Mosaic or the Pallas interpreter from the platform the
program is lowered for."""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` whose mode follows the lowering platform: the
    compiled Mosaic kernel where the program is lowered for a TPU, the
    Pallas interpreter everywhere else.  ``lax.platform_dependent`` lowers
    only the branch of the platform the arrays live on, so a TPU program
    never contains the interpreter and a CPU program never asks Mosaic."""
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)
    return call
