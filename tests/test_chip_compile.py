"""Compile the fused denoise-tick kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a v5e that
is described, not attached, and refuses what the chip would refuse (block
shapes off the (8, 128) tiling, too much fast memory).  The topology is
described inside a module fixture, never at import, so every xdist worker
collects the same tests and only the worker given this file loads the TPU
library.  The CPU rehearsal of ``chip_smoke``'s phases lives here too.
"""
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ddpm_step import ddpm_step, traj_masked_step

IMAGE = (128, 128, 1)          # the paper's 128x128 grayscale images


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("slots", [8, 64])
@pytest.mark.parametrize("columns", [100, 4196])
def test_traj_masked_step_compiles_for_v5e(one_chip, no_compile_cache,
                                           slots, columns):
    """The engine's tick kernel at T=100 columns and with 4096 spare ones."""
    img = _spec((slots,) + IMAGE, jnp.float32, one_chip)
    lanes_i = _spec((slots,), jnp.int32, one_chip)
    lanes_b = _spec((slots,), jnp.bool_, one_chip)
    tables = _spec((5, columns), jnp.float32, one_chip)
    compiled = jax.jit(traj_masked_step).lower(
        img, lanes_i, img, img, lanes_b, tables).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [8, 64])
def test_ddpm_step_compiles_for_v5e(one_chip, no_compile_cache, batch):
    img = _spec((batch,) + IMAGE, jnp.float32, one_chip)
    coefs = _spec((batch, 4), jnp.float32, one_chip)
    compiled = jax.jit(ddpm_step).lower(img, img, img, coefs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chip_smoke_phases_at_tiny_size():
    """``chip_smoke``'s phases on the CPU at a tiny U-Net: the fused tick
    agrees with the jnp tick, the served x_c agrees with
    ``split_sample_lane`` lane by lane, and every output is finite."""
    import chip_smoke
    tick = chip_smoke.check_fused_tick(slots=8, image_size=8, seed=0, T=20)
    assert tick["inactive_bitwise"]
    eng, requests, client_stack = chip_smoke.build(
        "toy", slots=8, n_requests=4, seed=0, T=20, image=8)
    res, _ = chip_smoke.serve(eng, requests, client_stack)
    assert len(res.completions) == len(requests) == 4
    chip_smoke.check_finite(res)
    worst, mean = chip_smoke.check_against_reference(res, eng, n_classes=2)
    assert worst <= chip_smoke.XC_MAX_TOL and mean <= chip_smoke.XC_MEAN_TOL


def test_peaks_are_keyed_by_device_kind():
    from repro.launch.mesh import peaks
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")


def test_compile_cache_honours_env_else_fixed_repo_path(monkeypatch):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.REPO_CACHE)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.REPO_CACHE.parent == \
            Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
