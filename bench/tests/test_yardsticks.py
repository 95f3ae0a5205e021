"""The frozen yardsticks against XLA's own counts, and the traffic
generator's guarantees."""
import jax
import jax.numpy as jnp
import pytest

import tiny
from benchlib import spec, traffic
from benchlib.yardsticks import PEAKS, peaks, unet_forward_flops


@pytest.mark.parametrize("name", ["paper_unet", "ddpm_cifar10"])
def test_forward_flops_match_xla_cost_analysis(name):
    """Our count leaves out normalisation and activations: within 1% below
    XLA's count of the lowered forward at batch 1 (88.10 and 11.68 GFLOP)."""
    from repro.configs.base import UNetConfig
    from repro.models import unet
    m = spec.load_json(spec.BENCH / "configs" / f"{name}.json")["model"]
    cfg = UNetConfig(**{k: tuple(v) if isinstance(v, list) else v
                        for k, v in m.items()})
    p = jax.eval_shape(lambda k: unet.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, cfg.image_size, cfg.image_size,
                              cfg.in_channels), jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.int32)
    xla = jax.jit(lambda p, x, t: unet.forward(p, x, t, cfg)).lower(
        p, x, t).compile().cost_analysis()["flops"]
    ours = unet_forward_flops(m)
    assert 0.99 * xla <= ours <= xla, (ours, xla)


def test_peaks_table_has_no_default():
    assert peaks("TPU v5 lite") is PEAKS["TPU v5 lite"]
    with pytest.raises(KeyError):
        peaks("cpu")


@pytest.mark.parametrize("mix,slots", [("ddpm100_job", 32),
                                       ("ddim50_job", 128),
                                       ("ddim20_job", 32)])
def test_every_seed_asks_for_the_same_work(mix, slots):
    """The job is fixed by the traffic file alone, holds every (sampler,
    cut, client) group the check samples from, and fills its images."""
    t = spec.load_json(spec.BENCH / "traffic" / f"{mix}.json")
    specs = traffic.composition(t, slots, 4)
    assert specs == traffic.composition(t, slots, 4)
    assert traffic.job_images(specs) == t["job_images_per_slot"] * slots
    groups = {(s.sampler, s.cut_ratio, s.client) for s in specs}
    assert groups == {(n, c, k) for n in t["samplers"]
                      for c in t["cut_ratios"] for k in range(4)}


def test_lane_steps_are_what_the_engine_steps():
    """The composition's server and client lane-steps (mfu's and the
    finisher's denominators) equal the lanes the engine steps in a job:
    live slots each tick, and valid finisher lanes to their ends."""
    import numpy as np
    from benchlib.cell import Cell
    c = tiny.cell(traffic="ddim20_job", schedule="linear", T=40)
    cell = Cell(c["config"], c["traffic"], 5)
    eng, seen = cell.engine, {"server": 0, "client": 0}
    tick, finish = eng._tick, eng._finish

    def counted_tick(state, params, menu):
        seen["server"] += int(np.asarray(state["active"]).sum())
        return tick(state, params, menu)

    def counted_finish(stack, menu, x, pos, end, traj, keys, valid):
        seen["client"] += int(np.where(valid, end - pos, 0).sum())
        return finish(stack, menu, x, pos, end, traj, keys, valid)
    eng._tick, eng._finish = counted_tick, counted_finish
    cell.run_job(1, keep_outputs=False)
    assert cell.lane_steps == (seen["server"], seen["client"])
    assert seen["server"] > 0 and seen["client"] > 0
