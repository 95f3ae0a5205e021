"""The frozen yardsticks against XLA's own counts, and the traffic
generator's guarantees."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import tiny
from benchlib import spec, traffic
from benchlib.yardsticks import PEAKS, peaks


@pytest.mark.parametrize("name", [c["name"] for c in
                                  spec.benchmark()["configs"]])
def test_forward_flops_match_xla_cost_analysis(name):
    """The family's count leaves out normalisation and activations: within
    1% below XLA's count of the lowered forward at batch 1 (88.10 and
    11.68 GFLOP for the two U-Nets)."""
    conf = [c for c in spec.benchmark()["configs"] if c["name"] == name][0]
    config = spec.load_json(spec.ROOT / conf["file"])
    family, m = spec.family(config), config["model"]
    p = jax.eval_shape(family.init(m), jax.random.PRNGKey(0))
    args = [p, jax.ShapeDtypeStruct((1,) + tuple(family.image_shape(m)),
                                    jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32)]
    if family.num_classes(m):
        args.append(jax.ShapeDtypeStruct((1,), jnp.int32))
    xla = jax.jit(family.apply_fn(m)).lower(
        *args).compile().cost_analysis()["flops"]
    ours = family.forward_flops(m)
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


def test_labels_are_dealt_round_robin():
    """On a model that takes class labels, request i asks for label
    i mod classes; the rest of the job is the unconditional one."""
    t = spec.load_json(spec.BENCH / "traffic" / "ddim50_job.json")
    plain = traffic.composition(t, 128, 4)
    labelled = traffic.composition(t, 128, 4, num_classes=10)
    assert all(s.label == 0 for s in plain)
    assert [s.label for s in labelled] == [i % 10
                                           for i in range(len(labelled))]
    assert [dataclasses.replace(s, label=0) for s in labelled] == plain


@pytest.mark.parametrize("guided", [False, True], ids=["plain", "guided"])
def test_lane_steps_are_what_the_engine_steps(guided):
    """The composition's server and client lane-steps (mfu's and the
    finisher's denominators) equal the lanes the engine steps in a job:
    live slots each tick (both lanes of a guided image), and valid
    finisher lanes to their ends."""
    import inspect

    import numpy as np
    from benchlib.cell import Cell
    c = (tiny.guided_cell() if guided else
         tiny.cell(traffic="ddim20_job", schedule="linear", T=40))
    cell = Cell(c["config"], c["traffic"], 5)
    eng, seen = cell.engine, {"server": 0, "client": 0}
    tick, finish = eng._tick, eng._finish
    tick_sig, finish_sig = inspect.signature(tick), inspect.signature(finish)

    def counted_tick(*args, **kwargs):
        state = tick_sig.bind(*args, **kwargs).arguments["state"]
        seen["server"] += int(np.asarray(state["active"]).sum())
        return tick(*args, **kwargs)

    def counted_finish(*args, **kwargs):
        a = finish_sig.bind(*args, **kwargs).arguments
        seen["client"] += int(np.where(a["valid"], a["end"] - a["pos"],
                                       0).sum())
        return finish(*args, **kwargs)
    eng._tick, eng._finish = counted_tick, counted_finish
    cell.run_job(1, keep_outputs=False)
    assert cell.lane_steps == (seen["server"], seen["client"])
    assert seen["server"] > 0 and seen["client"] > 0
    if guided:
        assert any(s.sampler == "ddim20_cfg" for s in cell.specs)
