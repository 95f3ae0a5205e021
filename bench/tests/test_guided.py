"""A class-conditional cell with classifier-free guided traffic, driven
through the whole run: correct when sound, not correct when the label,
the guidance or the uncond lane is broken underneath, and runnable under
a family adapter that lives outside the benchmark's tree.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import numpy as np
import pytest

import calibrate
import run as harness
import tiny
from benchlib import spec


def _run(c=None):
    return harness.run(tiny.args(), c=c or tiny.guided_cell(),
                       require_chip=False)


def test_guided_cell_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def _relabel(monkeypatch, fault):
    """Alter the labels of each admitted request's lanes where the engine
    makes them: ``conds`` is False on a guided pair's uncond lane only."""
    from repro.serve.engine import ServeEngine
    admit = ServeEngine._admit_host

    def altered(self, req, *a, **k):
        k_init, k_srv, ys, pairs, conds = admit(self, req, *a, **k)
        if fault == "label_dropped":
            ys = np.full_like(ys, self.num_classes)
        else:
            ys = np.where(conds, ys, req.label)
        return k_init, k_srv, ys, pairs, conds
    monkeypatch.setattr(ServeEngine, "_admit_host", altered)


@pytest.mark.parametrize("fault", ["label_dropped", "guidance_ignored",
                                   "shadow_sees_the_label"])
def test_guided_fault_is_not_correct(monkeypatch, fault):
    if fault == "guidance_ignored":
        from repro.diffusion.backend import StepBackend

        def unguided(self, x, cols, eps_hat, noise, active, pair, cond,
                     tables, *, clip=3.0):
            return self.masked_index_step(x, cols, eps_hat, noise, active,
                                          tables, clip=clip)
        monkeypatch.setattr(StepBackend, "guided_masked_index_step",
                            unguided)
    else:
        _relabel(monkeypatch, fault)
    assert not _run()["correct"]


def test_a_family_outside_the_tree_runs_by_name(monkeypatch, tmp_path):
    """A configuration naming a family that exists only as a file in the
    families directory runs through run.py and calibrate.py; a name with
    no file there is refused."""
    (tmp_path / "unet_twin.py").write_text(
        (spec.BENCH / "families" / "unet.py").read_text())
    monkeypatch.setattr(spec, "FAMILIES", tmp_path)
    c = tiny.guided_cell()
    c["config"]["family"] = "unet_twin"
    res = _run(c)
    assert res["correct"], res["checks"]
    seed = 4_000_000_007
    got = calibrate.readings(c, seed, control=False)
    assert got["failed"] == 0
    assert all(v <= c["limits"]["numbers"][k]["limit"]
               for k, v in got["program"].items()), got
    c["config"]["family"] = "unet"
    with pytest.raises(FileNotFoundError):
        _run(c)
