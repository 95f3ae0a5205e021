"""The whole run on a tiny cell, with the timed path sound and with it
broken underneath: ``correct`` has to come out true, then false for each
fault a serving cell on one chip can have.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import tiny
import run as harness


def _run(**kw):
    return harness.run(tiny.args(), c=tiny.cell(**kw), require_chip=False)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"images_per_s", "xc_p95_s", "x0_p95_s",
                                   "setup_s"}
    assert list(res)[-1] == "checks"


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.diffusion.backend import StepBackend
    monkeypatch.setattr(StepBackend, "masked_index_step",
                        lambda self, x, *a, **k: x)
    assert not _run()["correct"]


def test_half_of_the_batch_left_out(monkeypatch):
    """The model runs on the first half of each batch of lanes and the
    rest reuse its outputs."""
    import jax.numpy as jnp
    from repro.models import unet
    forward = unet.forward

    def half(params, x, t, cfg, y=None):
        b = x.shape[0]
        h = max(1, -(-b // 2))
        eps = forward(params, x[:h], t[:h], cfg)
        return jnp.concatenate([eps, eps[:b - h]])
    monkeypatch.setattr(unet, "forward", half)
    assert not _run()["correct"]


def test_disclosed_answer_altered_where_produced(monkeypatch):
    from repro.serve.engine import ServeEngine
    rows = ServeEngine._host_rows

    def altered(self, arr, lanes):
        return {lane: row + 0.05 for lane, row in
                rows(self, arr, lanes).items()}
    monkeypatch.setattr(ServeEngine, "_host_rows", altered)
    assert not _run()["correct"]


def test_final_image_altered_where_produced(monkeypatch):
    from repro.serve.engine import ServeEngine
    scatter = ServeEngine._scatter_finish

    def altered(self, x0_ref, placement):
        return scatter(self, x0_ref * 1.01, placement)
    monkeypatch.setattr(ServeEngine, "_scatter_finish", altered)
    assert not _run()["correct"]
