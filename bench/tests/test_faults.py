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
    import types

    import jax.numpy as jnp
    from benchlib import spec
    family = spec.family

    def halved(config):
        fam = family(config)

        def apply_fn(model):
            apply = fam.apply_fn(model)

            def half(params, x, t, *y):
                b = x.shape[0]
                h = max(1, -(-b // 2))
                eps = apply(params, x[:h], t[:h], *(v[:h] for v in y))
                return jnp.concatenate([eps, eps[:b - h]])
            return half
        return types.SimpleNamespace(**dict(vars(fam), apply_fn=apply_fn))
    monkeypatch.setattr(spec, "family", halved)
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
