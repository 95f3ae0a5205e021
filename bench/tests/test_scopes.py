"""Device time by layer scope, host self time by program span and idle
gaps named by program spans: the op_name map of a compiled program, a
hand-made trace, and slices recorded on a v5e."""
import gzip
import json
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from benchlib import scopes, trace

DATA = Path(__file__).resolve().parent / "data"


def _hand_made():
    ops = [["%fusion.1 = f32[2] fusion(..)", 0, 50],
           ["%fusion.2 = f32[2] fusion(..)", 50, 10],
           ["%rng.3 = u32[2] fusion(..)", 60, 5],
           ['%b.4 = f32[2] custom-call(..), '
            'custom_call_target="tpu_custom_call"', 65, 5],
           ["%copy.5 = f32[2] copy(..)", 70, 10],
           ["%while.6 = (f32[2]) while(..), body=%b", 200, 100],
           ["%fusion.7 = f32[2] fusion(..)", 200, 80],
           ["%copy.8 = f32[2] copy(..)", 280, 20]]
    op_scopes = ["unet", "unet/attn", "noise", "step", "", "", "unet", None]
    mods = [["jit_window(5)", 0, 80], ["jit_finish(6)", 200, 100]]
    host = [["bench:slice", 0, 400],
            ["serve.window", 0, 180],
            ["serve.dispatch", 5, 10],
            ["serve.sync_wait", 20, 70],
            ["serve.retire", 95, 60],
            ["serve.retire_rows", 100, 40],
            ["bench:admit", 160, 20],
            ["serve.finish_drain", 180, 160],
            ["serve.client_finish_sync", 300, 40],
            ["serve.finish_wait", 300, 5]]
    return {"devices": [{"name": "/device:TPU:0",
                         "lines": {"XLA Ops": ops, "XLA Modules": mods},
                         "op_scopes": op_scopes}],
            "host": host}


def test_scope_path():
    assert scopes.scope_path(
        "jit(window)/while/body/closed_call/unet/attn/dot_general") == \
        "unet/attn"
    assert scopes.scope_path("jit(finish)/vmap(per_client)/while/body/"
                             "noise/threefry2x32") == "noise"
    assert scopes.scope_path("jit(window)/while/body/add") == ""
    # a path component, not a substring
    assert scopes.scope_path("jit(f)/unet_like/mul") == ""


def test_op_names_of_a_compiled_program():
    """Each instruction of a compiled program maps to its op_name, and so
    to the layer scopes around the code that made it."""
    import jax
    import jax.numpy as jnp

    def tick(x):
        with jax.named_scope("unet"):
            with jax.named_scope("attn"):
                y = jnp.tanh(x @ x)
            y = y * 2.0
        with jax.named_scope("noise"):
            z = jax.random.normal(jax.random.PRNGKey(0), x.shape)
        with jax.named_scope("step"):
            return y + z
    text = jax.jit(tick).lower(jnp.ones((8, 8))).compile().as_text()
    names = scopes.op_names(text)
    found = {scopes.scope_path(op) for op in names.values()}
    assert {"unet/attn", "noise", "step"} <= found
    dots = [i for i, op in names.items() if op.endswith("/dot_general")]
    assert dots and all(scopes.scope_path(names[i]) == "unet/attn"
                        for i in dots)
    assert all(isinstance(op, str) for op in names.values())


def test_each_run_takes_the_text_that_names_its_ops():
    """A program compiled at two shapes has two texts; each run of it in
    the profile takes the one that names its instructions."""
    ops = [["%fusion.1 = f32[4] fusion(..)", 0, 10],
           ["%copy.2 = f32[4] copy(..)", 10, 5],
           ["%fusion.1 = f32[8] fusion(..)", 100, 10],
           ["%fusion.9 = f32[8] fusion(..)", 110, 5],
           ["%add.3 = f32[2] add(..)", 200, 5]]
    mods = [(0, 20, "jit_finish(1)"), (100, 120, "jit_finish(2)"),
            (200, 210, "jit_other(3)")]
    hlo = {"jit_finish": [{"fusion.1": "jit(finish)/unet/conv",
                           "copy.2": ""},
                          {"fusion.1": "jit(finish)/noise/x",
                           "fusion.9": "jit(finish)/step/y"}]}
    assert scopes._scopes(ops, mods, hlo) == ["unet", "", "noise", "step",
                                              None]


def test_device_time_by_scope():
    r = scopes.reduce(_hand_made())
    assert r["device_s"] == {
        "jit_window": {"": pytest.approx(10e-9),
                       "noise": pytest.approx(5e-9),
                       "step": pytest.approx(5e-9),
                       "unet": pytest.approx(50e-9),
                       "unet/attn": pytest.approx(10e-9)},
        "jit_finish": {"?": pytest.approx(20e-9),
                       "unet": pytest.approx(80e-9)}}
    assert scopes.scope_time(r, "jit_window", "unet") == \
        pytest.approx(60e-9)
    assert scopes.scope_time(r, "jit_window", "unet/attn") == \
        pytest.approx(10e-9)
    assert scopes.scope_time(r, "jit_finish", "noise") is None
    assert scopes.scope_time(r, "jit_admit", "unet") is None


def test_host_self_time_and_nesting():
    r = scopes.reduce(_hand_made())
    h = r["host"]
    assert h["serve.window"]["s"] == pytest.approx(180e-9)
    # window minus dispatch, sync_wait and retire
    assert h["serve.window"]["self_s"] == pytest.approx(40e-9)
    assert h["serve.retire"]["self_s"] == pytest.approx(20e-9)
    assert h["serve.retire_rows"]["calls"] == 1
    assert h["serve.finish_drain"]["s"] == pytest.approx(160e-9)
    assert r["nested_s"]["serve.window>serve.sync_wait"] == \
        pytest.approx(70e-9)
    assert r["nested_s"]["serve.window>serve.retire_rows"] == \
        pytest.approx(40e-9)
    assert r["nested_s"]["serve.finish_drain>serve.finish_wait"] == \
        pytest.approx(5e-9)
    assert "serve.window>serve.finish_wait" not in r["nested_s"]


def test_idle_gaps_named_by_program_spans():
    r = scopes.reduce(_hand_made())
    # ops cover [0, 80] and [200, 300]; idle [80, 200] and [300, 400]
    assert r["idle_s"] == pytest.approx(220e-9)
    # [80, 180] lies in serve.window, [180, 200] and [300, 340] in
    # serve.finish_drain; [340, 400] in no program span
    assert r["idle_in_span_s"] == pytest.approx(160e-9)
    gaps = dict((round(g * 1e9), name) for name, g in r["idle_gaps"])
    # innermost spans over [80, 200]: retire_rows 40, window 30 (90-95,
    # 155-180), retire 20, drain 20, sync_wait 10
    assert gaps[120] == "host: serve.retire_rows"
    # [300, 400]: client_finish_sync holds 35, finish_wait 5, drain 0;
    # 60 of it is in no program span at all, but spans are preferred
    assert gaps[100] == "host: serve.client_finish_sync"


def test_gap_outside_program_spans_falls_back():
    t = _hand_made()
    t["host"] = [e for e in t["host"] if not e[0].startswith("serve.")]
    r = scopes.reduce(t)
    gaps = dict((round(g * 1e9), name) for name, g in r["idle_gaps"])
    assert gaps[120] == "host: admit"
    assert gaps[100] == "host: engine bookkeeping (not annotated)"
    assert r["host"] == {} and r["idle_in_span_s"] == 0.0


def test_no_device_work_reads_nothing(tmp_path):
    assert scopes.reduce({"devices": [], "host": []}) == {}
    with pytest.raises(FileNotFoundError):
        scopes.extract(tmp_path, {})


def _old_slice():
    with gzip.open(DATA / "cifar_ddim50_slice.json.gz", "rt") as f:
        return json.load(f)


def test_old_slice_reduces_byte_identically():
    """The slice recorded before the program had spans or scopes (only
    ``bench:`` host events, ops without a scope field) reduces as it was
    recorded to."""
    got = json.dumps(trace.reduce(_old_slice()), sort_keys=True)
    with open(DATA / "cifar_ddim50_slice.reduced.json") as f:
        assert got == f.read()


def test_old_slice_gaps_keep_the_harness_labels():
    old = _old_slice()
    r = scopes.reduce(old)
    assert r["host"] == {} and r["idle_in_span_s"] == 0.0
    assert set(r["device_s"]["jit_window"]) == {scopes.UNMAPPED}
    assert r["idle_gaps"] == trace.reduce(old)["idle_gaps"]


def test_recorded_scoped_slice():
    """A slice recorded on a v5e with the program's tracer on
    (``paper_unet.ddim20_job``): the last three scan windows of a job and
    the first 60 ms of its finish wave, its ops mapped to scopes through
    the programs' compiled text."""
    with gzip.open(DATA / "paper_ddim20_scoped_slice.json.gz", "rt") as f:
        recorded = json.load(f)
    r = scopes.reduce(recorded)
    w = r["device_s"]["jit_window"]
    assert w["unet"] == pytest.approx(0.429543794)
    assert w["unet/attn"] == pytest.approx(0.018087327)
    assert w["noise"] == pytest.approx(5.0731e-05)
    assert w["step"] == pytest.approx(8.472e-05)
    # the scopes hold 98% of the window program's op time
    assert sum(v for k, v in w.items() if k) / sum(w.values()) > 0.98
    assert scopes.scope_time(r, "jit_finish", "unet") == \
        pytest.approx(0.057461758)
    h = r["host"]
    assert h["serve.window"]["calls"] == 5
    assert h["serve.sync_wait"]["s"] == pytest.approx(0.460825735)
    assert h["serve.retire"]["self_s"] == pytest.approx(0.000708)
    assert r["nested_s"]["serve.finish_drain>serve.finish_wait"] == \
        pytest.approx(0.060140973)
    # every idle gap lies under a program span and is named by one
    assert r["idle_in_span_s"] / r["idle_s"] > 0.99
    assert all(name.startswith("host: serve.") for name, _ in r["idle_gaps"])
    assert r["idle_gaps"][0] == ["host: serve.client_finish_dispatch",
                                 pytest.approx(0.006646543)]
    # the harness's reduction reads the same slice as before
    assert trace.reduce(recorded)["module_s"]["jit_finish"] == \
        pytest.approx(0.06)
