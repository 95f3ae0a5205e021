"""The reduction from a profiler trace to busy time, program and kernel
times and idle gaps, on a hand-made trace and on a recorded one."""
import gzip
import json
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from benchlib import trace

DATA = Path(__file__).resolve().parent / "data"


def _hand_made():
    ops = [["%convolution.1 = f32[2] convolution(..)", 0, 60],
           ['%branch_0_fun.2 = f32[2] custom-call(..), '
            'custom_call_target="tpu_custom_call"', 60, 10],
           ["%while.3 = (f32[2]) while(..), body=%b", 150, 140],
           ["%fusion.7 = f32[2] fusion(..)", 150, 140],
           ["%copy.2 = f32[2] copy(..)", 380, 40]]
    mods = [["jit_window(5)", 0, 100], ["jit_finish(6)", 150, 150]]
    host = [["bench:slice", 0, 400], ["bench:admit", 100, 50],
            ["bench:dispatch_finish", 295, 30]]
    return {"devices": [{"name": "/device:TPU:0",
                         "lines": {"XLA Ops": ops, "XLA Modules": mods}}],
            "host": host}


def test_hand_made_trace():
    r = trace.reduce(_hand_made())
    assert r["window_s"] == pytest.approx(400e-9)
    # ops cover [0, 70], [150, 290] and [380, 400] (clipped to the slice)
    assert r["busy_s"] == pytest.approx(230e-9)
    assert r["module_s"] == pytest.approx({"jit_window": 100e-9,
                                           "jit_finish": 150e-9})
    assert r["kernel"] == {"jit_window": {"s": pytest.approx(10e-9),
                                          "calls": 1}}
    assert r["idle_gaps"] == [["host: dispatch_finish", pytest.approx(90e-9)],
                              ["host: admit", pytest.approx(80e-9)]]
    assert r["device_ops"][0] == ["jit_finish/fusion", pytest.approx(140e-9)]
    assert trace.module_time(r, "jit_window") == pytest.approx(100e-9)
    assert trace.module_time(r, "jit_admit") is None


def test_base_name():
    assert trace.base_name("jit_window(12)") == "jit_window"
    assert trace.base_name("fusion.123") == "fusion"
    assert trace.base_name("convolution") == "convolution"
    assert trace.base_name("%fusion.1944 = (f32[4]) fusion(f32[4] %a)") == \
        "fusion"
    assert trace.base_name('%custom-call.24 = f32[2] custom-call(..), '
                           'custom_call_target="ConcatBitcast"') == \
        "custom-call[ConcatBitcast]"


def test_no_device_work_reads_nothing():
    assert trace.reduce({"devices": [], "host": []}) == {}


def test_recorded_trace():
    """A slice recorded on a v5e (``ddpm_cifar10.ddim50_job``): the last
    three scan windows of a job and the first 60 ms of its finish wave."""
    with gzip.open(DATA / "cifar_ddim50_slice.json.gz", "rt") as f:
        recorded = json.load(f)
    r = trace.reduce(recorded)
    assert r["window_s"] == pytest.approx(0.34397336)
    assert r["busy_s"] == pytest.approx(0.246973202)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["module_s"]["jit_window"] == pytest.approx(0.186966501)
    assert r["module_s"]["jit_finish"] == pytest.approx(0.06)
    # one fused tick kernel per scan window, ~8 us each
    assert r["kernel"] == {"jit_window": {"s": pytest.approx(2.4623e-05),
                                          "calls": 3}}
    assert r["device_ops"][0] == ["jit_window/fusion{kOutput}",
                                  pytest.approx(0.151425127)]
    assert len(r["device_ops"]) == len(r["idle_gaps"]) == 10
    assert r["idle_gaps"][0] == ["host: dispatch_finish",
                                 pytest.approx(0.088092832)]
