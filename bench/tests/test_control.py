"""The control: the plain reference, put in the program's place at a
precision below the configuration's, has to come out as not correct under
the limits, while the program passes them.

On a TPU the control runs at the configuration's ``control_precision``
(``high``: three bfloat16 passes).  XLA on the CPU ignores the matmul
precision, so there the control's weights are rounded to bfloat16.  At the
cells' own sizes the same readings come from ``bench/calibrate.py`` on the
chip.
"""
import jax
import jax.numpy as jnp

import tiny
from benchlib import check
from benchlib.cell import Cell


def test_control_fails_where_the_program_passes():
    c = tiny.cell(traffic="ddim20_job", schedule="linear", T=40)
    config, traffic = c["config"], c["traffic"]
    seed = 2_147_483_659
    with jax.default_matmul_precision(config["precision"]):
        cell = Cell(config, traffic, seed)
        job = cell.run_job(1)
    sample = check.draw_sample([job.outputs], seed)
    ref = check.reference_outputs(config, traffic, sample, seed,
                                  config["precision"])
    program = check.judge(check.gaps(sample, ref), c["limits"])
    assert check.passed(program), program

    on_tpu = jax.devices()[0].platform == "tpu"
    low = check.reference_outputs(
        config, traffic, sample, seed,
        config["control_precision"] if on_tpu else config["precision"],
        weights_dtype=None if on_tpu else jnp.bfloat16)
    control = check.judge(
        check.gaps(check.in_place_of_program(sample, low), ref),
        c["limits"])
    assert not check.passed(control), control
