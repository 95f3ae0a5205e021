"""The probe finds the finisher's arguments by name: a program that adds
an argument to its finisher keeps the window count, the finisher-shape
record and the warm-up's stepping of no lane."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from benchlib.cell import Probe


def _stepped(x, pos, end, valid):
    return jnp.where(valid, end - pos, 0).sum() + 0 * x.sum()


def _finish_with_labels_before_valid(stack, menu, x, pos, end, traj, keys,
                                     y, valid):
    return _stepped(x, pos, end, valid)


def _finish_with_a_keyword_after_valid(stack, menu, x, pos, end, traj,
                                       keys, valid, *, y=None):
    return _stepped(x, pos, end, valid)


@pytest.mark.parametrize("finish", [_finish_with_labels_before_valid,
                                    _finish_with_a_keyword_after_valid])
def test_probe_binds_the_finisher_by_name(finish):
    eng = types.SimpleNamespace(
        _tick=jax.jit(lambda state, params, menu: state + 1),
        _finish=jax.jit(finish))
    probe = Probe(eng)
    lanes = 4
    x = jnp.zeros((1, lanes, 2, 2, 1))
    pos, end = jnp.zeros((1, lanes), jnp.int32), jnp.full((1, lanes), 3)
    traj = keys = jnp.zeros((1, lanes), jnp.int32)
    y, valid = jnp.zeros((1, lanes), jnp.int32), np.ones((1, lanes), bool)

    def call():
        if finish is _finish_with_labels_before_valid:
            return int(eng._finish(None, None, x, pos, end, traj, keys, y,
                                   valid))
        return int(eng._finish(None, None, x, pos, end, traj, keys,
                               valid=valid, y=y))

    probe.warming = True
    assert call() == 0
    probe.warming = False
    assert call() == 3 * lanes
    state = jnp.zeros(())
    for _ in range(3):
        state = eng._tick(state, None, None)
    assert probe.windows == 3 and int(state) == 3
    assert probe.finish_shapes == {(1, lanes)}
