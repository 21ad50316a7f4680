"""The comparison fails a lower precision in the program's place, and a
run whose timed path is broken underneath."""

import numpy as np
import pytest

from bench import control
from bench.run import run_cell

SEED = 4


def test_sound_run_is_correct(small_cell, cpu):
    res = run_cell(small_cell(), SEED, 0.1, False, devices=cpu,
                   log=lambda s: None)
    assert res["correct"], res["checks"]


def test_three_pass_reference_in_the_programs_place_is_not_correct(
        small_cell, cpu):
    res = control.control_run(small_cell(), SEED, 0.1, cpu)
    assert not res["correct"], res["checks"]
    failing = [k for k, c in res["checks"].items()
               if c["value"] > c["limit"]]
    assert "bmode.mean_abs" in failing and "doppler.mean_abs" in failing


# Faults act on the host copy of the output: a jnp edit would compile
# inside the window, which the run refuses before it compares anything.
def _alter_first_image(out):
    out = np.array(out)
    out[0] += 0.05
    return out


def _swap_first_two(out):
    out = np.array(out)
    return out[[1, 0] + list(range(2, out.shape[0]))]


def _drop_half_the_batch(out):
    out = np.array(out)
    out[out.shape[0] // 2:] = 0.0
    return out


@pytest.mark.parametrize("fault", [_alter_first_image, _swap_first_two,
                                   _drop_half_the_batch],
                         ids=["answer-altered", "answers-reordered",
                              "half-batch-left-out"])
def test_broken_timed_path_is_not_correct(small_cell, cpu, fault):
    def hook(engine):
        launch = engine.dispatch_staged
        engine.dispatch_staged = (
            lambda dev, pad_to: fault(launch(dev, pad_to)))
    res = run_cell(small_cell(), SEED, 0.1, False, devices=cpu,
                   engine_hook=hook, log=lambda s: None)
    assert not res["correct"], res["checks"]
