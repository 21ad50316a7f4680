"""The plain reference against the program, and against what
serve_multitenant serves."""

import json
import os

import numpy as np
import pytest

import jax

from bench import check, reference, rf
from bench.run import run_cell

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "small-mixed.json")


@pytest.fixture(scope="module")
def conf():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rf_one(conf):
    return rf.make_pool(conf["pipeline"], conf["rf"], [2 ** 35 + 11])[0]


@pytest.mark.parametrize("variant", ["dynamic", "cnn", "sparse"])
@pytest.mark.parametrize("modality", ["bmode", "doppler", "power_doppler"])
def test_reference_matches_each_formulation(conf, rf_one, modality,
                                            variant):
    from repro.core.config import Modality, UltrasoundConfig, Variant
    from repro.core.pipeline import init_pipeline, monolithic_pipeline_fn
    g = {k: v for k, v in conf["pipeline"].items() if k != "variant"}
    cfg = UltrasoundConfig(**g, variant=Variant(variant),
                           modality=Modality(modality))
    got = np.asarray(jax.jit(monolithic_pipeline_fn(cfg))(
        init_pipeline(cfg), rf_one))
    want = reference.Reference(conf["pipeline"])(modality, rf_one)
    nums = check.image_numbers(modality, got, want, cfg.smooth_kernel)
    limit = conf["limits"].get(modality, {"mean_abs": 1e-5})["mean_abs"]
    assert nums["mean_abs"] < limit, nums


def test_reference_agrees_with_served_images(small_cell, cpu):
    cell = small_cell("paper-mixed.fleet", {
        "offered_acq_per_s": 400.0, "window_acq_per_s": 400.0,
        "phase_share": 0.25})
    res = run_cell(cell, 3, 0.1, False, devices=cpu, log=lambda s: None)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 48
    assert set(res["checks"]) == {"missing_images", "bmode.mean_abs",
                                  "doppler.mean_abs", "doppler.max_abs"}
