"""A configuration without the steering keys describes one 0-degree wave
per frame: the RF and the reference images it gives are those recorded
before steering existed, bit for bit
(bench/tests/fixtures/zero-degree-outputs.json), and so is its work
count."""

import hashlib
import json
import os

import numpy as np
import pytest

from bench import reference, rf, work

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
with open(os.path.join(HERE, "fixtures", "zero-degree-outputs.json")) as f:
    RECORDED = json.load(f)


def load(name):
    path = (os.path.join(HERE, "fixtures", name + ".json")
            if name.startswith("small") else
            os.path.join(CONFIGS, name + ".json"))
    with open(path) as f:
        return json.load(f)


def digest(a):
    a = np.ascontiguousarray(a)
    return {"shape": list(a.shape), "dtype": str(a.dtype),
            "sha256": hashlib.sha256(a.tobytes()).hexdigest()}


@pytest.fixture(scope="module")
def made():
    """One pool and one reference per configuration, shared by the
    cases."""
    cache = {}

    def get(name, seed):
        if name not in cache:
            conf = load(name)
            cache[name] = (conf, reference.Reference(conf["pipeline"]), {})
        conf, ref, pools = cache[name]
        if seed not in pools:
            pools[seed] = rf.make_pool(conf["pipeline"], conf["rf"],
                                       [seed])[0]
        return pools[seed], ref
    return get


@pytest.mark.parametrize("key", sorted(RECORDED["rf"]))
def test_rf_is_bit_identical(made, key):
    name, seed = key.split("/")
    x, _ = made(name, int(seed))
    assert digest(x) == RECORDED["rf"][key]


@pytest.mark.parametrize("key", sorted(RECORDED["reference"]))
def test_reference_image_is_bit_identical(made, key):
    name, seed, modality = key.split("/")
    x, ref = made(name, int(seed))
    assert digest(ref(modality, x)) == RECORDED["reference"][key]


# The counts recorded before steering existed.
PAPER_FLOPS = {"bmode": 642_367_488, "doppler": 639_057_920,
               "power_doppler": 636_895_232}


@pytest.mark.parametrize("modality", sorted(PAPER_FLOPS))
@pytest.mark.parametrize("config", ["paper-bmode", "paper-mixed"])
def test_paper_work_count_is_unchanged(config, modality):
    assert work.flops_per_acquisition(load(config)["pipeline"],
                                      modality) == PAPER_FLOPS[modality]
