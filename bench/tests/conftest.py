import json
import os

import pytest


@pytest.fixture(scope="session", autouse=True)
def _session_caches(tmp_path_factory):
    """Keep the program's constants and compile caches inside the test
    session, never in the checkout's directories."""
    from repro.core import set_compile_cache_dir, set_consts_cache_dir
    set_consts_cache_dir(str(tmp_path_factory.mktemp("consts-cache")))
    set_compile_cache_dir(str(tmp_path_factory.mktemp("compile-cache")))
    yield


FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture
def small_cell():
    """A cell of the real BENCHMARK.json, resized to the test geometry
    (bench/tests/fixtures/small-mixed.json) and given a short traffic."""
    from bench import spec

    def make(cell="paper-mixed.backlog", traffic=None):
        c = spec.cell(spec.load_benchmark(), cell)
        with open(os.path.join(FIXTURES, "small-mixed.json")) as f:
            c.config = json.load(f)
        c.traffic = traffic or {"offered_acq_per_s": 1e6,
                                "window_acq_per_s": 40.0,
                                "phase_share": 0.25}
        return c
    return make


@pytest.fixture
def cpu():
    import jax
    return jax.devices()[:1]
