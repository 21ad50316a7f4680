import numpy as np
import pytest

import jax


@pytest.fixture(scope="session", autouse=True)
def _sandbox_consts_cache(tmp_path_factory):
    """Keep the on-disk consts cache inside the test session.

    Tests must never read entries a previous checkout wrote to the user
    cache dir (stale constants would corrupt oracle comparisons), nor
    leave test-geometry entries behind.
    """
    from repro.core import set_consts_cache_dir
    set_consts_cache_dir(str(tmp_path_factory.mktemp("consts-cache")))
    yield


@pytest.fixture(scope="session", autouse=True)
def _sandbox_compile_cache(tmp_path_factory):
    """Keep the XLA persistent compilation cache inside the session.

    Same discipline as the consts cache: AOT warm-ups must never
    populate (or hit) the checkout's `.jax_cache` from a test run —
    the warm-start tests assert cold-vs-warm timing differences that a
    pre-populated cache would erase.
    """
    from repro.core import set_compile_cache_dir
    set_compile_cache_dir(str(tmp_path_factory.mktemp("compile-cache")))
    yield


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
