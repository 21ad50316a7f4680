"""Loop-aware HLO cost parser: exact accounting on a known scanned module,
and regression vs XLA's body-counted-once behavior."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.launch import hlo_cost


@pytest.fixture(scope="module")
def scanned_module():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = lax.scan(body, x, None, length=10)
        return out

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    return jax.jit(f).lower(x, w).compile()


def test_scan_flops_multiplied_by_trip_count(scanned_module):
    cost = hlo_cost.analyze(scanned_module.as_text())
    expected = 10 * 2 * 128 ** 3
    assert abs(cost.flops - expected) / expected < 0.05, cost.flops
    assert cost.unknown_loops == 0


def test_xla_cost_analysis_counts_body_once(scanned_module):
    """The reason hlo_cost exists (documented limitation of XLA)."""
    xla_flops = scanned_module.cost_analysis().get("flops", 0.0)
    assert xla_flops < 2 * 2 * 128 ** 3   # ~one body, not ten


def test_dot_flops_from_contracting_dims():
    a = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((64, 16), jnp.float32)
    compiled = jax.jit(lambda x, y: x @ y).lower(a, b).compile()
    cost = hlo_cost.analyze(compiled.as_text())
    assert abs(cost.flops - 2 * 32 * 64 * 16) / (2 * 32 * 64 * 16) < 0.05


def test_gather_elems_counted():
    x = jax.ShapeDtypeStruct((1024, 8), jnp.float32)
    idx = jax.ShapeDtypeStruct((256,), jnp.int32)
    compiled = jax.jit(
        lambda x, i: jnp.take(x, i, axis=0)).lower(x, idx).compile()
    cost = hlo_cost.analyze(compiled.as_text())
    assert cost.gather_elems >= 256 * 8, cost.gather_elems


def test_dus_counts_window_not_buffer():
    buf = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    upd = jax.ShapeDtypeStruct((1, 1024), jnp.float32)

    def f(b, u):
        return lax.dynamic_update_slice(b, u, (jnp.int32(3), jnp.int32(0)))

    compiled = jax.jit(f).lower(buf, upd).compile()
    cost = hlo_cost.analyze(compiled.as_text())
    # The module holds one real full-buffer copy (param -> in-place dest,
    # 2 x 4 MB) plus the DUS *window* (2 x 4 KB). If the DUS result were
    # (wrongly) charged as the whole buffer the total would exceed 16 MB.
    assert cost.bytes_min < 10e6, cost.bytes_min
    assert cost.bytes_min > 8e6, cost.bytes_min
