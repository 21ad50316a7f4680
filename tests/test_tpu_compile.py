"""Ahead-of-time compiles of the TPU plan's kernels for a described v5e.

Interpret mode cannot show what Mosaic refuses (unaligned slices,
dynamic lane indexing, VMEM overruns), so these tests compile each
kernel of the served path with ``interpret=False`` at the paper's
acquisition geometry for a ``v5e:2x2`` topology that is described, not
attached — the kernels the executor runs, shaped as it runs them
(vmapped over the acquisition batch). Nothing executes.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import lowering
from repro.core.config import Modality, Variant, paper_config

BATCH = 4          # the serving pad shape of chip_smoke.py
BSR_K = 2          # blocks per row bsr_operator stores at paper geometry


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-device compile is written to the persistent cache
    but can never be read back without the chip — keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_das_beamform_compiles_at_paper_geometry(one_chip,
                                                 no_compile_cache,
                                                 precision):
    from repro.kernels.das_beamform import das_beamform
    cfg = paper_config()
    p, c = cfg.n_pix, cfg.n_c

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(idx, frac, apod, rot, iq):
        return jax.vmap(
            lambda x: das_beamform(idx, frac, apod, rot, x,
                                   precision=precision,
                                   interpret=False))(iq)

    _compile(fn, s((p, c), jnp.int32), s((p, c)), s((p, c)),
             s((p, c, 2)), s((BATCH, cfg.n_s, c, cfg.n_f, 2)))


def test_bsr_beamform_compiles_at_paper_geometry(one_chip,
                                                 no_compile_cache):
    from repro.kernels.bsr_spmm import bsr_beamform
    from repro.kernels.pallas_compat import block_sample_axis
    cfg = paper_config()
    bp, bs = cfg.sparse_block_p, cfg.sparse_block_s
    n_pb = -(-cfg.n_pix // bp)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(cols, blocks, iq):
        return jax.vmap(lambda x: bsr_beamform(
            cols, blocks, block_sample_axis(x, bs), interpret=False))(iq)

    _compile(fn, s((cfg.n_c, n_pb, BSR_K), jnp.int32),
             s((cfg.n_c, n_pb, BSR_K, bp, 2 * bs)),
             s((BATCH, cfg.n_s, cfg.n_c, cfg.n_f, 2)))


@pytest.mark.parametrize("modality", [Modality.BMODE,
                                      Modality.POWER_DOPPLER],
                         ids=["bmode", "power_doppler"])
def test_fused_kernel_refused_at_paper_geometry_compiles_below(
        one_chip, no_compile_cache, modality):
    """At the paper geometry the fused megakernel's resident RF block
    and IQ cube exceed the VMEM budget, so the TPU predicate refuses it
    (and `resolve_fused` says so); at a geometry the predicate admits,
    the compiled kernel builds."""
    from repro.kernels import fused_pipeline as fp
    paper = paper_config(variant=Variant.DYNAMIC, modality=modality,
                         fusion="fused")
    assert not lowering.fused_supported(paper, "tpu")
    with pytest.raises(ValueError, match="capability predicate"):
        lowering.resolve_fused(paper, "tpu")

    cfg = paper.with_(n_c=8, n_f=8)
    assert lowering.fused_supported(cfg, "tpu")
    p, c = cfg.n_pix, cfg.n_c

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tables = (s((cfg.n_l, 2)), s((cfg.lpf_taps,)), s((p, c), jnp.int32),
              s((p, c)), s((p, c)), s((p, c, 2)))
    rf = s(cfg.rf_shape, jnp.int16)
    if modality == Modality.BMODE:
        _compile(lambda *a: fp.fused_rf_to_envelope(
            *a, decim=cfg.decim, interpret=False), *tables, rf)
    else:
        _compile(lambda *a: fp.fused_rf_to_power(
            *a, decim=cfg.decim, interpret=False),
            *tables, s((cfg.wall_filter_taps,)), rf)
