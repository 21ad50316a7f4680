"""Ahead-of-time compiles of the TPU plan's kernels for a described v5e.

Interpret mode cannot show what Mosaic refuses (unaligned slices,
dynamic lane indexing, VMEM overruns), so these tests compile each
kernel of the served path with ``interpret=False`` at the paper's
acquisition geometry for a ``v5e:2x2`` topology that is described, not
attached — the kernels the executor runs, shaped as it runs them
(vmapped over the acquisition batch). Nothing executes.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import lowering, stages
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


def _compile_served_cnn(sharding, modality):
    """The served CNN graph at the paper geometry, vmapped at BATCH as
    the executor runs it, compiled from shapes alone."""
    cfg = paper_config(variant=Variant.CNN, modality=modality)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    consts = {k: s(v.shape, v.dtype) for stage in stages.build_graph(cfg)
              if stage is not stages.BEAMFORM
              for k, v in stage.init_consts(cfg).items()}
    half = s((cfg.n_c, -(-cfg.n_s // 8) * 8, cfg.n_pix))
    consts.update(interp_re=half, interp_im=half)
    fn = jax.vmap(stages.graph_fn(cfg), in_axes=(None, 0))
    return cfg, jax.jit(fn).lower(
        consts, s((BATCH,) + cfg.rf_shape, jnp.int16)).compile()


@pytest.fixture(scope="module")
def served_cnn(one_chip, no_compile_cache):
    """(cfg, compiled) of the served CNN graph per modality, compiled
    once for the module (about 45 s each on the CPU)."""
    done = {}

    def get(modality):
        if modality not in done:
            done[modality] = _compile_served_cnn(one_chip, modality)
        return done[modality]
    return get


def _scoped(text, scope):
    return [line for line in text.splitlines()
            if re.search(rf'op_name="[^"]*{scope}', line)]


def test_served_cnn_demod_has_no_strided_slice(served_cnn):
    """The served CNN B-mode graph reads the FIR taps as unit-stride
    windows: on the TPU a strided slice of the axial axis is a gather
    across lanes, one unfused pass per tap."""
    _, compiled = served_cnn(Modality.BMODE)
    demod_ops = _scoped(compiled.as_text(), "demod")
    assert demod_ops, "no instruction carries the demod scope"
    strided = [line.split("=")[0].strip() for line in demod_ops
               if re.search(r"= \S+ slice\(", line)
               and any(int(step) > 1 for step in re.findall(
                   r"\[\d+:\d+:(\d+)\]", line))]
    assert not strided, strided


@pytest.mark.parametrize("modality", [Modality.BMODE, Modality.DOPPLER],
                         ids=["bmode", "doppler"])
def test_served_cnn_beamform_reads_operator_as_stored(served_cnn, modality):
    """The einsums read the operator's re and im halves where they lie:
    no beamform instruction writes an operator-sized array (a per-batch
    split or relayout), no operator-sized scratch, and each half laid
    out channel, sample, pixel from major to minor. A second-minor axis
    of 334 samples would be laid out with the channels there instead,
    and the einsums would cost about 1.7 times as much."""
    cfg, compiled = served_cnn(modality)
    n_op = cfg.n_c * cfg.n_pix * cfg.n_s
    beamform_ops = _scoped(compiled.as_text(), "beamform")
    assert beamform_ops, "no instruction carries the beamform scope"
    big = []
    for line in beamform_ops:
        out = re.match(r"\s*(?:ROOT )?(\S+) = (.*?) [a-z][\w-]*\(", line)
        for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", out.group(2)):
            if np.prod([int(d) for d in dims.split(",") if d]) >= n_op:
                big.append(out.group(1))
    assert not big, big

    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < n_op * 2 * 4 / 10, temp

    consts_formats = compiled.input_formats[0][0]
    for name in ("interp_re", "interp_im"):
        assert consts_formats[name].layout.major_to_minor == (0, 1, 2), \
            (name, consts_formats[name].layout)
