"""Stage-graph engine: graph composition == legacy monolith, batched ==
per-frame, for every variant x modality (the refactor's contract), and
each stage under its own named scope in the traced program."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (BatchedExecutor, Modality, UltrasoundPipeline,
                        Variant, build_graph, init_graph_consts,
                        init_pipeline, monolithic_pipeline_fn, tiny_config)
from repro.core.stages import HEADS, graph_fn
from repro.data import synth_rf

# AUTO is a planner placeholder, not an executable variant (test_plan.py)
COMBOS = [(v, m) for v in Variant if v.concrete for m in Modality]


@pytest.mark.parametrize(
    "variant,modality", COMBOS,
    ids=[f"{v.value}-{m.value}" for v, m in COMBOS])
def test_graph_engine_contract(variant, modality):
    """One compile set per combo checks both refactor invariants."""
    cfg = tiny_config(n_f=8, variant=variant, modality=modality)
    rf_b = jnp.stack([jnp.asarray(synth_rf(cfg, seed=s)) for s in range(2)])

    pipe = UltrasoundPipeline(cfg)
    per_frame = np.stack([np.asarray(pipe(rf_b[i])) for i in range(2)])

    # 1. stage-graph composition reproduces the legacy monolithic fn
    mono = jax.jit(monolithic_pipeline_fn(cfg))
    ref = np.asarray(mono(pipe.consts, rf_b[0]))
    np.testing.assert_allclose(per_frame[0], ref, rtol=1e-5, atol=1e-6)

    # 2. batched executor == per-frame execution (donate=False: rf_b is
    # reused above, and on accelerator backends donation would free it)
    batched = np.asarray(BatchedExecutor(cfg, donate=False)(rf_b))
    np.testing.assert_allclose(batched, per_frame, rtol=1e-5, atol=1e-5)


def test_exec_map_sequential_matches_vmap():
    """lax.map execution path == vmap path (fusion-order float noise only)."""
    cfg = tiny_config(n_f=8, modality=Modality.DOPPLER)
    rf_b = jnp.stack([jnp.asarray(synth_rf(cfg, seed=s)) for s in range(3)])
    a = np.asarray(BatchedExecutor(cfg, donate=False)(rf_b))
    b = np.asarray(BatchedExecutor(cfg.with_(exec_map="map"),
                                   donate=False)(rf_b))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_exec_map_unknown_rejected():
    with pytest.raises(ValueError):
        BatchedExecutor(tiny_config(exec_map="pmap"))


def test_graph_consts_match_legacy_init():
    """Per-stage const init merges to exactly the legacy pipeline consts."""
    for modality in Modality:
        cfg = tiny_config(modality=modality)
        legacy = init_pipeline(cfg)
        graph = init_graph_consts(cfg)
        assert set(legacy) == set(graph)
        for k in legacy:
            np.testing.assert_array_equal(legacy[k], graph[k])


def test_graph_order_and_stage_composition():
    cfg = tiny_config(n_f=8, modality=Modality.POWER_DOPPLER)
    names = [s.name for s in build_graph(cfg)]
    assert names == ["demod", "beamform", "power_doppler"]

    pipe = UltrasoundPipeline(cfg)
    rf = jnp.asarray(synth_rf(cfg, seed=3))
    x = rf
    for _, fn in pipe.stage_callables().items():
        x = fn(pipe.consts, x)
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(pipe(rf)), rtol=1e-5, atol=1e-6)


MODALITY_IDS = [m.value for m in Modality]


def _hlo(fn, cfg, *, debug_info):
    """HLO text of ``fn`` lowered for one RF acquisition of ``cfg``."""
    rf = jax.ShapeDtypeStruct(cfg.rf_shape, cfg.rf_dtype)
    return jax.jit(fn).lower(init_pipeline(cfg), rf).as_text(
        dialect="hlo", debug_info=debug_info)


def _scopes(hlo):
    """The scope right under the jitted root of every op's op_name."""
    return set(re.findall(r'op_name="jit\(\w+\)/([^/"]+)/', hlo))


@pytest.mark.parametrize("modality", list(Modality), ids=MODALITY_IDS)
def test_graph_fn_names_each_stage_in_op_metadata(modality):
    """Every traced operation sits under its stage's named scope, which
    is what a device trace shows as the op's ``op_name``."""
    cfg = tiny_config(n_f=8, modality=modality)
    assert _scopes(_hlo(graph_fn(cfg), cfg, debug_info=True)) == {
        "demod", "beamform", HEADS[modality].name}


@pytest.mark.parametrize("modality", list(Modality), ids=MODALITY_IDS)
def test_stage_scopes_change_only_metadata(modality):
    """The scoped graph lowers to the same program as the plain
    composition of its stages, once debug metadata is left out."""
    cfg = tiny_config(n_f=8, modality=modality)
    stages = build_graph(cfg)

    def run(consts, rf):
        x = rf
        for stage in stages:
            x = stage.apply(cfg, consts, x)
        return x

    names = {stage.name for stage in stages}
    assert not _scopes(_hlo(run, cfg, debug_info=True)) & names
    assert (_hlo(graph_fn(cfg), cfg, debug_info=False)
            == _hlo(run, cfg, debug_info=False))


def test_fused_span_runs_under_its_group_scope():
    """A fused span is one scope, named by its fusion group."""
    cfg = tiny_config(modality=Modality.BMODE, variant=Variant.DYNAMIC,
                      fusion="fused")
    assert "demod+beamform+bmode" in _scopes(
        _hlo(graph_fn(cfg), cfg, debug_info=True))
