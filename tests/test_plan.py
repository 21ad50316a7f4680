"""Planner (repro.core.plan) + two-tier consts cache (repro.core.pipeline).

Contracts under test:
  * Variant.AUTO resolves deterministically under heuristic/autotune and
    is refused by fixed;
  * autotune picks the argmin of its measured timings, memoizes per
    (config-sans-variant, backend), and honors injected probes;
  * all three policies produce images allclose to the monolithic oracle;
  * repeated init_pipeline for one config hash recomputes nothing (memory
    tier) and the disk tier round-trips constants bit-exactly;
  * the resolved plan is stamped into bench + streaming telemetry.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.bench import bench_callable
from repro.core import (CONSTS_CACHE_STATS, Modality, UltrasoundPipeline,
                        Variant, clear_consts_cache, config_hash,
                        init_pipeline, monolithic_pipeline_fn, plan_pipeline,
                        paper_config, set_consts_cache_dir, tiny_config)
from repro.core import plan as plan_lib
from repro.data import synth_rf
from repro.launch.scheduler import BatchPolicy, StreamSpec, serve_multitenant


@pytest.fixture(autouse=True)
def _fresh_planner_state():
    plan_lib.clear_autotune_memo()
    yield
    plan_lib.clear_autotune_memo()


# ---------------------------------------------------------------------------
# config hash
# ---------------------------------------------------------------------------


def test_config_hash_stable_and_sensitive():
    cfg = tiny_config()
    assert config_hash(cfg) == config_hash(tiny_config())
    assert config_hash(cfg) != config_hash(cfg.with_(variant=Variant.SPARSE))
    # exclude: the autotune memo key ignores the axis it searches over
    a = config_hash(cfg.with_(variant=Variant.CNN), exclude=("variant",))
    b = config_hash(cfg.with_(variant=Variant.DYNAMIC), exclude=("variant",))
    assert a == b
    with pytest.raises(KeyError):
        config_hash(cfg, exclude=("not_a_field",))


# ---------------------------------------------------------------------------
# plan policies
# ---------------------------------------------------------------------------


def test_fixed_policy_honors_variant_and_refuses_auto():
    cfg = tiny_config(variant=Variant.SPARSE)
    plan = plan_pipeline(cfg, policy="fixed")
    assert plan.variant == Variant.SPARSE
    assert plan.policy == "fixed"
    assert plan.backend == jax.default_backend()
    with pytest.raises(ValueError, match="fixed"):
        plan_pipeline(cfg.with_(variant=Variant.AUTO), policy="fixed")
    with pytest.raises(ValueError, match="policy"):
        plan_pipeline(cfg, policy="oracle")


def test_heuristic_auto_resolves_deterministically():
    cfg = tiny_config(variant=Variant.AUTO)
    p1 = plan_pipeline(cfg, policy="heuristic")
    p2 = plan_pipeline(cfg, policy="heuristic")
    assert p1 == p2
    assert p1.variant.concrete
    # this container is the gather-friendly CPU stand-in (paper GPU rows)
    assert p1.backend == "cpu"
    assert p1.variant == plan_lib.BACKEND_VARIANT_PREFERENCE["cpu"]
    assert p1.variant == Variant.DYNAMIC
    # explicit concrete variant wins over the registry under every policy
    p3 = plan_pipeline(tiny_config(variant=Variant.CNN), policy="heuristic")
    assert p3.variant == Variant.CNN and "explicit" in p3.provenance


def test_autotune_picks_argmin_of_injected_timings_and_memoizes():
    calls = []

    def fake_measure(cfg, variant, *, runs, warmup):
        calls.append(variant)
        return {Variant.DYNAMIC: 3.0, Variant.CNN: 1.0,
                Variant.SPARSE: 2.0}[variant]

    cfg = tiny_config(variant=Variant.AUTO)
    plan = plan_pipeline(cfg, policy="autotune", measure=fake_measure)
    assert plan.variant == Variant.CNN
    assert len(calls) == 3
    assert dict(plan.autotune_t_s) == {"dynamic": 3.0, "cnn": 1.0,
                                       "sparse": 2.0}
    # memoized: same config modulo variant, same backend -> no re-timing
    plan2 = plan_pipeline(cfg, policy="autotune", measure=fake_measure)
    assert plan2 == plan and len(calls) == 3
    # a geometry change invalidates the memo
    plan_pipeline(cfg.with_(nx=8), policy="autotune", measure=fake_measure)
    assert len(calls) == 6
    # so do different probe settings (2-run timings must not answer a
    # 5-run request)
    plan_pipeline(cfg, policy="autotune", measure=fake_measure,
                  autotune_runs=5)
    assert len(calls) == 9


def test_fused_block_autotune_probes_only_tiles_the_backend_admits(
        monkeypatch):
    """On the TPU the fused lowering takes whole 128-lane pixel tiles
    only: autotune must not probe (or pick) a block the predicate, and so
    resolve_fused, would refuse — even when that block times fastest."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    probed = []

    def measure_stage(c, stage, *, runs, warmup):
        probed.append(c.fusion_block)
        return c.fusion_block / 1000.0          # smaller block = faster

    cfg = paper_config(variant=Variant.DYNAMIC, fusion="fused",
                       n_c=8, n_f=8)
    plan = plan_pipeline(cfg, policy="autotune", measure_stage=measure_stage)
    assert probed == [128, 256]
    assert plan.backend == "tpu" and plan.fusion_block == 128
    assert [k for k, _ in plan.lowering_t_s] == [
        f"{plan.fusion_group}:pallas@bp128",
        f"{plan.fusion_group}:pallas@bp256"]


def test_autotune_real_timings_on_cpu_pick_fastest_variant():
    """Acceptance: autotune's pick IS the best measured fixed variant."""
    cfg = tiny_config(variant=Variant.AUTO)
    plan = plan_pipeline(cfg, policy="autotune",
                         autotune_runs=2, autotune_warmup=1)
    timings = dict(plan.autotune_t_s)
    assert set(timings) == {"dynamic", "cnn", "sparse"}
    assert all(t > 0 for t in timings.values())
    assert plan.variant.value == min(timings, key=timings.get)


@pytest.mark.parametrize("policy", ["fixed", "heuristic", "autotune"])
def test_all_policies_allclose_to_monolithic_oracle(policy):
    base = tiny_config(n_f=8, modality=Modality.DOPPLER)
    cfg = base if policy == "fixed" else base.with_(variant=Variant.AUTO)
    measure = (lambda c, v, *, runs, warmup:
               {Variant.DYNAMIC: 1.0, Variant.CNN: 2.0,
                Variant.SPARSE: 3.0}[v])
    # The stage probe is pinned too: left to wall time, the lowering
    # choice (and so the summation order under test) would vary per run.
    measure_stage = (lambda c, s, *, runs, warmup:
                     {"xla": 1.0, "pallas": 2.0}[dict(c.stage_lowerings)[s]])
    plan = plan_pipeline(cfg, policy=policy, measure=measure,
                         measure_stage=measure_stage)
    pipe = UltrasoundPipeline(cfg, plan=plan)
    assert pipe.cfg.variant.concrete

    rf = jnp.asarray(synth_rf(pipe.cfg, seed=0))
    mono = jax.jit(monolithic_pipeline_fn(pipe.cfg))
    np.testing.assert_allclose(
        np.asarray(pipe(rf)), np.asarray(mono(pipe.consts, rf)),
        rtol=1e-5, atol=1e-6)


def test_auto_image_allclose_to_every_fixed_variant():
    """Acceptance: the planner changes speed, never the image."""
    cfg = tiny_config(n_f=8)
    auto = UltrasoundPipeline(cfg.with_(variant=Variant.AUTO),
                              policy="heuristic")
    rf = jnp.asarray(synth_rf(cfg, seed=1))
    img = np.asarray(auto(rf))
    for v in [Variant.DYNAMIC, Variant.CNN, Variant.SPARSE]:
        fixed = UltrasoundPipeline(cfg.with_(variant=v))
        np.testing.assert_allclose(
            img, np.asarray(fixed(rf)), rtol=1e-4, atol=1e-4,
            err_msg=f"AUTO image diverged from fixed {v.value}")


def test_pipeline_rejects_conflicting_plan_and_policy():
    cfg = tiny_config()
    plan = plan_pipeline(cfg, policy="fixed")
    with pytest.raises(ValueError, match="policy"):
        UltrasoundPipeline(cfg, plan=plan, policy="heuristic")
    # matching policy is redundant but legal
    assert UltrasoundPipeline(cfg, plan=plan, policy="fixed").plan is plan


def test_pipeline_rejects_plan_for_different_geometry():
    plan = plan_pipeline(tiny_config(), policy="fixed")
    with pytest.raises(ValueError, match="geometry"):
        UltrasoundPipeline(tiny_config(nx=8), plan=plan)
    # a plan built on an AUTO config matches the cfg it resolves
    cfg = tiny_config(variant=Variant.AUTO)
    auto_plan = plan_pipeline(cfg, policy="heuristic")
    assert auto_plan.matches(cfg)
    assert auto_plan.matches(auto_plan.concretize(cfg))


def test_pipeline_rejects_plan_conflicting_with_explicit_variant():
    cfg = tiny_config(variant=Variant.AUTO)
    plan = plan_pipeline(cfg, policy="heuristic")    # resolves DYNAMIC
    assert plan.variant == Variant.DYNAMIC
    with pytest.raises(ValueError, match="explicit"):
        UltrasoundPipeline(tiny_config(variant=Variant.SPARSE), plan=plan)
    # the AUTO config and the plan-resolved config both remain valid
    assert UltrasoundPipeline(cfg, plan=plan).cfg.variant == Variant.DYNAMIC


def test_explicit_exec_map_wins_over_plan_and_is_restamped():
    """An explicit cfg.exec_map (e.g. "map" to bound peak memory) must not
    be reverted by a plan recorded under a different mapping, and the
    telemetry stamp must reflect what actually runs."""
    from repro.core import BatchedExecutor
    cfg = tiny_config()                        # exec_map="vmap"
    plan = plan_pipeline(cfg, policy="fixed")
    eng = BatchedExecutor(cfg.with_(exec_map="map"), plan=plan)
    assert eng.cfg.exec_map == "map"
    assert eng.plan.exec_map == "map"
    assert eng.plan.variant == plan.variant    # rest of the plan survives


def test_auto_without_plan_resolves_via_heuristic():
    pipe = UltrasoundPipeline(tiny_config(variant=Variant.AUTO))
    assert pipe.plan.policy == "heuristic"
    assert pipe.cfg.variant.concrete
    assert pipe.jitted is pipe._fn          # public handle, same object


def test_init_pipeline_refuses_auto():
    with pytest.raises(ValueError, match="AUTO"):
        init_pipeline(tiny_config(variant=Variant.AUTO))


# ---------------------------------------------------------------------------
# consts cache
# ---------------------------------------------------------------------------


def test_consts_cache_memory_tier_skips_recompute():
    cfg = tiny_config(variant=Variant.CNN, nx=12)      # unique geometry
    clear_consts_cache()
    CONSTS_CACHE_STATS.reset()
    a = init_pipeline(cfg)
    assert CONSTS_CACHE_STATS.misses == 1
    b = init_pipeline(cfg)
    assert CONSTS_CACHE_STATS.misses == 1              # zero recomputation
    assert CONSTS_CACHE_STATS.mem_hits == 1
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    # returned dicts are caller-owned copies
    a.clear()
    assert set(init_pipeline(cfg)) == set(b)


def test_consts_cache_disk_tier_roundtrips_bit_exact(tmp_path):
    from repro.core import consts_cache_dir
    cfg = tiny_config(variant=Variant.SPARSE, nz=20)   # unique geometry
    prev = consts_cache_dir()
    set_consts_cache_dir(str(tmp_path))
    try:
        clear_consts_cache()
        CONSTS_CACHE_STATS.reset()
        fresh = init_pipeline(cfg)
        assert CONSTS_CACHE_STATS.misses == 1
        assert any(p.suffix == ".npz" for p in tmp_path.iterdir())

        clear_consts_cache(memory=True)                # simulate restart
        cached = init_pipeline(cfg)
        assert CONSTS_CACHE_STATS.disk_hits == 1
        assert CONSTS_CACHE_STATS.misses == 1          # no recompute
        assert set(cached) == set(fresh)
        for k in fresh:
            assert cached[k].dtype == fresh[k].dtype
            np.testing.assert_array_equal(cached[k], fresh[k])
    finally:
        set_consts_cache_dir(prev)


def test_consts_cache_shared_across_exec_map_and_read_only():
    cfg = tiny_config(variant=Variant.DYNAMIC, nz=28)  # unique geometry
    clear_consts_cache()
    CONSTS_CACHE_STATS.reset()
    a = init_pipeline(cfg)
    b = init_pipeline(cfg.with_(exec_map="map"))       # same constants
    assert CONSTS_CACHE_STATS.misses == 1
    assert CONSTS_CACHE_STATS.mem_hits == 1
    # cached buffers are shared across consumers -> mutation is refused
    with pytest.raises(ValueError):
        a["idx"][0] = 0
    assert b["idx"] is a["idx"]


def test_consts_cache_disabled_paths():
    cfg = tiny_config(variant=Variant.DYNAMIC, nx=20)
    clear_consts_cache()
    CONSTS_CACHE_STATS.reset()
    init_pipeline(cfg, cache=False)
    init_pipeline(cfg, cache=False)
    assert CONSTS_CACHE_STATS.misses == 0              # bypass counts nothing
    assert CONSTS_CACHE_STATS.mem_hits == 0


# ---------------------------------------------------------------------------
# plan-stamped telemetry
# ---------------------------------------------------------------------------


def test_bench_result_carries_plan_in_every_ndjson_row():
    cfg = tiny_config()
    pipe = UltrasoundPipeline(cfg)
    rf = jnp.asarray(synth_rf(cfg, seed=0))
    res = bench_callable("t", None, (pipe.consts, rf),
                         input_bytes=cfg.input_bytes, warmup=1, runs=3,
                         deadline_s=1.0, jitted=pipe.jitted, plan=pipe.plan)
    from repro.bench import bench_stages
    res.stage_breakdown = bench_stages(cfg, rf, runs=2)

    assert res.plan["variant"] == cfg.variant.value
    assert res.plan["backend"] == jax.default_backend()
    recs = [json.loads(line) for line in res.ndjson_lines()]
    assert {r["kind"] for r in recs} == {"summary", "sample", "stage"}
    for r in recs:
        assert r["plan"]["policy"] == "fixed"
        assert r["plan"]["variant"] == cfg.variant.value


def test_streaming_stats_carry_resolved_plan():
    cfg = tiny_config(variant=Variant.AUTO)
    stats = serve_multitenant(
        [StreamSpec("probe0", cfg, fps=1e9, n_frames=6)],
        policy=BatchPolicy(max_batch=2), in_flight=1,
        plan_policy="heuristic")
    (group,) = stats["groups"].values()
    plan = group["plan"]
    assert plan["policy"] == "heuristic"
    assert Variant(plan["variant"]).concrete
    assert plan["exec_map"] == "vmap"
    # the stream's stamp names the resolved variant, never "auto"
    assert stats["per_stream"]["probe0"]["variant"] == plan["variant"]
