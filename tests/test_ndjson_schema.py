"""One schema, every emitter: repro.bench.schema applied end to end.

Every NDJSON-producing path — the bench harness's summary / sample /
stage records (benchmarks/run.py) and the multi-tenant scheduler rows
(multitenant.py) — is generated here in-process at tiny geometry and
pushed through the SAME `validate_record` that CI runs against the
artifact files, so the schema cannot fork between what tests check and
what CI enforces.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.bench import bench_callable, bench_stages, write_ndjson
from repro.bench.schema import (SchemaError, validate_lines,
                                validate_ndjson, validate_record)
from repro.core import UltrasoundPipeline, Variant, tiny_config
from repro.data import synth_rf


def _tiny_cfg():
    return tiny_config(variant=Variant.DYNAMIC)


@pytest.fixture(scope="module")
def bench_result():
    """A full BenchResult exactly like a table1 row: plan stamp, latency
    distribution, stage breakdown."""
    cfg = _tiny_cfg()
    pipe = UltrasoundPipeline(cfg)
    rf = jnp.asarray(synth_rf(cfg, seed=0))
    res = bench_callable(
        f"{cfg.name}:{cfg.variant.value}", None, (pipe.consts, rf),
        input_bytes=cfg.input_bytes, warmup=1, runs=3, deadline_s=10.0,
        jitted=pipe.jitted, plan=pipe.plan)
    res.stage_breakdown = bench_stages(cfg, rf, runs=2)
    return res


def test_harness_records_validate(bench_result):
    kinds = [validate_record(json.loads(line))
             for line in bench_result.ndjson_lines()]
    assert kinds[0] == "summary"
    assert kinds.count("sample") == 3
    assert kinds.count("stage") == 3          # demod, beamform, head


def test_write_ndjson_file_validates(tmp_path, bench_result):
    path = tmp_path / "bench.ndjson"
    write_ndjson(str(path), [bench_result])
    counts = validate_ndjson(str(path))
    assert counts == {"summary": 1, "sample": 3, "stage": 3}


@pytest.fixture(scope="module")
def mt_records():
    """One real multitenant sweep (in-flight 1 and 2) for schema tests."""
    from benchmarks import multitenant
    cfg = _tiny_cfg()
    _, records = multitenant.run(
        client_counts=(2,), policies=((2, 1.0),), in_flights=(1, 2),
        fast=True, cfg_bmode=cfg)
    return records


def test_multitenant_emitter_validates(mt_records):
    assert len(mt_records) == 2
    for rec, depth in zip(mt_records, (1, 2)):
        assert validate_record(rec) == "multitenant"
        assert rec["clients"] == 2
        assert rec["in_flight"] == depth
        assert rec["warmup_s"] >= 0.0
        assert 0.0 <= rec["transfer_frac"] <= 1.0
        assert rec["acq_per_s_ci"]["n_runs"] >= 1
        assert set(rec["per_stream"]) == {"probe0", "probe1"}
        for g in rec["groups"].values():
            assert g["plan"]["variant"] == "dynamic"
            assert g["plan"]["in_flight"] == depth
            assert g["warm_source"] in ("aot", "pool")
    # The sweep shares one warm pool: only the first cell pays AOT.
    assert mt_records[0]["warmup_s"] > 0.0
    assert mt_records[1]["warmup_s"] == 0.0


def test_validator_rejects_multitenant_overlap_violations(mt_records):
    """The new overlap/warm-start columns are REQUIRED and bounded — a
    producer that drops or corrupts one fails loudly."""
    import copy

    base = mt_records[1]
    validate_record(base)

    rec = copy.deepcopy(base)
    del rec["in_flight"]
    with pytest.raises(SchemaError, match="missing required key"):
        validate_record(rec)

    for key in ("warmup_s", "acq_per_s_ci", "transfer_frac",
                "in_flight_occupancy"):
        rec = copy.deepcopy(base)
        del rec[key]
        with pytest.raises(SchemaError, match="missing required key"):
            validate_record(rec)

    rec = copy.deepcopy(base)
    rec["transfer_frac"] = 1.5
    with pytest.raises(SchemaError, match=r"fraction in \[0, 1\]"):
        validate_record(rec)

    rec = copy.deepcopy(base)
    del rec["in_flight_occupancy"]["mean_depth"]
    with pytest.raises(SchemaError, match="mean_depth"):
        validate_record(rec)

    gid = next(iter(base["groups"]))
    for key in ("warmup_s", "warm_source", "in_flight"):
        rec = copy.deepcopy(base)
        del rec["groups"][gid][key]
        with pytest.raises(SchemaError, match="missing required key"):
            validate_record(rec)

    # The serving-context plan stamp is part of PLAN_KEYS everywhere.
    rec = copy.deepcopy(base)
    del rec["groups"][gid]["plan"]["warm_start"]
    with pytest.raises(SchemaError, match="warm_start"):
        validate_record(rec)


def test_validator_rejects_bad_records():
    good = {"kind": "sample", "name": "x", "run": 0, "t_s": 0.1}
    validate_record(good)
    with pytest.raises(SchemaError, match="unknown kind"):
        validate_record({"kind": "nope"})
    with pytest.raises(SchemaError, match="missing required key"):
        validate_record({"kind": "sample", "name": "x", "run": 0})
    with pytest.raises(SchemaError, match="expected real"):
        validate_record({**good, "t_s": "fast"})
    with pytest.raises(SchemaError, match="expected int"):
        validate_record({**good, "run": 1.5})
    with pytest.raises(SchemaError, match="null not allowed"):
        validate_record({**good, "t_s": None})
    # bool must not satisfy int/real (True is an int in Python)
    with pytest.raises(SchemaError, match="expected int"):
        validate_record({**good, "run": True})


def _ci_block(means=(0.1,)):
    return {"mean": sum(means) / len(means), "ci_lo": min(means),
            "ci_hi": max(means), "n_runs": len(means),
            "confidence": 0.95, "n_boot": 2000, "seed": 0,
            "method": "kalibera-jones-bootstrap",
            "run_means": list(means)}


def test_validator_rejects_non_monotone_percentiles():
    lat = {"n": 2, "mean_s": 0.1, "std_s": 0.0, "p50_s": 0.2,
           "p95_s": 0.1, "p99_s": 0.3, "jitter_s": 0.0,
           "budget_s": None, "miss_rate": 0.0}
    rec = {"kind": "summary", "name": "x", "t_avg_s": 0.1, "fps": 10.0,
           "mbps": 1.0, "joules_per_run_model": 0.0, "peak_mem_gb": 0.0,
           "runs": 2, "latency": lat, "ci": _ci_block()}
    with pytest.raises(SchemaError, match="percentiles not monotone"):
        validate_record(rec)


def test_summary_requires_ci_block(bench_result):
    """The statistical gate needs an interval on every summary row —
    a producer that drops the ci block (or corrupts it) fails CI
    loudly, it does not degrade the gate silently."""
    summary = json.loads(bench_result.ndjson_lines()[0])
    validate_record(summary)
    assert summary["ci"]["n_runs"] >= 1

    rec = {k: v for k, v in summary.items() if k != "ci"}
    with pytest.raises(SchemaError, match="missing required key 'ci'"):
        validate_record(rec)
    with pytest.raises(SchemaError, match="null not allowed"):
        validate_record({**summary, "ci": None})
    # A ci block missing its level-one data cannot be re-bootstrapped.
    truncated = {k: v for k, v in summary["ci"].items()
                 if k != "run_means"}
    with pytest.raises(SchemaError, match="run_means"):
        validate_record({**summary, "ci": truncated})


def test_ci_block_internal_consistency_enforced():
    good = {"kind": "summary", "name": "x", "t_avg_s": 0.1, "fps": 10.0,
            "mbps": 1.0, "joules_per_run_model": 0.0, "peak_mem_gb": 0.0,
            "runs": 2, "ci": _ci_block((0.1, 0.12, 0.08)),
            "latency": {"n": 2, "mean_s": 0.1, "std_s": 0.0,
                        "p50_s": 0.1, "p95_s": 0.1, "p99_s": 0.1,
                        "jitter_s": 0.0, "budget_s": None,
                        "miss_rate": 0.0}}
    validate_record(good)
    # Interval must contain its point estimate.
    bad = {**good, "ci": {**good["ci"], "ci_lo": 0.11}}
    with pytest.raises(SchemaError, match="point estimate"):
        validate_record(bad)
    # run_means length must equal n_runs (re-bootstrappability).
    bad = {**good, "ci": {**good["ci"], "run_means": [0.1]}}
    with pytest.raises(SchemaError, match="n_runs=3"):
        validate_record(bad)
    bad = {**good, "ci": {**good["ci"], "n_runs": 0, "run_means": []}}
    with pytest.raises(SchemaError, match="n_runs"):
        validate_record(bad)


def test_multitenant_requires_load_profile_stamp(mt_records):
    """The load-profile provenance columns are REQUIRED: a multitenant
    row without its profile name, trace hash, or drop count could be
    mistaken for a different load scenario when gated."""
    import copy

    base = mt_records[0]
    assert base["load_profile"] == "steady"
    assert len(base["trace_sha256"]) == 64
    assert base["dropped"] == 0

    for key in ("load_profile", "trace_sha256", "dropped"):
        rec = copy.deepcopy(base)
        del rec[key]
        with pytest.raises(SchemaError, match="missing required key"):
            validate_record(rec)

    rec = copy.deepcopy(base)
    rec["trace_sha256"] = "not-a-hash"
    with pytest.raises(SchemaError, match="64 lowercase hex"):
        validate_record(rec)
    rec["trace_sha256"] = base["trace_sha256"][:-1] + "G"
    with pytest.raises(SchemaError, match="64 lowercase hex"):
        validate_record(rec)

    # A served stream may not carry a null latency block.
    rec = copy.deepcopy(base)
    sid = next(iter(rec["per_stream"]))
    rec["per_stream"][sid]["latency"] = None
    with pytest.raises(SchemaError, match="null but the stream served"):
        validate_record(rec)


def test_multitenant_requires_acq_per_s_ci(mt_records):
    import copy

    rec = copy.deepcopy(mt_records[0])
    validate_record(rec)
    assert rec["acq_per_s_ci"]["n_runs"] >= 1    # producer-stamped
    del rec["acq_per_s_ci"]
    with pytest.raises(SchemaError,
                       match="missing required key 'acq_per_s_ci'"):
        validate_record(rec)


def test_validator_rejects_bad_plan_stamp():
    rec = {"kind": "sample", "name": "x", "run": 0, "t_s": 0.1,
           "plan": {"policy": "fixed"}}          # truncated stamp
    with pytest.raises(SchemaError, match=r"plan: missing required key"):
        validate_record(rec)


def test_validator_rejects_bad_stage_lowerings_stamp():
    """repro-bench-v1 stays valid (the stamp is additive), but a plan
    stamp without — or with a malformed — stage_lowerings field fails."""
    plan = UltrasoundPipeline(_tiny_cfg()).plan.json_dict()
    rec = {"kind": "sample", "name": "x", "run": 0, "t_s": 0.1,
           "plan": plan}
    validate_record(rec)                         # the real stamp passes
    assert plan["stage_lowerings"] == {"demod": "xla", "beamform": "xla",
                                       "bmode": "xla"}
    truncated = {**plan}
    del truncated["stage_lowerings"]
    with pytest.raises(SchemaError,
                       match=r"missing required key 'stage_lowerings'"):
        validate_record({**rec, "plan": truncated})
    with pytest.raises(SchemaError, match=r"stage_lowerings: expected dict"):
        validate_record({**rec, "plan": {**plan,
                                         "stage_lowerings": "pallas"}})
    with pytest.raises(SchemaError, match=r"expected a\s+lowering name"):
        validate_record({**rec, "plan": {**plan,
                                         "stage_lowerings": {"demod": 3}}})


def test_validator_requires_fusion_precision_stamp():
    """repro-bench-v1 requires the fusion/precision contract columns in
    every plan stamp — a fused/bf16 row must never be mistakable for an
    unfused/f32 one because a producer dropped the field."""
    plan = UltrasoundPipeline(_tiny_cfg()).plan.json_dict()
    rec = {"kind": "sample", "name": "x", "run": 0, "t_s": 0.1,
           "plan": plan}
    validate_record(rec)                         # the real stamp passes
    assert plan["fusion"] == "none" and plan["precision"] == "f32"
    assert plan["fusion_group"] is None and plan["fusion_block"] is None
    for key in ("fusion", "precision"):
        truncated = {k: v for k, v in plan.items() if k != key}
        with pytest.raises(SchemaError,
                           match=f"missing required key '{key}'"):
            validate_record({**rec, "plan": truncated})
        with pytest.raises(SchemaError, match=f"{key}: null not allowed"):
            validate_record({**rec, "plan": {**plan, key: None}})
    with pytest.raises(SchemaError, match="fusion_block: expected int"):
        validate_record({**rec, "plan": {**plan, "fusion_block": "128"}})
    # A fused stamp (group + block set) is valid as-is.
    fused = {**plan, "fusion": "fused", "precision": "bf16",
             "fusion_group": "demod+beamform+bmode", "fusion_block": 128}
    validate_record({**rec, "plan": fused})


def test_fused_plan_stamp_validates():
    """A real fused plan's json_dict passes the schema with the group
    stamped — wired end to end, not just the hand-built dict above."""
    from repro.core.plan import plan_pipeline
    cfg = tiny_config(variant=Variant.DYNAMIC, fusion="fused")
    plan = plan_pipeline(cfg).json_dict()
    validate_record({"kind": "sample", "name": "x", "run": 0, "t_s": 0.1,
                     "plan": plan})
    assert plan["fusion"] == "fused"
    assert plan["fusion_group"] == "demod+beamform+bmode"
    assert set(plan["stage_lowerings"].values()) == {"pallas"}


def test_validate_lines_counts_and_empty():
    lines = [json.dumps({"kind": "sample", "name": "x", "run": i,
                         "t_s": 0.1}) for i in range(3)]
    assert validate_lines(lines) == {"sample": 3}
    with pytest.raises(SchemaError, match="no NDJSON records"):
        validate_lines([])
    with pytest.raises(SchemaError, match="invalid JSON"):
        validate_lines(["{not json"])


def test_numpy_scalars_do_not_sneak_past_json():
    """Emitters serialize through json.dumps — numpy scalars would raise
    there, so the validator only ever sees plain JSON types. Assert the
    round trip stays clean for a real multitenant record."""
    from repro.launch.scheduler import (BatchPolicy, StreamSpec,
                                        serve_multitenant)
    cfg = _tiny_cfg()
    stats = serve_multitenant(
        [StreamSpec("s0", cfg, fps=1e9, n_frames=2)],
        policy=BatchPolicy(max_batch=2, max_queue_delay_ms=1.0))
    line = json.dumps({"kind": "multitenant", **stats})
    assert validate_lines([line]) == {"multitenant": 1}
    assert not isinstance(json.loads(line)["fps"], np.ndarray)


def test_multitenant_requires_transfer_and_drain_stamp(mt_records):
    """The host-transfer telemetry is REQUIRED on multitenant rows: a
    record without its drain mode, transfer seconds, or throughput CI
    block could not be gated against the transfer baseline."""
    import copy

    base = mt_records[0]
    assert base["drain"] in ("async", "block")
    for key in ("stage_copy_s", "h2d_s", "d2h_s"):
        assert base[key] >= 0.0
    assert 0.0 <= base["transfer_frac"] <= 1.0
    assert base["acq_per_s_ci"]["n_runs"] >= 1

    for key in ("drain", "stage_copy_s", "h2d_s", "d2h_s",
                "transfer_frac", "acq_per_s_ci"):
        rec = copy.deepcopy(base)
        del rec[key]
        with pytest.raises(SchemaError, match="missing required key"):
            validate_record(rec)

    rec = copy.deepcopy(base)
    rec["drain"] = "sideways"
    with pytest.raises(SchemaError, match="async.*block|drain"):
        validate_record(rec)

    rec = copy.deepcopy(base)
    rec["transfer_frac"] = 1.5
    with pytest.raises(SchemaError, match=r"fraction in \[0, 1\]"):
        validate_record(rec)


def test_optional_transfer_and_variance_blocks_validate():
    """Any record kind may carry an optional 'transfer' or 'variance'
    block; when present the blocks are checked, not waved through."""
    good = {"kind": "sample", "name": "x", "run": 0, "t_s": 0.1,
            "transfer": {"stage_copy_s": 0.01, "h2d_s": 0.02,
                         "d2h_s": 0.005, "transfer_frac": 0.2},
            "variance": {"n_runs": 3, "mean_iters": 10.0,
                         "within_var": 1e-6, "between_var": 2e-6,
                         "within_share": 0.3, "between_share": 0.7}}
    assert validate_record(good) == "sample"

    import copy
    rec = copy.deepcopy(good)
    del rec["transfer"]["h2d_s"]
    with pytest.raises(SchemaError, match="missing required key"):
        validate_record(rec)

    rec = copy.deepcopy(good)
    rec["transfer"]["transfer_frac"] = -0.1
    with pytest.raises(SchemaError, match=r"fraction in \[0, 1\]"):
        validate_record(rec)

    rec = copy.deepcopy(good)
    rec["variance"]["between_share"] = 1.2
    with pytest.raises(SchemaError, match=r"fraction in \[0, 1\]"):
        validate_record(rec)

    rec = copy.deepcopy(good)
    rec["variance"]["n_runs"] = "three"
    with pytest.raises(SchemaError, match="int"):
        validate_record(rec)
