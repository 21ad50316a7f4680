"""Statistical regression gate (benchmarks/gate.py): pure-logic tests.

The gate's job is narrow — compare CI smoke rows against the committed
baseline with the CI-exclusion rule — so the tests pin exactly the
decisions that matter: a true regression (interval entirely past the
factor) fails, a noisy-but-straddling cell passes (the false alarm the
statistical gate exists to kill), rows without run-level data degrade
to the legacy strict mean rule annotated ``(mean-only)``, a baseline
row MISSING from the current artifact fails loudly, malformed records
become *named* failures instead of KeyError tracebacks, and cells
match on their full identity (table1: name + devices; multitenant: the
sweep key including in-flight depth).
"""

import json

import pytest

from benchmarks.gate import (GateRecordError, gate_multitenant,
                             gate_table1, mt_key, run_gate, t1_key)


def _ci(means):
    return {"mean": sum(means) / len(means), "ci_lo": min(means),
            "ci_hi": max(means), "n_runs": len(means),
            "confidence": 0.95, "n_boot": 2000, "seed": 0,
            "method": "kalibera-jones-bootstrap",
            "run_means": list(means)}


def _t1(name, t, runs=None, devices=None):
    rec = {"name": name, "t_avg_s": t}
    if runs is not None:
        rec["ci"] = _ci(runs)
    if devices is not None:
        rec["plan"] = {"devices": devices}
    return rec


def _mt(clients, max_batch, delay_ms, in_flight, acq_per_s, runs=None,
        profile=None):
    rec = {"clients": clients,
           "policy": {"max_batch": max_batch,
                      "max_queue_delay_ms": delay_ms},
           "in_flight": in_flight, "acq_per_s": acq_per_s,
           "kind": "multitenant"}
    if runs is not None:
        rec["acq_per_s_ci"] = _ci(runs)
    if profile is not None:
        rec["load_profile"] = profile
    return rec


# ---------------------------------------------------------------------------
# Mean-only degradation (rows without repeats)
# ---------------------------------------------------------------------------

def test_gate_table1_mean_only_factor_and_missing():
    base = [_t1("a", 1.0), _t1("b", 1.0), _t1("c", 1.0)]
    cur = [_t1("a", 1.9),            # within 2x -> ok
           _t1("b", 2.1),            # beyond 2x -> fail
           _t1("extra", 99.0)]       # not in baseline -> ignored
    failures = gate_table1(base, cur, factor=2.0)
    assert len(failures) == 2
    assert any("'b devices=1'" in f and "(mean-only)" in f
               for f in failures)
    assert any("'c devices=1'" in f and "missing" in f for f in failures)
    assert gate_table1(base[:1], cur[:1], factor=2.0) == []


def test_gate_multitenant_keys_on_full_cell_identity():
    base = [_mt(2, 4, 5.0, 1, 100.0), _mt(2, 4, 5.0, 2, 200.0)]
    # depth-1 cell healthy, depth-2 cell regressed to depth-1 speed:
    # the per-depth key must catch it.
    cur = [_mt(2, 4, 5.0, 1, 95.0), _mt(2, 4, 5.0, 2, 90.0)]
    failures = gate_multitenant(base, cur, factor=2.0)
    assert len(failures) == 1
    assert "in_flight=2" in failures[0] and "acq_per_s" in failures[0]
    # A missing cell fails; an extra current cell does not.
    failures = gate_multitenant(base, cur[:1] + [_mt(8, 4, 5.0, 1, 1.0)],
                                factor=2.0)
    assert len(failures) == 1 and "missing" in failures[0]
    assert mt_key(base[0]) != mt_key(base[1])


def test_gate_multitenant_profile_is_part_of_cell_identity():
    """A burst window must never gate against a steady baseline cell —
    and a record without the stamp (pre-profile baseline) IS the steady
    cell it ran as."""
    base = [_mt(2, 4, 5.0, 2, 100.0, profile="steady"),
            _mt(2, 4, 5.0, 2, 60.0, profile="burst")]
    assert mt_key(base[0]) != mt_key(base[1])
    assert mt_key(base[0])[4] == "steady"
    # unstamped record == steady: backwards-compatible identity
    assert mt_key(_mt(2, 4, 5.0, 2, 100.0)) == mt_key(base[0])

    # a burst row at steady-regression speed satisfies its OWN cell but
    # must not stand in for the missing steady cell
    cur = [_mt(2, 4, 5.0, 2, 55.0, profile="burst")]
    failures = gate_multitenant(base, cur, factor=2.0)
    assert len(failures) == 1
    assert "missing" in failures[0] and "profile=steady" in failures[0]
    # both profiles present and healthy -> pass
    cur.append(_mt(2, 4, 5.0, 2, 95.0, profile="steady"))
    assert gate_multitenant(base, cur, factor=2.0) == []


# ---------------------------------------------------------------------------
# CI-exclusion decisions (rows with run-level data)
# ---------------------------------------------------------------------------

def test_within_noise_excursion_passes_with_ci():
    """The statistical gate's reason to exist: a point estimate past
    the factor whose ratio interval still straddles it is runner noise
    and must NOT fail — the legacy mean rule would have."""
    base = [_t1("a", 1.0, runs=[1.00, 1.02, 0.98])]
    cur = [_t1("a", 1.13, runs=[1.15, 1.00, 1.25])]
    assert gate_table1(base, cur, factor=1.05) == []
    # Sanity: the same point excursion WITHOUT intervals does fail.
    assert len(gate_table1([_t1("a", 1.0)], [_t1("a", 1.13)],
                           factor=1.05)) == 1


def test_true_regression_fails_with_ci():
    base = [_t1("a", 1.0, runs=[1.00, 1.02, 0.98])]
    cur = [_t1("a", 3.0, runs=[3.0, 3.1, 2.9])]
    failures = gate_table1(base, cur, factor=2.0)
    assert len(failures) == 1
    assert "entirely above" in failures[0]
    assert "(mean-only)" not in failures[0]


def test_multitenant_ci_exclusion_rule():
    base = [_mt(2, 4, 5.0, 2, 100.0, runs=[100.0, 102.0, 98.0])]
    # Noisy dip straddling the floor: pass.
    cur = [_mt(2, 4, 5.0, 2, 55.0, runs=[45.0, 55.0, 65.0])]
    assert gate_multitenant(base, cur, factor=2.0) == []
    # Collapsed throughput, tight interval: fail.
    cur = [_mt(2, 4, 5.0, 2, 30.0, runs=[30.0, 31.0, 29.0])]
    failures = gate_multitenant(base, cur, factor=2.0)
    assert len(failures) == 1 and "entirely below" in failures[0]


def test_table1_key_includes_devices():
    base = [_t1("a", 1.0, devices=2)]
    # Same name at devices=1 must NOT satisfy the devices=2 baseline.
    failures = gate_table1(base, [_t1("a", 1.0, devices=1)], factor=2.0)
    assert len(failures) == 1 and "missing" in failures[0]
    assert gate_table1(base, [_t1("a", 1.0, devices=2)],
                       factor=2.0) == []
    assert t1_key(_t1("a", 1.0)) == ("a", 1)      # no plan -> 1 device


# ---------------------------------------------------------------------------
# Malformed records: named failures, never KeyError tracebacks
# ---------------------------------------------------------------------------

def test_malformed_multitenant_record_is_named_failure():
    bad = {"kind": "multitenant", "name": "mt/broken",
           "clients": 2, "acq_per_s": 10.0}      # no policy/in_flight
    with pytest.raises(GateRecordError, match="mt/broken"):
        mt_key(bad)
    # In the current rows: reported once, the well-formed cells still
    # gate.
    base = [_mt(2, 4, 5.0, 1, 100.0)]
    failures = gate_multitenant(base, [bad] + [_mt(2, 4, 5.0, 1, 95.0)],
                                factor=2.0)
    assert len(failures) == 1 and "mt/broken" in failures[0]
    assert "cell-identity" in failures[0]
    # In the baseline rows: also a named failure, not a crash.
    failures = gate_multitenant([bad], [], factor=2.0)
    assert len(failures) == 1 and "mt/broken" in failures[0]


def test_malformed_table1_record_is_named_failure():
    bad = {"t_avg_s": 1.0}                        # no name
    with pytest.raises(GateRecordError, match="missing 'name'"):
        t1_key(bad)
    failures = gate_table1([bad], [], factor=2.0)
    assert len(failures) == 1 and "missing 'name'" in failures[0]
    # A named row without its metric is identified by name.
    base = [_t1("a", 1.0)]
    failures = gate_table1(base, [{"name": "a"}], factor=2.0)
    assert len(failures) == 1
    assert "'a'" in failures[0] and "t_avg_s" in failures[0]


# ---------------------------------------------------------------------------
# End to end over artifact files
# ---------------------------------------------------------------------------

def test_run_gate_end_to_end(tmp_path):
    baseline = {"results": [_t1("a", 1.0)],
                "multitenant": [_mt(2, 4, 5.0, 2, 100.0)]}
    (tmp_path / "base.json").write_text(json.dumps(baseline))
    (tmp_path / "cur.json").write_text(
        json.dumps({"results": [_t1("a", 1.5)]}))
    with open(tmp_path / "mt.ndjson", "w") as f:
        f.write(json.dumps(_mt(2, 4, 5.0, 2, 80.0)) + "\n")
        f.write(json.dumps({"kind": "summary"}) + "\n")   # skipped

    assert run_gate(str(tmp_path / "base.json"),
                    current_path=str(tmp_path / "cur.json"),
                    multitenant_path=str(tmp_path / "mt.ndjson")) == []

    (tmp_path / "cur.json").write_text(
        json.dumps({"results": [_t1("a", 2.5)]}))
    failures = run_gate(str(tmp_path / "base.json"),
                        current_path=str(tmp_path / "cur.json"),
                        multitenant_path=str(tmp_path / "mt.ndjson"),
                        factor=2.0)
    assert len(failures) == 1 and "'a devices=1'" in failures[0]

    # No multitenant baseline rows -> the NDJSON side is skipped.
    (tmp_path / "base2.json").write_text(
        json.dumps({"results": [_t1("a", 1.0)]}))
    assert run_gate(str(tmp_path / "base2.json"),
                    multitenant_path=str(tmp_path / "mt.ndjson")) == []


def test_run_gate_multiple_current_artifacts(tmp_path):
    """The CI workflow gates the default + lowering + fused smoke
    artifacts in one invocation: the union of their rows must cover
    every baseline cell."""
    baseline = {"results": [_t1("a", 1.0), _t1("b", 1.0)]}
    (tmp_path / "base.json").write_text(json.dumps(baseline))
    (tmp_path / "cur_a.json").write_text(
        json.dumps({"results": [_t1("a", 1.2)]}))
    (tmp_path / "cur_b.json").write_text(
        json.dumps({"results": [_t1("b", 1.2)]}))

    # Either artifact alone leaves a hole; together they cover.
    failures = run_gate(str(tmp_path / "base.json"),
                        current_path=str(tmp_path / "cur_a.json"))
    assert len(failures) == 1 and "'b devices=1'" in failures[0]
    assert run_gate(str(tmp_path / "base.json"),
                    current_path=[str(tmp_path / "cur_a.json"),
                                  str(tmp_path / "cur_b.json")]) == []


# ---------------------------------------------------------------------------
# Drain mode in the cell identity
# ---------------------------------------------------------------------------

def _mt_drain(clients, max_batch, delay_ms, in_flight, acq_per_s, *,
              drain=None, runs=None):
    rec = _mt(clients, max_batch, delay_ms, in_flight, acq_per_s,
              runs=runs)
    if drain is not None:
        rec["drain"] = drain
    return rec


def test_gate_multitenant_drain_is_part_of_cell_identity():
    """An async-drain window must never gate against a blocking
    baseline cell — and an unstamped (pre-drain) record IS the blocking
    cell it ran as."""
    base = [_mt_drain(2, 4, 5.0, 2, 100.0, drain="block"),
            _mt_drain(2, 4, 5.0, 2, 120.0, drain="async")]
    assert mt_key(base[0]) != mt_key(base[1])
    assert mt_key(base[0])[5] == "block"
    # unstamped record == block: backwards-compatible identity
    assert mt_key(_mt(2, 4, 5.0, 2, 100.0)) == mt_key(base[0])

    # an async row at block speed satisfies its own cell but must not
    # stand in for the missing block cell
    cur = [_mt_drain(2, 4, 5.0, 2, 115.0, drain="async")]
    failures = gate_multitenant(base, cur, factor=2.0)
    assert len(failures) == 1
    assert "missing" in failures[0] and "drain=block" in failures[0]
    cur.append(_mt_drain(2, 4, 5.0, 2, 95.0, drain="block"))
    assert gate_multitenant(base, cur, factor=2.0) == []


def test_run_gate_multiple_multitenant_artifacts(tmp_path):
    """--multitenant is repeatable: the union of NDJSON artifacts must
    cover every baseline multitenant cell (the CI workflow feeds the
    steady and transfer-telemetry smoke files in one invocation)."""
    baseline = {"results": [],
                "multitenant": [
                    _mt_drain(2, 4, 5.0, 2, 100.0, drain="block"),
                    _mt_drain(2, 4, 5.0, 2, 110.0, drain="async")]}
    (tmp_path / "base.json").write_text(json.dumps(baseline))
    (tmp_path / "mt_block.ndjson").write_text(
        json.dumps(_mt_drain(2, 4, 5.0, 2, 95.0, drain="block"))
        + "\n")
    (tmp_path / "mt_async.ndjson").write_text(
        json.dumps(_mt_drain(2, 4, 5.0, 2, 105.0, drain="async"))
        + "\n")

    failures = run_gate(str(tmp_path / "base.json"),
                        multitenant_path=str(tmp_path
                                             / "mt_block.ndjson"))
    assert len(failures) == 1 and "drain=async" in failures[0]
    assert run_gate(
        str(tmp_path / "base.json"),
        multitenant_path=[str(tmp_path / "mt_block.ndjson"),
                          str(tmp_path / "mt_async.ndjson")]) == []
