"""Statistical throughput regression gate: CI smoke rows vs baseline.

`BENCH_cpu.json` is the committed CPU reference (regenerated with the
commands in its provenance note, ``--repeats >= 3`` so every row carries
a real bootstrap confidence interval). CI re-runs the same smoke
commands on whatever runner it lands on and this gate compares the two
with the CI-exclusion rule from `repro.bench.stats`: a cell FAILS only
when the bootstrap interval of the current/baseline ratio *excludes*
the allowed factor. A point estimate beyond the factor whose interval
still straddles it is runner noise and passes; an interval entirely
beyond it is a regression no rerun will undo. Rows without run-level
data (``--repeats 1`` artifacts, pre-CI baselines) degrade to the
legacy strict mean-factor comparison, annotated ``(mean-only)``.

Two row families are gated:

  * table1 summary rows (``benchmarks.run --fast --repeats 3 --json``):
    matched by the full cell key — ``name`` already encodes
    (pipeline, variant, lowering, fusion, precision) and the stamped
    plan contributes the device count. Time-like: FAIL when the
    t_avg ratio CI sits entirely above ``factor``. ``--current`` is
    repeatable so the default, pallas-lowering and fused-precision
    smoke artifacts are all gated against the one baseline.
  * multitenant rows (``benchmarks.multitenant`` NDJSON): matched by
    the sweep cell key (clients, max_batch, max_queue_delay_ms,
    in_flight, load_profile, drain — a burst window never gates
    against a steady baseline, an async-drain window never against a
    blocking one); throughput-like: FAIL when the acq/s ratio CI sits
    entirely below ``1/factor``. Gating acq/s per in-flight depth
    keeps the async scheduler's overlap win (depth 2 > depth 1 in the
    baseline) from regressing back to synchronous throughput
    unnoticed.

A baseline row with no current counterpart fails loudly (a renamed or
dropped row is a silent gate hole); extra current rows are ignored so
new benchmarks can land before the baseline is regenerated. A record
missing its identity keys (e.g. a multitenant row without ``in_flight``)
is a *named* gate failure identifying the offending record — never a
bare KeyError traceback.

  PYTHONPATH=src python -m benchmarks.gate \
      --baseline BENCH_cpu.json --current BENCH_ci.json \
      --current BENCH_lowering.json --current BENCH_fused.json \
      --multitenant MULTITENANT_ci.ndjson
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Sequence, Tuple, Union

from repro.bench.stats import GateDecision, gate_ratio

MtKey = Tuple[int, int, float, int, str, str]
T1Key = Tuple[str, int]


class GateRecordError(ValueError):
    """A benchmark record too malformed to gate (missing identity or
    metric keys). Callers turn it into a named gate failure pointing at
    the offending record instead of a raw KeyError traceback."""


def _ident(rec: dict) -> str:
    """Best-effort identification of a malformed record for failure
    messages: its name if present, else its keys."""
    if isinstance(rec, dict) and rec.get("name"):
        return f"record {rec['name']!r}"
    keys = sorted(rec.keys()) if isinstance(rec, dict) else type(rec)
    return f"record with keys {keys}"


def t1_key(rec: dict) -> T1Key:
    """A table1 summary row's gate-cell identity.

    ``name`` already encodes (pipeline, variant, lowering,
    fusion@precision); the stamped plan contributes the device count so
    a multi-device row never masks its single-device counterpart.
    """
    try:
        name = rec["name"]
    except (TypeError, KeyError):
        raise GateRecordError(
            f"table1 {_ident(rec)}: missing 'name' (not a summary row?)")
    devices = (rec.get("plan") or {}).get("devices") or 1
    return (name, int(devices))


def mt_key(rec: dict) -> MtKey:
    """A multitenant record's sweep-cell identity.

    ``load_profile`` is part of the identity — a burst or churn window
    must never gate against a steady baseline cell. Pre-profile records
    (old baselines) default to "steady", which is exactly the schedule
    they ran. ``drain`` (the host-transfer retirement mode) is likewise
    part of the identity — an async-drain window must never gate
    against a blocking baseline — and pre-drain records default to
    "block", the only retirement path that existed when they ran.
    """
    try:
        return (rec["clients"], rec["policy"]["max_batch"],
                rec["policy"]["max_queue_delay_ms"], rec["in_flight"],
                rec.get("load_profile", "steady"),
                rec.get("drain", "block"))
    except (TypeError, KeyError) as e:
        raise GateRecordError(
            f"multitenant {_ident(rec)}: missing cell-identity key "
            f"{e} (need clients, policy.max_batch, "
            f"policy.max_queue_delay_ms, in_flight)")


def _metric_runs(rec: dict, metric: str, ci_key: str,
                 family: str) -> Tuple[List[float], bool]:
    """(run-level means for the metric, whether they are real repeats).

    A row whose ``ci_key`` block carries ``run_means`` with more than
    one entry contributes its full level-one data (the gate can
    re-bootstrap it); anything else degrades to the single mean —
    flagged so the verdict is annotated ``(mean-only)``.
    """
    ci = rec.get(ci_key)
    if isinstance(ci, dict):
        means = ci.get("run_means")
        if isinstance(means, list) and len(means) > 1:
            return [float(m) for m in means], True
    try:
        return [float(rec[metric])], False
    except (TypeError, KeyError):
        raise GateRecordError(
            f"{family} {_ident(rec)}: missing metric {metric!r}")


def _gate_cell(base: dict, cur: dict, *, metric: str, ci_key: str,
               family: str, factor: float,
               higher_is_better: bool) -> Tuple[GateDecision, bool]:
    """(decision, statistical) for one matched baseline/current pair.

    ``statistical`` is False when either side lacked run-level data and
    the CI-exclusion rule therefore collapsed to the legacy strict mean
    comparison (degenerate zero-width intervals).
    """
    base_runs, base_real = _metric_runs(base, metric, ci_key, family)
    cur_runs, cur_real = _metric_runs(cur, metric, ci_key, family)
    decision = gate_ratio(base_runs, cur_runs, factor=factor,
                          higher_is_better=higher_is_better)
    return decision, base_real and cur_real


def gate_table1(baseline: List[dict], current: List[dict], *,
                factor: float) -> List[str]:
    """Failures: table1 cells whose t_avg ratio CI excludes the factor."""
    failures: List[str] = []
    cur: Dict[T1Key, dict] = {}
    for rec in current:
        try:
            cur[t1_key(rec)] = rec
        except GateRecordError as e:
            failures.append(str(e))
    for base in baseline:
        try:
            key = t1_key(base)
            row = cur.get(key)
            cell = f"{key[0]} devices={key[1]}"
            if row is None:
                failures.append(
                    f"table1 row {cell!r}: missing from current")
                continue
            dec, statistical = _gate_cell(
                base, row, metric="t_avg_s", ci_key="ci", family="table1",
                factor=factor, higher_is_better=False)
        except GateRecordError as e:
            failures.append(str(e))
            continue
        if not dec.ok:
            note = "" if statistical else " (mean-only)"
            failures.append(
                f"table1 row {cell!r}: t_avg {dec.reason}{note}")
    return failures


def gate_multitenant(baseline: List[dict], current: List[dict], *,
                     factor: float) -> List[str]:
    """Failures: multitenant cells whose acq/s ratio CI excludes the
    allowed floor."""
    failures: List[str] = []
    cur: Dict[MtKey, dict] = {}
    for rec in current:
        try:
            cur[mt_key(rec)] = rec
        except GateRecordError as e:
            failures.append(str(e))
    for base in baseline:
        try:
            key = mt_key(base)
            row = cur.get(key)
            cell = (f"clients={key[0]} max_batch={key[1]} "
                    f"delay_ms={key[2]:g} in_flight={key[3]} "
                    f"profile={key[4]} drain={key[5]}")
            if row is None:
                failures.append(f"multitenant cell [{cell}]: missing "
                                f"from current")
                continue
            dec, statistical = _gate_cell(
                base, row, metric="acq_per_s", ci_key="acq_per_s_ci",
                family="multitenant", factor=factor,
                higher_is_better=True)
        except GateRecordError as e:
            failures.append(str(e))
            continue
        if not dec.ok:
            note = "" if statistical else " (mean-only)"
            failures.append(
                f"multitenant cell [{cell}]: acq_per_s "
                f"{dec.reason}{note}")
    return failures


def run_gate(baseline_path: str, *,
             current_path: Union[str, Sequence[str], None] = None,
             multitenant_path: Union[str, Sequence[str], None] = None,
             factor: float = 2.0) -> List[str]:
    """All gate failures for the given artifact files (empty = pass).

    ``current_path`` and ``multitenant_path`` each accept one path or a
    sequence of paths — the CI workflow gates the default, lowering and
    fused smoke artifacts (and the steady + transfer-telemetry
    multitenant NDJSON artifacts) against the one baseline in a single
    invocation, so every baseline cell must be covered by the union of
    the current artifacts.
    """
    with open(baseline_path) as f:
        baseline = json.load(f)
    failures = []
    if current_path is not None:
        paths = ([current_path] if isinstance(current_path, str)
                 else list(current_path))
        current: List[dict] = []
        for path in paths:
            with open(path) as f:
                current += json.load(f)["results"]
        failures += gate_table1(baseline["results"], current,
                                factor=factor)
    mt_base = baseline.get("multitenant", [])
    if multitenant_path is not None and mt_base:
        mt_paths = ([multitenant_path]
                    if isinstance(multitenant_path, str)
                    else list(multitenant_path))
        mt_cur: List[dict] = []
        for path in mt_paths:
            with open(path) as f:
                mt_cur += [json.loads(line) for line in f
                           if line.strip()]
        mt_cur = [r for r in mt_cur if r.get("kind") == "multitenant"]
        failures += gate_multitenant(mt_base, mt_cur, factor=factor)
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Compare CI smoke benchmark rows against the "
                    "checked-in baseline (bootstrap-CI regression gate).")
    ap.add_argument("--baseline", default="BENCH_cpu.json",
                    help="committed reference JSON (table1 results + "
                         "multitenant rows)")
    ap.add_argument("--current", action="append", default=None,
                    help="benchmarks.run --json artifact to gate "
                         "(repeatable; the union of rows must cover "
                         "every baseline cell)")
    ap.add_argument("--multitenant", action="append", default=None,
                    help="benchmarks.multitenant --ndjson artifact to "
                         "gate (repeatable; the union of rows must "
                         "cover every baseline multitenant cell)")
    ap.add_argument("--factor", type=float, default=2.0,
                    help="allowed slowdown factor (default 2.0); FAIL "
                         "only when the ratio CI excludes it")
    args = ap.parse_args()
    if args.current is None and args.multitenant is None:
        ap.error("nothing to gate: pass --current and/or --multitenant")

    failures = run_gate(args.baseline, current_path=args.current,
                        multitenant_path=args.multitenant,
                        factor=args.factor)
    for msg in failures:
        print(f"gate failure: {msg}", file=sys.stderr)
    if not failures:
        print(f"gate ok (factor {args.factor:g}, CI-exclusion rule, "
              f"baseline {args.baseline})")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
