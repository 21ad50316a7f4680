"""The paper's benchmarking methodology (§II-E..I), as a harness.

Public API (full methodology reference: docs/benchmarking-methodology.md)
-------------------------------------------------------------------------
`latency_stats`  — per-run samples -> `LatencyStats` (p50/p95/p99,
                   jitter = p95-p50, deadline-miss rate). Also used for
                   queue-delay distributions (any per-event seconds
                   samples summarize the same way).
`occupancy_stats`— per-dispatch batch sizes -> `OccupancyStats` (mean /
                   p50 occupancy, fill fraction, full-batch rate) for
                   the dynamic-batching scheduler's coalescing window.
`bench_callable` — time a jitted callable per the paper's execution
                   model; returns a `BenchResult` carrying the full
                   sample distribution, the resolved `plan` stamp, and
                   measured `ResourceStats` (repro.bench.resources).
                   `repeats` > 1 repeats the whole timed window and the
                   result additionally carries a two-level bootstrap
                   confidence interval over the per-repeat means
                   (repro.bench.stats; Kalibera & Jones) — the `ci`
                   stamp the statistical regression gate compares.
`bench_stages`   — per-stage timing breakdown of the stage graph.
`BenchResult`    — one benchmark row; `csv()` (frozen legacy format),
                   `json_dict()`, `ndjson_lines()` (summary / sample /
                   stage records; every record carries the plan stamp,
                   and summary/sample additionally carry the resources
                   stamp for the metered window — stage timings run in
                   their own windows, so stamping the end-to-end
                   resources on them would misattribute).
`write_json` / `write_ndjson` — telemetry serialization.

Invariants: warm-up runs never count toward samples; every timed sample
is bracketed by `jax.block_until_ready`; metering (resources.py) is
exception-free and reports `None` — never zero — for metrics the
backend cannot measure; `csv()` output stays parseable by the frozen
paper-table readers.

Execution model reproduced exactly:
  * constants precomputed at init, excluded from timing (§II-C),
  * multiple warm-up iterations amortize compilation/graph setup (§II-E),
  * explicit device synchronization (block_until_ready) around the timed
    window (§II-E),
  * repeated inference-only forward passes on a fixed input tensor,
  * per-run samples are retained (not just the mean): every result carries
    the full latency distribution — p50/p95/p99, jitter (p95 − p50), and
    the deadline-miss rate against a configurable frame budget — because
    a mean alone cannot support a real-time throughput claim
    (Kalibera & Jones; CORTEX methodology);
      T_avg = mean(samples)
      FPS  = 1 / T_avg                      (eq. 1)
      MB/s = B_in / (T_avg * 1e6)           (eq. 2)
  * incremental energy per run E_run = (P_active - P_idle) * T_avg (eq. 3)
    — on this CPU stand-in there is no board telemetry (the paper hits the
    same wall on TPU), so E_run is reported from a documented MODEL:
    P_active - P_idle ≈ utilization * (TDP - idle), utilization from the
    roofline compute fraction. Flagged as modeled, never measured. Where
    NVML board power IS available, the *measured* incremental energy
    rides along in `ResourceStats.energy_joules` (None elsewhere).
  * peak memory from compiled.memory_analysis() (args + outputs + temps)
    — the static analogue of the paper's allocator peak — plus the
    *measured* high-water mark in `ResourceStats.peak_memory_bytes`
    (allocator stats on GPU/TPU, live-array sampling fallback on CPU).

Telemetry is serialized two ways: the legacy one-line CSV (paper tables,
unchanged) and NDJSON (one summary line + one line per sample + one line
per stage; schema in EXPERIMENTS.md).
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

# Energy model constants (documented in EXPERIMENTS.md; eq. 3 shape).
CHIP_TDP_W = 200.0       # TPU v5e-class accelerator board power
CHIP_IDLE_W = 60.0


# ---------------------------------------------------------------------------
# Latency distributions
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LatencyStats:
    """Distribution summary of per-run wall-clock samples (seconds)."""

    n: int
    mean_s: float
    std_s: float
    p50_s: float
    p95_s: float
    p99_s: float
    jitter_s: float                       # p95 - p50
    budget_s: Optional[float] = None      # deadline per run, if configured
    miss_rate: float = 0.0                # fraction of samples > budget_s

    def json_dict(self) -> dict:
        d = dataclasses.asdict(self)
        return d


def latency_stats(samples_s: List[float],
                  budget_s: Optional[float] = None) -> LatencyStats:
    """Summarize per-run samples into the distribution the tables report."""
    a = np.asarray(samples_s, dtype=np.float64)
    assert a.size > 0, "latency_stats needs at least one sample"
    p50, p95, p99 = np.percentile(a, [50.0, 95.0, 99.0])
    miss = float((a > budget_s).mean()) if budget_s is not None else 0.0
    return LatencyStats(
        n=int(a.size), mean_s=float(a.mean()), std_s=float(a.std()),
        p50_s=float(p50), p95_s=float(p95), p99_s=float(p99),
        jitter_s=float(p95 - p50), budget_s=budget_s, miss_rate=miss)


# ---------------------------------------------------------------------------
# Batch occupancy (dynamic-batching scheduler telemetry)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class OccupancyStats:
    """Distribution of per-dispatch batch occupancy under coalescing.

    One sample per dispatched batch: how many *valid* frames it carried
    against the policy's ``max_batch`` padding target. ``mean_fill``
    (mean occupancy / max_batch) is the fraction of dispatched compute
    that served real frames — the padding waste is ``1 - mean_fill`` —
    and ``full_rate`` is the fraction of dispatches at exactly
    ``max_batch`` (coalescing filled the batch before the queue-delay
    bound forced a partial flush).
    """

    batches: int
    frames: int
    max_batch: int
    mean_occupancy: float
    p50_occupancy: float
    min_occupancy: int
    max_occupancy: int
    mean_fill: float                      # mean_occupancy / max_batch
    full_rate: float                      # fraction dispatched at max_batch

    def json_dict(self) -> dict:
        return dataclasses.asdict(self)


def occupancy_stats(batch_sizes: List[int],
                    max_batch: int) -> OccupancyStats:
    """Summarize per-dispatch occupancy samples (scheduler invariant:
    no sample may exceed ``max_batch`` — asserted here so a policy bug
    shows up in telemetry generation, not in silently wrong ratios)."""
    a = np.asarray(batch_sizes, dtype=np.int64)
    assert a.size > 0, "occupancy_stats needs at least one batch"
    assert max_batch >= 1, max_batch
    assert a.min() >= 1 and a.max() <= max_batch, (
        f"occupancy outside 1..{max_batch}: {a.min()}..{a.max()}")
    return OccupancyStats(
        batches=int(a.size), frames=int(a.sum()), max_batch=int(max_batch),
        mean_occupancy=float(a.mean()),
        p50_occupancy=float(np.percentile(a, 50.0)),
        min_occupancy=int(a.min()), max_occupancy=int(a.max()),
        mean_fill=float(a.mean() / max_batch),
        full_rate=float((a == max_batch).mean()))


@dataclasses.dataclass
class InFlightStats:
    """Distribution of in-flight dispatch depth at launch time.

    One sample per dispatched batch: how many dispatches (including the
    new one) were in flight the moment it launched, against the
    scheduler's ``in_flight`` ring bound. ``mean_depth`` near 1.0 means
    the window behaved synchronously (no overlap to win); ``full_rate``
    is the fraction of launches that filled the ring — sustained
    full-ring launches mean the device, not the host, is the
    bottleneck.
    """

    dispatches: int
    in_flight: int                        # the ring bound (the knob)
    mean_depth: float
    p50_depth: float
    max_depth: int
    full_rate: float                      # fraction launched at the bound

    def json_dict(self) -> dict:
        return dataclasses.asdict(self)


def in_flight_stats(depths: List[int], in_flight: int) -> InFlightStats:
    """Summarize per-launch in-flight depth samples (scheduler
    invariant: the bounded ring can never exceed ``in_flight`` —
    asserted here, like `occupancy_stats`, so a ring bug surfaces in
    telemetry generation)."""
    a = np.asarray(depths, dtype=np.int64)
    assert a.size > 0, "in_flight_stats needs at least one dispatch"
    assert in_flight >= 1, in_flight
    assert a.min() >= 1 and a.max() <= in_flight, (
        f"in-flight depth outside 1..{in_flight}: {a.min()}..{a.max()}")
    return InFlightStats(
        dispatches=int(a.size), in_flight=int(in_flight),
        mean_depth=float(a.mean()),
        p50_depth=float(np.percentile(a, 50.0)),
        max_depth=int(a.max()),
        full_rate=float((a == in_flight).mean()))


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BenchResult:
    name: str
    t_avg_s: float
    fps: float
    mbps: float
    joules_per_run_model: float
    peak_mem_gb: float
    runs: int
    samples_s: List[float] = dataclasses.field(default_factory=list)
    stats: Optional[LatencyStats] = None
    # Two-level bootstrap CI over per-repeat means (repro.bench.stats
    # CIStats.json_dict): {mean, ci_lo, ci_hi, n_runs, run_means, ...}.
    # n_runs == 1 (no --repeats) is the documented degenerate interval.
    ci: Optional[dict] = None
    # Per-repeat sample lists (level-two data behind `ci`); flattened
    # into samples_s for the legacy distribution columns.
    run_samples_s: List[List[float]] = dataclasses.field(
        default_factory=list)
    stage_breakdown: Dict[str, LatencyStats] = dataclasses.field(
        default_factory=dict)
    # Resolved execution plan (PipelinePlan.json_dict()): the exact
    # (backend, variant, exec_map, policy, devices) decision behind this
    # number.
    plan: Optional[dict] = None
    # Measured resource usage over the timed window
    # (ResourceStats.json_dict()): peak_memory_bytes + energy_joules,
    # None where the backend cannot measure them.
    resources: Optional[dict] = None
    # Stage-graph roofline stamp (benchmarks/roofline_report.py):
    # per-stage {flops, bytes, t_roof_s, pct_roofline, bound} against
    # calibrated machine peaks, so the gated number carries its
    # "% of attainable" context.
    roofline: Optional[dict] = None
    # Variance decomposition over the per-repeat sample lists
    # (repro.bench.stats VarianceDecomposition.json_dict): within- vs
    # between-run share of the run-mean variance — the diagnostic that
    # sizes --repeats per backend. Stamped when repeats > 1.
    variance: Optional[dict] = None
    # Host-transfer telemetry stamp ({stage_copy_s, h2d_s, d2h_s,
    # transfer_frac}; schema TRANSFER_KEYS) for rows whose producer
    # measured the host edge — serving rows carry the keys flat, a
    # summary producer may attach this block.
    transfer: Optional[dict] = None

    def csv(self) -> str:
        """Legacy one-line CSV — format frozen (paper-table parsers)."""
        return (f"{self.name},{self.t_avg_s * 1e6:.1f},"
                f"fps={self.fps:.2f};mbps={self.mbps:.2f};"
                f"J_run_model={self.joules_per_run_model:.4f};"
                f"peak_gb={self.peak_mem_gb:.3f}")

    def json_dict(self) -> dict:
        d = {
            "name": self.name,
            "t_avg_s": self.t_avg_s,
            "fps": self.fps,
            "mbps": self.mbps,
            "joules_per_run_model": self.joules_per_run_model,
            "peak_mem_gb": self.peak_mem_gb,
            "runs": self.runs,
        }
        if self.plan is not None:
            d["plan"] = self.plan
        if self.resources is not None:
            d["resources"] = self.resources
        if self.ci is not None:
            d["ci"] = self.ci
        if self.roofline is not None:
            d["roofline"] = self.roofline
        if self.variance is not None:
            d["variance"] = self.variance
        if self.transfer is not None:
            d["transfer"] = self.transfer
        if self.stats is not None:
            d["latency"] = self.stats.json_dict()
        if self.stage_breakdown:
            d["stages"] = {k: v.json_dict()
                           for k, v in self.stage_breakdown.items()}
        return d

    def ndjson_lines(self) -> List[str]:
        """Telemetry records: summary, per-sample, per-stage lines.

        Every record carries the resolved plan (when one was stamped) so
        each row is independently attributable to an exact
        (backend, variant, exec_map) decision.
        """
        lines = [json.dumps({"kind": "summary", **self.json_dict()})]
        budget = self.stats.budget_s if self.stats else None
        for i, t in enumerate(self.samples_s):
            rec = {"kind": "sample", "name": self.name, "run": i, "t_s": t}
            if budget is not None:
                rec["deadline_missed"] = bool(t > budget)
            if self.plan is not None:
                rec["plan"] = self.plan
            if self.resources is not None:
                rec["resources"] = self.resources
            lines.append(json.dumps(rec))
        for stage, st in self.stage_breakdown.items():
            rec = {"kind": "stage", "name": self.name, "stage": stage,
                   **st.json_dict()}
            if self.plan is not None:
                rec["plan"] = self.plan
            lines.append(json.dumps(rec))
        return lines


def write_ndjson(path: str, results: List["BenchResult"]) -> None:
    with open(path, "w") as f:
        for r in results:
            for line in r.ndjson_lines():
                f.write(line + "\n")


def write_json(path: str, results: List["BenchResult"],
               extra: Optional[dict] = None) -> None:
    doc = {"schema": "repro-bench-v1",
           "results": [r.json_dict() for r in results]}
    if extra:
        doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------


def _timed_samples(fn_j: Callable, args: tuple, *, warmup: int,
                   runs: int, meter=None,
                   start_meter: bool = True) -> List[float]:
    """The paper's §II-E measurement protocol, shared by every bench:
    warm-up iterations excluded from timing, then per-run wall clock with
    device sync (block_until_ready) bracketing each sample. `meter` (a
    ResourceMeter) is started only after the warm-up loop — compilation
    energy/memory never count — and sampled after each run, outside the
    timed bracket, so metering overhead never pollutes the samples.
    `start_meter=False` keeps an already-open metering window running
    (repeat windows share one window; start() would reset its clock)."""
    for _ in range(warmup):
        jax.block_until_ready(fn_j(*args))
    if meter is not None and start_meter:
        meter.start()
    samples: List[float] = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = fn_j(*args)
        jax.block_until_ready(out)
        samples.append(time.perf_counter() - t0)
        if meter is not None:
            meter.sample()
    return samples


def bench_callable(name: str, fn: Callable, args: tuple, *,
                   input_bytes: int, warmup: int = 2, runs: int = 5,
                   repeats: int = 1, utilization: float = 0.5,
                   deadline_s: Optional[float] = None,
                   jitted: Optional[Callable] = None,
                   plan=None) -> BenchResult:
    """Time `fn(*args)` per the paper's execution model.

    Each steady-state run is timed individually (sync'd with
    block_until_ready) so the result carries the full latency
    distribution, not just T_avg. ``repeats`` repeats the whole timed
    window (warm-up is paid once): each repeat is one *run* in the
    Kalibera & Jones sense and the result's ``ci`` stamp is the
    two-level bootstrap confidence interval over the per-repeat means
    (degenerate zero-width at ``repeats=1`` — no noise estimate is
    ever invented). `plan` (a PipelinePlan or its json_dict) is
    stamped into the result and every telemetry record, as is the
    measured `ResourceStats` for the timed window (peak memory +
    incremental energy, None where unsupported).
    """
    from repro.bench.resources import ResourceMeter, devices_of
    from repro.bench.stats import bootstrap_ci, variance_decomposition

    assert repeats >= 1, repeats
    fn_j = jitted if jitted is not None else jax.jit(fn)
    if plan is not None and not isinstance(plan, dict):
        plan = plan.json_dict()

    # Scope the meter to the devices holding the inputs (host-resident
    # args: fall back to all local); started post-warmup by
    # _timed_samples. Later repeats skip the warm-up loop (the program
    # is warm by construction) and keep the same meter running.
    meter = ResourceMeter(devices=devices_of(args))
    run_samples = [_timed_samples(fn_j, args, warmup=warmup, runs=runs,
                                  meter=meter)]
    for _ in range(repeats - 1):
        run_samples.append(_timed_samples(fn_j, args, warmup=0,
                                          runs=runs, meter=meter,
                                          start_meter=False))
    resources = meter.stop()
    samples = [t for rs in run_samples for t in rs]
    t_avg = sum(samples) / len(samples)
    ci = bootstrap_ci(run_samples)

    # peak memory: static analysis of the compiled executable
    peak = 0.0
    try:
        mem = fn_j.lower(*args).compile().memory_analysis()
        peak = (getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)) / 1e9
    except Exception:   # noqa: BLE001 — memory analysis is best-effort
        pass

    e_run = (CHIP_TDP_W - CHIP_IDLE_W) * utilization * t_avg
    # Within/between-run noise split: only claimable from > 1 repeat
    # (a single window has no between-run axis to decompose).
    variance = (variance_decomposition(run_samples).json_dict()
                if repeats > 1 else None)
    return BenchResult(
        name=name, t_avg_s=t_avg, fps=1.0 / t_avg,
        mbps=input_bytes / (t_avg * 1e6),
        joules_per_run_model=e_run, peak_mem_gb=peak, runs=runs,
        samples_s=samples, stats=latency_stats(samples, deadline_s),
        ci=ci.json_dict(), run_samples_s=run_samples,
        plan=plan, resources=resources.json_dict(),
        variance=variance)


def bench_stages(cfg, rf, *, warmup: int = 1,
                 runs: int = 3) -> Dict[str, LatencyStats]:
    """Per-stage timing breakdown of the stage graph.

    Each stage is jitted and synchronized individually on the real
    intermediate tensors (each stage consumes its predecessor's output),
    so the breakdown attributes end-to-end time to demod / beamform /
    head. Individually-synced stage times need not sum to the fused
    end-to-end time — fusion across stage boundaries is exactly what the
    comparison quantifies.
    """
    from repro.core import stages as stages_lib
    from repro.core.pipeline import init_pipeline

    consts = jax.tree.map(jnp.asarray, init_pipeline(cfg))
    out: Dict[str, LatencyStats] = {}
    x = rf
    for name, fn in stages_lib.stage_fns(cfg).items():
        fn_j = jax.jit(fn)
        samples = _timed_samples(fn_j, (consts, x), warmup=warmup, runs=runs)
        out[name] = latency_stats(samples)
        x = fn_j(consts, x)
    return out
