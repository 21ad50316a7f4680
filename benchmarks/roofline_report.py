"""Stage-graph roofline: measured per-stage time vs the analytic floor.

For every schedulable unit of the DSP stage graph (demod / beamform /
head — `repro.core.stages.stage_fns`), the stage's compiled HLO is
costed with `repro.launch.hlo_cost.analyze` (loop-aware FLOPs, fusion
boundary bytes, perfectly-fused ``bytes_min``) and compared against
*calibrated* machine peaks — a timed large matmul for attainable
FLOP/s, a timed large copy for attainable bytes/s, both measured on
this process's actual backend rather than quoted from a datasheet. The
roofline floor for a stage is

    t_roof = max(flops / peak_flops, bytes_min / peak_bytes)

and ``pct_roofline = t_roof / t_measured`` is the fraction of
attainable the measured stage actually achieves (1.0 = on the roof;
the dominant term names the stage ``bound``). `attach_roofline` stamps
this per-stage dict onto a `BenchResult` (schema:
`repro.bench.schema.ROOFLINE_STAGE_KEYS`), so every *gated* benchmark
row carries its "% of attainable" context — a regression verdict can
distinguish "we left the roof" from "the roof moved".

  PYTHONPATH=src python -m benchmarks.roofline_report [--paper] [--json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, Optional

# What would move a stage's dominant term down, per (bound, stage kind).
# Stage names are graph-dependent (fusion collapses spans into
# 'demod+beamform+head'), so advice is keyed by the bound alone with a
# gather-specific override — the beamform DAS gather is the documented
# TPU-hostile access pattern.
ADVICE = {
    "compute": ("on-roof compute: only an algorithmic change (sparser "
                "apodization, lower-rank delay model) buys more"),
    "memory": ("memory-bound: fuse across the stage boundary (the "
               "megakernel path) or drop the precision tier to halve "
               "the traffic"),
    "memory+gather": ("gather-dominated traffic: the dynamic DAS gather "
                      "is the portability cliff — the CNN variant "
                      "trades it for dense MACs"),
}


@dataclasses.dataclass(frozen=True)
class MachinePeaks:
    """Attainable (not datasheet) peaks, measured on this backend."""

    flops_per_s: float
    bytes_per_s: float
    backend: str

    def json_dict(self) -> dict:
        return dataclasses.asdict(self)


_PEAKS_CACHE: Dict[str, MachinePeaks] = {}


def calibrate_peaks(backend: Optional[str] = None,
                    n: int = 1024, copy_mb: int = 64,
                    reps: int = 3) -> MachinePeaks:
    """Measure attainable FLOP/s (large f32 matmul) and bytes/s (large
    copy) once per backend; memoized for the process lifetime.

    Calibrating instead of quoting a datasheet keeps pct_roofline
    meaningful across the heterogeneous CI runners the gate runs on:
    the peak moves with the machine, so the ratio compares like with
    like.
    """
    import jax
    import jax.numpy as jnp

    backend = backend or jax.default_backend()
    cached = _PEAKS_CACHE.get(backend)
    if cached is not None:
        return cached

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype=jnp.float32)
    b = jax.random.normal(key, (n, n), dtype=jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    jax.block_until_ready(mm(a, b))          # compile outside the clock
    best_mm = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(mm(a, b))
        best_mm = min(best_mm, time.perf_counter() - t0)
    flops_per_s = 2.0 * n ** 3 / best_mm

    elems = copy_mb * (1 << 20) // 4
    big = jnp.zeros((elems,), dtype=jnp.float32)
    cp = jax.jit(lambda x: x + 1.0)          # read + write one pass each
    jax.block_until_ready(cp(big))
    best_cp = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(cp(big))
        best_cp = min(best_cp, time.perf_counter() - t0)
    bytes_per_s = 2.0 * elems * 4 / best_cp

    peaks = MachinePeaks(flops_per_s=flops_per_s,
                         bytes_per_s=bytes_per_s, backend=backend)
    _PEAKS_CACHE[backend] = peaks
    return peaks


def stage_costs(cfg) -> Dict[str, "object"]:
    """Per-stage `hlo_cost.Cost` from each stage's *compiled* module.

    Each stage is lowered on the real intermediate tensors (each
    consumes its predecessor's output, exactly like `bench_stages`), so
    the analytic bytes/FLOPs describe the program the timings measured,
    not an idealization of it.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import stages as stages_lib
    from repro.core.pipeline import init_pipeline
    from repro.data import synth_rf
    from repro.launch import hlo_cost

    consts = jax.tree.map(jnp.asarray, init_pipeline(cfg))
    x = jnp.asarray(synth_rf(cfg, seed=0))
    costs: Dict[str, hlo_cost.Cost] = {}
    for name, fn in stages_lib.stage_fns(cfg).items():
        fn_j = jax.jit(fn)
        compiled = fn_j.lower(consts, x).compile()
        costs[name] = hlo_cost.analyze(compiled.as_text())
        x = fn_j(consts, x)
    return costs


def stage_roofline(cfg, measured_s: Dict[str, float], *,
                   peaks: Optional[MachinePeaks] = None) -> Dict[str, dict]:
    """Per-stage roofline rows (schema: ROOFLINE_STAGE_KEYS + extras).

    ``measured_s`` maps stage name -> measured seconds (mean of the
    `bench_stages` breakdown). Stages without a measurement are
    skipped — the stamp only ever annotates numbers that exist.
    """
    peaks = peaks or calibrate_peaks()
    out: Dict[str, dict] = {}
    for name, cost in stage_costs(cfg).items():
        t_meas = measured_s.get(name)
        if t_meas is None or t_meas <= 0.0:
            continue
        t_compute = cost.flops / peaks.flops_per_s
        t_memory = cost.bytes_min / peaks.bytes_per_s
        t_roof = max(t_compute, t_memory)
        bound = "compute" if t_compute >= t_memory else "memory"
        if bound == "memory" and cost.gather_elems > 0.0:
            bound = "memory+gather"
        out[name] = {
            "flops": float(cost.flops),
            "bytes": float(cost.bytes),
            "bytes_min": float(cost.bytes_min),
            "t_measured_s": float(t_meas),
            "t_roof_s": float(t_roof),
            "pct_roofline": float(t_roof / t_meas),
            "bound": bound,
            "peaks": peaks.json_dict(),
        }
    return out


def attach_roofline(res, cfg, *,
                    peaks: Optional[MachinePeaks] = None) -> None:
    """Stamp the per-stage roofline onto a BenchResult in place.

    Uses the result's own `stage_breakdown` means as the measured
    times; a result without a breakdown gets no stamp (the schema
    treats `roofline` as optional, never empty).
    """
    if not res.stage_breakdown:
        return
    measured = {name: st.mean_s
                for name, st in res.stage_breakdown.items()}
    roof = stage_roofline(cfg, measured, peaks=peaks)
    res.roofline = roof or None


def fmt_s(x: float) -> str:
    if x >= 1.0:
        return f"{x:8.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:7.2f}ms"
    return f"{x * 1e6:7.1f}us"


def render(roofline: Dict[str, dict], title: str = "") -> str:
    """Markdown table of one row's per-stage roofline stamp."""
    rows = []
    if title:
        rows.append(f"### {title}")
    rows.append("| stage | GFLOP | MB (min) | t_measured | t_roof | "
                "% roof | bound | note |")
    rows.append("|" + "---|" * 8)
    for name, r in roofline.items():
        note = ADVICE.get(r["bound"], "")
        rows.append(
            f"| {name} | {r['flops'] / 1e9:.3f} | "
            f"{r.get('bytes_min', r['bytes']) / 1e6:.2f} | "
            f"{fmt_s(r['t_measured_s'])} | {fmt_s(r['t_roof_s'])} | "
            f"{100.0 * r['pct_roofline']:5.1f}% | {r['bound']} | "
            f"{note[:70]} |")
    return "\n".join(rows)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Measure the stage graph and report each stage "
                    "against the calibrated machine roofline.")
    ap.add_argument("--paper", action="store_true",
                    help="exact paper geometry (slow on CPU)")
    ap.add_argument("--variant", default="dynamic",
                    choices=["dynamic", "cnn", "sparse"])
    ap.add_argument("--runs", type=int, default=3,
                    help="timed runs per stage")
    ap.add_argument("--json", action="store_true",
                    help="print the raw per-stage dict instead of the "
                         "markdown table")
    args = ap.parse_args()
    from repro.core.aot import configure_persistent_cache
    configure_persistent_cache()

    import jax.numpy as jnp

    from benchmarks.common import bench_config
    from repro.bench import bench_stages
    from repro.core import Variant
    from repro.data import synth_rf

    cfg = bench_config(args.paper).with_(variant=Variant(args.variant))
    rf = jnp.asarray(synth_rf(cfg, seed=0))
    breakdown = bench_stages(cfg, rf, runs=args.runs)
    measured = {name: st.mean_s for name, st in breakdown.items()}
    peaks = calibrate_peaks()
    roof = stage_roofline(cfg, measured, peaks=peaks)
    if args.json:
        print(json.dumps(roof, indent=2, sort_keys=True))
    else:
        print(f"peaks ({peaks.backend}): "
              f"{peaks.flops_per_s / 1e9:.1f} GFLOP/s, "
              f"{peaks.bytes_per_s / 1e9:.1f} GB/s")
        print(render(roof, title=f"{cfg.name}/{args.variant}"))


if __name__ == "__main__":
    main()
