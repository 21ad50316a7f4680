"""Benchmark entry point — one function per paper table.

Prints ``name,us_per_call,derived`` CSV lines:
  table1_* : Table I  — variants x modalities, end-to-end (CPU stand-in)
  table2_* : Table II — portability (CPU measured + TPU predicted)
  table3_* : Table III — throughput context vs prior work
  stream_* : sustained streaming throughput (batched stage-graph engine)
  lm_*     : zoo throughput smoke (tokens/s on reduced configs)

``--json PATH`` writes a BENCH_*.json-compatible results file (name,
t_avg, fps, mbps, percentiles); ``--ndjson PATH`` writes the full
distribution telemetry (summary / per-sample / per-stage records; schema
in EXPERIMENTS.md). ``--deadline-ms`` sets the per-forward-pass frame
budget used for the deadline-miss rate.

``--plan {fixed,heuristic,autotune}`` selects the variant-resolution
policy and ``--variant auto`` hands the choice to the planner
(repro.core.plan); the resolved plan is stamped into every telemetry
record. ``--lowering {xla,pallas}`` pins the beamform stage's operator
lowering (repro.core.lowering) for the table1/stream sections — pallas
sweeps only the variants that register a Pallas kernel, so the
variant x lowering matrix is benchmarkable end to end (interpret mode
off-TPU). ``--fusion {none,fused,both}`` routes the demod+beamform+head
span through the fused Pallas megakernel and ``--precision
{f32,bf16,f16}`` selects the mixed-precision contract tier; cells a
requested sweep cannot run (no fused registration, f32-only xla under
reduced precision, missing lowering) emit explicit
``<cell>,skipped,reason=...`` lines so coverage is auditable. ``--only``
restricts the run to one section (the CI autotune smoke uses
``--only table1 --variant auto --plan autotune``; the CI lowering smoke
uses ``--only table1 --lowering pallas``; the CI fused smoke uses
``--only table1 --fusion both --precision bf16``).

``python -m benchmarks.run [--paper] [--fast] [--json PATH] [--ndjson PATH]``
"""

from __future__ import annotations

import argparse
import sys
import time


def _lm_smoke_bench(runs: int = 3):
    """Reduced-config train-step timing for three representative archs."""
    import jax
    import jax.numpy as jnp

    from repro.configs import TrainConfig, get_smoke
    from repro.data.tokens import TokenDataset
    from repro.models import get_model
    from repro.train import steps as steps_lib

    lines = []
    for arch in ["qwen3-8b", "granite-moe-3b-a800m", "mamba2-130m"]:
        cfg = get_smoke(arch)
        model = get_model(cfg)
        tcfg = TrainConfig()
        state = steps_lib.init_train_state(model, jax.random.PRNGKey(0))
        step = jax.jit(steps_lib.make_train_step(model, tcfg))
        data = TokenDataset(cfg, 4, 128)
        batch = jax.tree.map(jnp.asarray, data.batch_for_step(0))
        state, _ = step(state, batch)  # warmup/compile
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(runs):
            state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
        t = (time.perf_counter() - t0) / runs
        tok_s = 4 * 128 / t
        lines.append(f"lm_train/{arch},{t * 1e6:.1f},tok_per_s={tok_s:.0f}")
    return lines


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="exact paper geometry (slow on CPU)")
    ap.add_argument("--fast", action="store_true",
                    help="fewer timed runs")
    ap.add_argument("--repeats", type=int, default=1,
                    help="repeat each table1 cell's timed window this "
                         "many times; > 1 makes the summary's `ci` "
                         "block a real bootstrap interval over the "
                         "per-repeat means (use >= 3 for gate "
                         "baselines; 1 = degenerate zero-width CI)")
    ap.add_argument("--roofline", action="store_true",
                    help="stamp per-stage roofline context (bytes/FLOPs "
                         "from compiled HLO vs calibrated machine "
                         "peaks) into the table1 summary rows")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write BENCH_*.json-compatible results")
    ap.add_argument("--merge-into", metavar="PATH", default=None,
                    help="merge this run's table1 rows into an existing "
                         "benchmarks.run --json artifact (rows with the "
                         "same name are replaced; the command is "
                         "appended to the file's provenance note) — "
                         "how the committed baseline accumulates its "
                         "pallas/fused cells")
    ap.add_argument("--ndjson", metavar="PATH", default=None,
                    help="write per-sample / per-stage NDJSON telemetry")
    ap.add_argument("--deadline-ms", type=float, default=100.0,
                    help="frame budget per forward pass (miss-rate metric)")
    ap.add_argument("--plan", default="fixed",
                    choices=["fixed", "heuristic", "autotune"],
                    help="variant-resolution policy for the table1/stream "
                         "sections (repro.core.plan)")
    ap.add_argument("--variant", default=None,
                    choices=["dynamic", "cnn", "sparse", "auto"],
                    help="single variant for the table1/stream sections "
                         "(auto = planner picks); default: sweep all "
                         "three. table2's dynamic-vs-cnn comparison is "
                         "fixed by construction")
    ap.add_argument("--lowering", default=None,
                    choices=["xla", "pallas"],
                    help="pin the beamform stage's operator lowering for "
                         "the table1/stream sections (pallas: only the "
                         "variants registering a kernel run; interpret "
                         "mode off-TPU); default: planner-resolved")
    ap.add_argument("--fusion", default="none",
                    choices=["none", "fused", "both"],
                    help="route the demod+beamform+head span through the "
                         "fused Pallas megakernel for the table1/stream "
                         "sections ('both' sweeps unfused and fused per "
                         "cell; cells with no fused registration emit "
                         "explicit skipped lines)")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "f16"],
                    help="kernel compute-precision tier (matmul operands; "
                         "accumulation stays f32). Reduced precision "
                         "needs --fusion fused/both — the xla references "
                         "are f32-only and their cells are skipped")
    ap.add_argument("--only", default="all",
                    choices=["all", "table1", "table2", "table3",
                             "stream", "lm"],
                    help="run a single benchmark section")
    args = ap.parse_args()
    from repro.core.aot import configure_persistent_cache
    configure_persistent_cache()
    runs = 2 if args.fast else 5
    deadline_s = args.deadline_ms / 1e3
    if args.repeats < 1:
        ap.error("--repeats must be >= 1")

    from repro.core import Variant
    variant = Variant(args.variant) if args.variant else None
    if variant == Variant.AUTO and args.plan == "fixed":
        ap.error("--variant auto needs --plan heuristic or autotune")
    if args.lowering == "pallas" and args.variant == "cnn":
        ap.error("no pallas lowering is registered for the cnn beamform "
                 "(the dense matmul IS the MXU formulation)")
    if args.lowering == "xla" and args.fusion in ("fused", "both"):
        ap.error("--lowering xla contradicts --fusion fused: the fused "
                 "span claims the beamform stage with its pallas "
                 "megakernel")
    if args.fusion in ("fused", "both") and args.variant in ("cnn",
                                                             "sparse"):
        ap.error("fused lowerings are registered for the dynamic variant "
                 "only (the megakernel's DAS gather IS the dynamic "
                 "formulation)")

    def on(section):
        return args.only in ("all", section)

    # Fail on unwritable telemetry paths now, not after minutes of timing.
    for path in (args.json, args.ndjson):
        if path:
            open(path, "a").close()
    if args.merge_into:
        open(args.merge_into).close()   # must already exist (run --json)

    from benchmarks import stream_throughput, table1_variants, \
        table2_portability, table3_comparison

    print("name,us_per_call,derived")
    t1 = []
    if on("table1") or on("table3"):   # table3 derives from table1 rows
        t1, t1_skipped = table1_variants.run(
            paper_scale=args.paper, runs=runs, repeats=args.repeats,
            deadline_s=deadline_s, stage_breakdown=True,
            roofline=args.roofline,
            policy=args.plan, variant=variant,
            lowering=args.lowering, fusion=args.fusion,
            precision=args.precision)
        if on("table1"):
            for r in t1:
                print(r.csv())
                sys.stdout.flush()
            # Sweep coverage is auditable from the output alone: every
            # requested cell that did not run says so, with the reason.
            for cell, reason in t1_skipped:
                print(f"{cell},skipped,reason={reason}")
                sys.stdout.flush()
    if on("table2"):
        for line in table2_portability.run(paper_scale=args.paper,
                                           runs=max(runs - 2, 2)):
            print(line)
            sys.stdout.flush()
    if on("table3"):
        for line in table3_comparison.run(t1):
            print(line)
    stream_records = []
    if on("stream"):
        stream_lines, stream_records = stream_throughput.run(
            paper_scale=args.paper, fast=args.fast,
            deadline_ms=args.deadline_ms,
            policy=args.plan, variant=variant,
            lowering=args.lowering,
            # "both" streams the fused program — the new cell; the
            # unfused stream is the long-standing default row.
            fusion="fused" if args.fusion != "none" else "none",
            precision=args.precision)
        for line in stream_lines:
            print(line)
            sys.stdout.flush()
    if on("lm"):
        for line in _lm_smoke_bench():
            print(line)
            sys.stdout.flush()

    if args.json or args.ndjson:
        from repro.bench import write_json, write_ndjson
        if args.json:
            write_json(args.json, t1,
                       extra={"stream": stream_records,
                              "deadline_ms": args.deadline_ms,
                              "plan_policy": args.plan,
                              "provenance": ["python -m benchmarks.run "
                                             + " ".join(sys.argv[1:])]})
        if args.ndjson:
            write_ndjson(args.ndjson, t1, extra_records=stream_records)

    if args.merge_into:
        import json
        with open(args.merge_into) as f:
            doc = json.load(f)
        fresh = {r.name: r.json_dict() for r in t1}
        doc["results"] = ([row for row in doc.get("results", [])
                           if row.get("name") not in fresh]
                          + list(fresh.values()))
        doc.setdefault("provenance", []).append(
            "python -m benchmarks.run " + " ".join(sys.argv[1:]))
        with open(args.merge_into, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
