"""Multi-tenant serving benchmark: clients x batch policy sweep.

Beyond the single-probe streaming rows: N open-loop tenants (alternating
B-mode / Color-Doppler configs at staggered frame rates — the mixed
traffic a real scanner fleet produces) contend for one device through
the dynamic-batching scheduler (`repro.launch.scheduler`), and each
(clients, policy) cell reports aggregate sustained MB/s / FPS plus the
distributions throughput claims hide: per-stream completion latency
p50/p95/p99, queue delay, batch occupancy / fill rate, and the
per-stream deadline-miss rate.

The policy axis is the Jouppi trade: ``max_batch=1`` is
dispatch-on-arrival (best latency, no amortization), larger
``max_batch`` with a ``max_queue_delay_ms`` bound buys occupancy with
bounded waiting. ``--in-flight`` adds the pipelining axis: depth 1 is
the synchronous launch-block-retire loop, depth >= 2 overlaps host
coalescing with device execution (the record's ``device_busy_frac`` /
``overlap_frac`` columns show where the win comes from). Determinism
is not on any axis — the scheduler oracle test pins every cell's
outputs to the per-frame monolithic reference bit-for-bit at every
depth.

All cells share one `repro.core.aot.WarmPool`: each distinct
(config-group, max_batch) program AOT-compiles once for the whole
sweep, warm cost lands in the first cell that needs it (``warmup_s``)
and later cells stamp ``warm_source="pool"``.

``--drain`` adds the host-transfer axis: ``async`` (default) retires
batches through `copy_to_host_async` staging-ring drains so D2H rides
off the admit loop's critical path; ``block`` is the legacy
detect-block-harvest retirement. Both are bit-identical — the drain
mode only moves *when* host copies happen — so a block/async cell pair
on the same geometry isolates the transfer-overlap win
(``transfer_frac`` / ``acq_per_s``) with no confound.

``--profile`` adds the load axis (repro.data.traces): ``steady`` is
the historical uniform open-loop schedule (reproduced bit-identically —
same arrivals, same trace_sha256), ``burst`` / ``diurnal_ramp`` /
``churn`` / ``adversarial`` generate seeded arrival traces and replay
them through `make_trace_streams`; ``--trace PATH`` replays a recorded
repro-trace-v1 file instead. Every record stamps ``load_profile`` and
``trace_sha256`` — the profile is part of the gate's cell identity, so
a burst row never gates against a steady baseline.

NDJSON rows are ``{"kind": "multitenant", ...}`` — schema enforced by
`repro.bench.schema` (CI validates the smoke artifact with exactly that
module):

  PYTHONPATH=src python -m benchmarks.multitenant --fast \
      --profile steady,burst --ndjson MT.ndjson
  PYTHONPATH=src python -m repro.bench.schema MT.ndjson \
      --require-kind multitenant
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional, Sequence, Tuple

# (max_batch, max_queue_delay_ms) cells: dispatch-on-arrival baseline,
# then two coalescing depths at a realistic wait bound.
DEFAULT_POLICIES = ((1, 0.0), (4, 5.0), (8, 10.0))
DEFAULT_CLIENTS = (1, 2, 4)


def run(client_counts: Sequence[int] = DEFAULT_CLIENTS,
        policies: Sequence[Tuple[int, float]] = DEFAULT_POLICIES, *,
        in_flights: Sequence[int] = (2,), fast: bool = False,
        repeats: int = 1, drains: Sequence[str] = ("async",),
        deadline_ms: Optional[float] = 100.0, base_fps: float = 120.0,
        plan_policy: Optional[str] = None, cfg_bmode=None,
        cfg_doppler=None, variant=None,
        profiles: Sequence[str] = ("steady",),
        trace_path: Optional[str] = None
        ) -> Tuple[List[str], List[dict]]:
    """Returns (csv lines, NDJSON-ready records), one per sweep cell.

    ``cfg_bmode`` / ``cfg_doppler`` override the tenant geometries
    (tests and the CI smoke pass tiny configs); the default is the
    streaming benchmark geometry with the Doppler head swapped in for
    odd tenants. ``in_flights`` sweeps the dispatch-pipelining depth.
    ``base_fps`` sets the fastest tenant's open-loop arrival rate —
    raise it far above the service rate to measure the device-bound
    throughput ceiling (where the in-flight overlap win is visible in
    ``acq_per_s`` rather than only in ``device_busy_frac``).

    ``repeats`` serves each cell's window that many times (the shared
    `WarmPool` means only the first window anywhere pays AOT cost) and
    replaces the record's degenerate ``acq_per_s_ci`` /
    ``device_busy_frac_ci`` / ``overlap_frac_ci`` with two-level
    bootstrap CIs over the per-window values — the intervals the
    statistical regression gate compares. The point metrics then
    report the across-window means; the distribution blocks (latency,
    occupancy) stay those of the last window.

    ``drains`` sweeps the host-transfer retirement mode
    (``async`` / ``block``, part of the record name and the gate's
    cell identity); outputs are bit-identical across the axis.

    ``profiles`` sweeps load scenarios (`repro.data.traces.PROFILES`):
    ``steady`` drives the historical `make_mixed_streams` uniform
    schedule directly (bit-identical arrivals and trace_sha256 to the
    pre-profile benchmark); other profiles generate a seeded trace per
    (profile, client count) and replay it through `make_trace_streams`.
    ``trace_path`` replays one recorded repro-trace-v1 file instead —
    the trace then fixes the tenant count and ``client_counts`` is
    ignored.
    """
    from benchmarks.common import stream_config
    from repro.bench.stats import bootstrap_ci
    from repro.core import Modality, Variant
    from repro.core.aot import WarmPool
    from repro.data.traces import PROFILES, generate_trace, load_trace
    from repro.launch.scheduler import (BatchPolicy, make_mixed_streams,
                                        make_trace_streams,
                                        serve_multitenant)

    assert repeats >= 1, repeats
    for d in drains:
        if d not in ("async", "block"):
            raise ValueError(f"unknown drain mode {d!r} "
                             f"(expected 'async' or 'block')")
    for p in profiles:
        if p not in PROFILES:
            raise ValueError(f"unknown profile {p!r} "
                             f"(expected one of {PROFILES})")

    v = variant if variant is not None else Variant.DYNAMIC
    if cfg_bmode is None:
        cfg_bmode = stream_config(False).with_(variant=v)
    if cfg_doppler is None:
        cfg_doppler = cfg_bmode.with_(modality=Modality.DOPPLER)
    n_frames = 8 if fast else 24

    replay = None
    if trace_path is not None:
        replay = load_trace(trace_path)
        client_counts = (len(replay.streams),)
        profiles = (replay.profile or "trace",)

    pool = WarmPool()
    lines, records = [], []
    for n in client_counts:
        for profile in profiles:
            if replay is not None:
                streams = make_trace_streams(
                    replay, cfg_bmode, cfg_doppler,
                    deadline_ms=deadline_ms)
            elif profile == "steady":
                # The historical uniform path, untouched: steady cells
                # must reproduce the pre-profile benchmark exactly.
                streams = make_mixed_streams(n, cfg_bmode, cfg_doppler,
                                             base_fps=base_fps,
                                             n_frames=n_frames,
                                             deadline_ms=deadline_ms)
            else:
                trace = generate_trace(profile, n_streams=n,
                                       n_frames=n_frames,
                                       base_fps=base_fps, seed=0)
                streams = make_trace_streams(
                    trace, cfg_bmode, cfg_doppler,
                    deadline_ms=deadline_ms)
            for max_batch, delay_ms in policies:
                for in_flight in in_flights:
                    for drain in drains:
                        windows = [serve_multitenant(
                            streams,
                            policy=BatchPolicy(max_batch, delay_ms),
                            in_flight=in_flight, drain=drain,
                            plan_policy=plan_policy,
                            pool=pool, load_profile=profile)
                            for _ in range(repeats)]
                        stats = windows[-1]
                        if repeats > 1:
                            for metric in ("acq_per_s",
                                           "device_busy_frac",
                                           "overlap_frac"):
                                ci = bootstrap_ci(
                                    [w[metric] for w in windows])
                                stats[metric] = ci.mean
                                stats[metric + "_ci"] = ci.json_dict()
                        rec = {"kind": "multitenant", **stats}
                        records.append(rec)
                        lat, occ = stats["latency"], stats["occupancy"]
                        worst_p95 = max(
                            s["latency"]["p95_s"]
                            for s in stats["per_stream"].values()
                            if s["latency"] is not None)
                        lines.append(
                            f"{stats['name']},"
                            f"{1e6 / stats['acq_per_s']:.1f},"
                            f"clients={n};profile={profile};"
                            f"max_batch={max_batch};"
                            f"delay_ms={delay_ms:g};"
                            f"in_flight={in_flight};drain={drain};"
                            f"mbps={stats['sustained_mbps']:.2f};"
                            f"fps={stats['fps']:.2f};"
                            f"p50_ms={lat['p50_s'] * 1e3:.2f};"
                            f"worst_stream_p95_ms={worst_p95 * 1e3:.2f};"
                            f"fill={occ['mean_fill']:.2f};"
                            f"busy={stats['device_busy_frac']:.2f};"
                            f"overlap={stats['overlap_frac']:.2f};"
                            f"xfer={stats['transfer_frac']:.2f};"
                            f"dropped={stats['dropped']};"
                            f"miss_rate={stats['deadline_miss_rate']:.3f}")
    return lines, records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer frames per tenant")
    ap.add_argument("--repeats", type=int, default=1,
                    help="serving windows per cell; > 1 replaces the "
                         "degenerate acq_per_s_ci with a bootstrap CI "
                         "over the per-window acq/s (the statistical "
                         "gate's interval; use >= 3 for baselines)")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny test geometry (CI smoke)")
    ap.add_argument("--clients", default=None,
                    help="comma-separated tenant counts "
                         f"(default {','.join(map(str, DEFAULT_CLIENTS))})")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="single policy cell: coalescing ceiling")
    ap.add_argument("--queue-delay-ms", type=float, default=5.0,
                    help="single policy cell: max queue delay "
                         "(with --max-batch)")
    ap.add_argument("--in-flight", default="2",
                    help="comma-separated dispatch-pipelining depths to "
                         "sweep (1 = synchronous; default 2)")
    ap.add_argument("--drain", default="async",
                    help="comma-separated host-transfer retirement "
                         "modes to sweep (async = staging-ring "
                         "copy_to_host_async drain, block = legacy "
                         "blocking harvest; default async)")
    ap.add_argument("--deadline-ms", type=float, default=100.0,
                    help="per-frame completion budget (miss-rate metric)")
    ap.add_argument("--base-fps", type=float, default=120.0,
                    help="fastest tenant's open-loop arrival rate; far "
                         "above the service rate = device-bound cells "
                         "(overlap win shows in acq_per_s)")
    ap.add_argument("--profile", default="steady",
                    help="comma-separated load profiles to sweep "
                         "(steady, burst, diurnal_ramp, churn, "
                         "adversarial — repro.data.traces; steady is "
                         "the historical uniform schedule)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="replay a recorded repro-trace-v1 file instead "
                         "of generating profiles (the trace fixes the "
                         "tenant count; overrides --profile/--clients)")
    ap.add_argument("--ndjson", metavar="PATH", default=None,
                    help="write one multitenant record per line")
    ap.add_argument("--merge-into", metavar="PATH", default=None,
                    help="merge the sweep's records into an existing "
                         "benchmarks.run --json artifact under its "
                         "'multitenant' key (regenerates the "
                         "benchmarks/gate.py baseline; the command is "
                         "appended to the file's provenance note)")
    ap.add_argument("--plan", default=None,
                    choices=["fixed", "heuristic", "autotune"],
                    help="variant-resolution policy (repro.core.plan)")
    ap.add_argument("--variant", default=None,
                    choices=["dynamic", "cnn", "sparse", "auto"],
                    help="operator variant (auto = planner picks via "
                         "--plan; default: dynamic)")
    args = ap.parse_args()
    from repro.core.aot import configure_persistent_cache
    configure_persistent_cache()

    # Fail on an unwritable telemetry path now, not after the sweep.
    if args.ndjson:
        open(args.ndjson, "a").close()
    if args.merge_into:
        open(args.merge_into).close()   # must already exist (run --json)

    from repro.core import Modality, Variant, tiny_config
    variant = Variant(args.variant) if args.variant else None
    if variant == Variant.AUTO and args.plan == "fixed":
        ap.error("--variant auto needs --plan heuristic or autotune")

    cfg_bmode = cfg_doppler = None
    if args.tiny:
        v = variant if variant is not None else Variant.DYNAMIC
        cfg_bmode = tiny_config(variant=v)
        cfg_doppler = cfg_bmode.with_(modality=Modality.DOPPLER)

    client_counts = ([int(x) for x in args.clients.split(",")]
                     if args.clients else DEFAULT_CLIENTS)
    policies = ([(args.max_batch, args.queue_delay_ms)]
                if args.max_batch is not None else DEFAULT_POLICIES)
    in_flights = [int(x) for x in args.in_flight.split(",")]

    lines, records = run(client_counts, policies, in_flights=in_flights,
                         fast=args.fast, repeats=args.repeats,
                         drains=tuple(args.drain.split(",")),
                         deadline_ms=args.deadline_ms,
                         base_fps=args.base_fps, plan_policy=args.plan,
                         cfg_bmode=cfg_bmode, cfg_doppler=cfg_doppler,
                         variant=variant,
                         profiles=tuple(args.profile.split(",")),
                         trace_path=args.trace)
    print("name,us_per_acq,derived")
    for line in lines:
        print(line)
        sys.stdout.flush()

    if args.ndjson:
        with open(args.ndjson, "w") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")

    if args.merge_into:
        with open(args.merge_into) as f:
            doc = json.load(f)
        doc["multitenant"] = records
        doc.setdefault("provenance", []).append(
            "python -m benchmarks.multitenant " + " ".join(sys.argv[1:]))
        with open(args.merge_into, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
