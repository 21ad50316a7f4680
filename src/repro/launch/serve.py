"""Ultrasound serving CLI: N probe clients through `serve_multitenant`.

Runs open-loop probe clients — alternating B-mode / Doppler configs at
staggered frame rates (`make_mixed_streams`; ``--clients 1`` is a single
probe) — through the one serving loop, the dynamic-batching scheduler
(`repro.launch.scheduler`): per-config queues, same-config-hash
coalescing under a max_batch / max_queue_delay_ms policy, fixed padded
dispatch shapes, AOT warm-start compilation (repro.core.aot), pipelined
dispatch to ``--in-flight`` depth, zero-copy staging rings with a
``--drain`` retirement mode (async copy_to_host_async vs legacy blocking
harvest), and per-stream latency + queue-delay + occupancy +
device-overlap + host-transfer telemetry. ``--devices N`` splits every
batch across N local devices (`repro.core.executor.make_executor`).
Latency semantics: EXPERIMENTS.md and docs/benchmarking-methodology.md;
design and knobs: docs/serving.md.

  PYTHONPATH=src python -m repro.launch.serve --ultrasound \
      --clients 4 --max-batch 4 --queue-delay-ms 5 --deadline-ms 100

  # one probe, every batch split across 2 devices (CPU hosts: force a
  # 2-device mesh first)
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  PYTHONPATH=src python -m repro.launch.serve --ultrasound \
      --clients 1 --devices 2 --max-batch 4

Language-model serving lives in `repro.train.serving`.
"""

from __future__ import annotations

import argparse

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ultrasound", action="store_true",
                    help="serve RF through the dynamic-batching "
                         "scheduler (repro.launch.scheduler; "
                         "docs/serving.md)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-acquisition completion budget")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard each batch across N local devices "
                         "(--max-batch must divide by N; CPU hosts need "
                         "XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--plan", default=None,
                    choices=["fixed", "heuristic", "autotune"],
                    help="variant-resolution policy")
    ap.add_argument("--variant", default=None,
                    choices=["dynamic", "cnn", "sparse", "auto"],
                    help="operator variant (auto = planner)")
    ap.add_argument("--clients", type=int, default=4,
                    help="number of probe clients")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="coalescing ceiling (padded dispatch shape)")
    ap.add_argument("--queue-delay-ms", type=float, default=5.0,
                    help="max wait of the oldest queued frame before a "
                         "partial batch flushes")
    ap.add_argument("--frames", type=int, default=24,
                    help="acquisitions per client")
    ap.add_argument("--in-flight", type=int, default=2,
                    help="dispatch-pipelining depth (1 = synchronous "
                         "launch-block-retire)")
    ap.add_argument("--drain", default="async",
                    choices=["async", "block"],
                    help="host-transfer retirement mode "
                         "(async = copy_to_host_async off the admit "
                         "loop's critical path, block = legacy "
                         "blocking harvest; bit-identical outputs)")
    args = ap.parse_args()
    if not args.ultrasound:
        ap.error("pass --ultrasound (language-model serving: "
                 "python -m repro.train.serving)")
    from repro.core.aot import configure_persistent_cache
    configure_persistent_cache()

    if args.variant == "auto" and args.plan == "fixed":
        ap.error("--variant auto needs --plan heuristic or autotune")

    devices = None
    if args.devices is not None:
        local = jax.local_devices()
        if args.devices < 1:
            ap.error(f"--devices must be >= 1 (got {args.devices})")
        if args.devices > len(local):
            ap.error(f"--devices {args.devices} > {len(local)} local "
                     "devices (CPU hosts: set XLA_FLAGS="
                     "--xla_force_host_platform_device_count="
                     f"{args.devices})")
        devices = local[:args.devices]

    from repro.core import Modality, Variant, tiny_config
    from repro.launch.scheduler import (BatchPolicy,
                                        make_mixed_streams,
                                        serve_multitenant)
    if args.clients < 1:
        ap.error(f"--clients must be >= 1 (got {args.clients})")
    variant = (Variant(args.variant) if args.variant
               else Variant.DYNAMIC)
    cfg = tiny_config(nz=32, nx=32, n_f=8, n_c=16, variant=variant)
    streams = make_mixed_streams(
        args.clients, cfg, cfg.with_(modality=Modality.DOPPLER),
        n_frames=args.frames, deadline_ms=args.deadline_ms)
    stats = serve_multitenant(
        streams,
        policy=BatchPolicy(args.max_batch, args.queue_delay_ms),
        in_flight=args.in_flight, drain=args.drain,
        devices=devices, plan_policy=args.plan)
    lat, qd = stats["latency"], stats["queue_delay"]
    occ = stats["occupancy"]
    print(f"{stats['name']}: {stats['acquisitions']} acquisitions "
          f"({stats['frames']} frames) from {stats['clients']} "
          f"clients in {stats['wall_s']:.2f}s = "
          f"{stats['sustained_mbps']:.2f} MB/s, "
          f"{stats['fps']:.1f} FPS "
          f"(warm-up {stats['warmup_s']:.2f}s ahead of window)")
    ifo = stats["in_flight_occupancy"]
    print(f"overlap: in_flight={stats['in_flight']} "
          f"mean_depth={ifo['mean_depth']:.2f}")
    print(f"transfer: drain={stats['drain']} "
          f"stage_copy={stats['stage_copy_s'] * 1e3:.2f}ms "
          f"h2d={stats['h2d_s'] * 1e3:.2f}ms "
          f"d2h={stats['d2h_s'] * 1e3:.2f}ms "
          f"transfer_frac={stats['transfer_frac']:.3f}")
    print(f"latency: p50={lat['p50_s'] * 1e3:.2f}ms "
          f"p95={lat['p95_s'] * 1e3:.2f}ms "
          f"p99={lat['p99_s'] * 1e3:.2f}ms; queue delay "
          f"p50={qd['p50_s'] * 1e3:.2f}ms "
          f"p95={qd['p95_s'] * 1e3:.2f}ms; "
          f"occupancy={occ['mean_occupancy']:.2f}/"
          f"{occ['max_batch']} (fill={occ['mean_fill']:.2f}, "
          f"full_rate={occ['full_rate']:.2f}); "
          f"miss_rate={stats['deadline_miss_rate']:.3f}")
    for sid, s in stats["per_stream"].items():
        sl = s["latency"]
        print(f"  {sid} [{s['pipeline']}/{s['variant']}"
              f"@{s['arrival_fps']:.0f}fps]: "
              f"p50={sl['p50_s'] * 1e3:.2f}ms "
              f"p95={sl['p95_s'] * 1e3:.2f}ms "
              f"p99={sl['p99_s'] * 1e3:.2f}ms "
              f"miss_rate={s['deadline_miss_rate']:.3f}")
    for key, g in stats["groups"].items():
        plan = g["plan"]
        print(f"  group {key}: streams={g['streams']} "
              f"variant={plan['variant']} "
              f"backend={plan['backend']} "
              f"devices={plan['devices']} "
              f"batches={g['batches']} "
              f"fill={g['occupancy']['mean_fill']:.2f}")


if __name__ == "__main__":
    main()
