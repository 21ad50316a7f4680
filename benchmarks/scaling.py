"""Scaling benchmark: devices x batch -> FPS, MB/s, peak-mem, J/frame.

Sweeps the two scale axes the streaming engine exposes — device count
(`ShardedExecutor` data-parallel mesh) and per-device batch — and emits
one row per cell with sustained throughput, measured peak memory,
measured incremental energy (None off-NVML — the J/frame column the
paper reports "where available"), and scale efficiency against the
single-device baseline at the same per-device batch.

On hosts with one physical device the benchmark forces a 2-device CPU
host mesh via ``XLA_FLAGS=--xla_force_host_platform_device_count=2``
(set *before* JAX initializes; pre-set XLA_FLAGS or
``--force-host-devices 0`` override this), so the scale axis is
exercised anywhere — CI runs exactly that smoke row.

  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  PYTHONPATH=src python -m benchmarks.scaling --fast --ndjson SCALING.ndjson

NDJSON rows are ``{"kind": "scaling", "plan": {...}, "devices": N,
"batch_per_device": B, "fps": ..., "sustained_mbps": ...,
"peak_memory_bytes": ..., "energy_joules": ..., "joules_per_frame": ...,
"speedup_vs_single": ..., "scale_efficiency": ..., ...}`` — schema in
docs/benchmarking-methodology.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _ensure_multidevice_host() -> None:
    """Force >=2 host devices when nothing else configured the count.

    Must run before any jax import; a no-op when XLA_FLAGS already
    forces a count (e.g. CI's explicit env) or on accelerator hosts
    (forcing the *host* platform count never hides GPUs/TPUs).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count=2 {flags}".strip()


def _device_counts(n_local: int) -> list:
    counts, c = {1, n_local}, 2
    while c < n_local:
        counts.add(c)
        c *= 2
    return sorted(counts)


def run(device_counts=None, batch_sizes=(1, 4), *, fast: bool = False,
        deadline_ms: float = 100.0, policy=None, variant=None, cfg=None):
    """Returns (csv lines, NDJSON-ready records), one per (devices, batch).

    ``device_counts=None`` sweeps 1, powers of two, and all local
    devices. Single-device rows run through `serve_ultrasound_stream`
    and seed the scale-efficiency baselines for the sharded rows.
    ``cfg`` overrides the streaming geometry (tests pass tiny configs
    to exercise the emitter cheaply).
    """
    import jax

    from benchmarks.common import stream_config
    from repro.core import Variant
    from repro.launch.serve import (serve_ultrasound_sharded,
                                    serve_ultrasound_stream)

    local = jax.local_devices()
    if device_counts is None:
        device_counts = _device_counts(len(local))
    bad = [d for d in device_counts if d > len(local)]
    if bad:
        raise ValueError(
            f"device counts {bad} exceed {len(local)} local devices "
            "(CPU hosts: XLA_FLAGS=--xla_force_host_platform_device_count=N)")

    if cfg is None:
        cfg = stream_config(False).with_(variant=Variant.DYNAMIC)
    if variant is not None:
        cfg = cfg.with_(variant=variant)   # explicit ask beats cfg's own
    n_batches = 8 if fast else 24
    deadline_s = deadline_ms / 1e3

    lines, records = [], []
    baselines = {}                     # batch_per_device -> single-device fps
    for d in device_counts:
        for b in batch_sizes:
            if d == 1:
                stats = serve_ultrasound_stream(
                    cfg, batch=b, n_batches=n_batches, depth=2,
                    deadline_s=deadline_s, policy=policy)
                stats.update(devices=1, batch_per_device=b, baseline_fps=None,
                             speedup_vs_single=1.0, scale_efficiency=1.0)
                baselines[b] = stats["fps"]
            else:
                stats = serve_ultrasound_sharded(
                    cfg, batch_per_device=b, n_batches=n_batches, depth=2,
                    deadline_s=deadline_s, policy=policy,
                    devices=local[:d], baseline_fps=baselines.get(b))
                if stats["baseline_fps"] is not None:
                    # a sweep without a devices=1 row measures its own
                    # baseline once — reuse it for later device counts
                    baselines.setdefault(b, stats["baseline_fps"])
            res = stats["resources"]
            joules = res["energy_joules"]
            rec = {
                "kind": "scaling",
                "name": stats["name"],
                "plan": stats["plan"],
                "devices": stats["devices"],
                "batch_per_device": b,
                "batch": stats["batch"],
                "n_batches": stats["n_batches"],
                "wall_s": stats["wall_s"],
                "fps": stats["fps"],
                "sustained_mbps": stats["sustained_mbps"],
                "peak_memory_bytes": res["peak_memory_bytes"],
                "memory_source": res["memory_source"],
                "energy_joules": joules,
                "joules_per_frame": (joules / stats["frames"]
                                     if joules is not None else None),
                "speedup_vs_single": stats["speedup_vs_single"],
                "scale_efficiency": stats["scale_efficiency"],
                "latency": stats["latency"].json_dict(),
            }
            records.append(rec)
            peak = res["peak_memory_bytes"]
            jpf = rec["joules_per_frame"]
            peak_mb = f"{peak / 1e6:.1f}" if peak is not None else "n/a"
            j_frame = f"{jpf:.5f}" if jpf is not None else "n/a"
            lines.append(
                f"scaling/{stats['name']},"
                f"{1e6 / stats['acq_per_s']:.1f},"
                f"devices={rec['devices']};batch={b};"
                f"fps={rec['fps']:.2f};mbps={rec['sustained_mbps']:.2f};"
                f"peak_mem_mb={peak_mb};J_per_frame={j_frame};"
                f"scale_eff={rec['scale_efficiency']:.2f}")
    return lines, records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="fewer batches")
    ap.add_argument("--ndjson", metavar="PATH", default=None,
                    help="write one scaling record per line")
    ap.add_argument("--devices", default=None,
                    help="comma-separated device counts (default: 1, "
                         "powers of 2, all local)")
    ap.add_argument("--batch", default="1,4",
                    help="comma-separated per-device batch sizes")
    ap.add_argument("--deadline-ms", type=float, default=100.0)
    ap.add_argument("--plan", default=None,
                    choices=["fixed", "heuristic", "autotune"],
                    help="variant-resolution policy (repro.core.plan)")
    ap.add_argument("--variant", default=None,
                    choices=["dynamic", "cnn", "sparse", "auto"],
                    help="operator variant (auto = planner picks via "
                         "--plan; default: dynamic)")
    ap.add_argument("--force-host-devices", type=int, default=None,
                    help="force N CPU host devices (default: 2 when "
                         "XLA_FLAGS doesn't already force a count; "
                         "0 disables)")
    args = ap.parse_args()

    # Before the first jax import — the host device count locks at init.
    if args.force_host_devices:
        # Appended, not prepended: XLA honors the LAST occurrence, so an
        # explicit CLI request must beat a pre-set env flag.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.force_host_devices}").strip()
    elif args.force_host_devices is None:
        _ensure_multidevice_host()

    device_counts = ([int(x) for x in args.devices.split(",")]
                     if args.devices else None)
    batch_sizes = tuple(int(x) for x in args.batch.split(","))

    # Fail on an unwritable telemetry path now, not after the sweep.
    if args.ndjson:
        open(args.ndjson, "a").close()

    # Imported only after the XLA flags are settled (jax init locks them).
    from repro.core import Variant
    from repro.core.aot import configure_persistent_cache
    configure_persistent_cache()
    variant = Variant(args.variant) if args.variant else None
    if variant == Variant.AUTO and args.plan == "fixed":
        ap.error("--variant auto needs --plan heuristic or autotune")

    lines, records = run(device_counts, batch_sizes, fast=args.fast,
                         deadline_ms=args.deadline_ms, policy=args.plan,
                         variant=variant)
    print("name,us_per_acq,derived")
    for line in lines:
        print(line)
        sys.stdout.flush()

    if args.ndjson:
        with open(args.ndjson, "w") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
