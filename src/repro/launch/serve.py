"""Serving drivers for both system halves.

LM half — slot-based batching: B fixed slots, each request prefills into
its slot, then all slots decode in lockstep (static shapes — one compiled
program for the whole serving session, the paper's §II-E execution model).
Works on CPU with smoke configs; the production mesh shards slots over
data and heads/experts over model exactly like the dry-run decode cells.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --requests 16 --batch 4 --prompt-len 32 --max-new 32

Ultrasound half — two streaming drivers over the stage-graph executors
(repro.core.executor), both fed by a synthetic acquisition source and
both reporting *sustained* MB/s / FPS under queue pressure plus the
completion-latency distribution (p50/p95/p99, jitter, deadline misses —
semantics in EXPERIMENTS.md and docs/benchmarking-methodology.md):

  * `serve_ultrasound_stream` — single-device `BatchedExecutor` loop;
    up to `depth` batches stay in flight against the async dispatch
    queue.
  * `serve_ultrasound_sharded` — multi-device `ShardedExecutor` loop:
    every dispatch splits its batch across the mesh, each device gets
    its own in-flight queue of output shards (per-device completion
    intervals -> per-device latency stats), and the stats report
    aggregated throughput plus scale efficiency against a single-device
    baseline (speedup_vs_single = sharded FPS / single-device FPS;
    scale_efficiency = speedup / n_devices).

Both stamp the resolved `PipelinePlan` (with device topology) and the
measured `ResourceStats` (peak memory; energy where NVML exists, else
None) into their stats dict, so streaming telemetry carries the same
attribution and resource columns as the offline tables.

Invariants: warm-up round trips never count toward the timed window;
throughput is computed over wall clock of the whole window (sustained,
not best-case); the sharded loop only dispatches device-aligned batches
(batch_per_device * n_devices), so no host-side remainder slicing ever
re-synchronizes the stream.

Multi-tenant serving (`--multitenant`) runs N open-loop probe clients
— alternating B-mode / Doppler configs at staggered frame rates —
through the dynamic-batching scheduler (`repro.launch.scheduler`):
per-config queues, same-config-hash coalescing under a
max_batch / max_queue_delay_ms policy, fixed padded dispatch shapes,
AOT warm-start compilation (repro.core.aot), pipelined dispatch to
``--in-flight`` depth, zero-copy staging rings with a ``--drain``
retirement mode (async copy_to_host_async vs legacy blocking harvest),
per-stream latency + queue-delay + occupancy + device-overlap +
host-transfer telemetry. Design and knobs: docs/serving.md.

  PYTHONPATH=src python -m repro.launch.serve --ultrasound \
      --batch 4 --batches 32 --depth 2 --deadline-ms 50

  # multi-device (CPU hosts: force a 2-device mesh first)
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  PYTHONPATH=src python -m repro.launch.serve --ultrasound \
      --devices 2 --batch 4 --batches 32 --depth 2

  # 4 mixed-modality tenants through the dynamic-batching scheduler
  PYTHONPATH=src python -m repro.launch.serve --ultrasound \
      --multitenant --clients 4 --max-batch 4 --queue-delay-ms 5 \
      --deadline-ms 100
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke
from repro.data.batches import synth_train_batch
from repro.models import get_model
from repro.train import steps as steps_lib


def serve_session(cfg, *, requests: int, batch: int, prompt_len: int,
                  max_new: int, seed: int = 0):
    """Process `requests` prompts in slot batches of `batch`.

    Returns (generated tokens array, stats dict).
    """
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    prefill_step = jax.jit(steps_lib.make_prefill_step(model))
    serve_step = jax.jit(steps_lib.make_serve_step(model))

    outs = []
    n_steps = 0
    # perf_counter, like every other timing window in this module: an
    # NTP step mid-session would skew (or negate) a time.time() wall.
    t0 = time.perf_counter()
    max_len = prompt_len + max_new + 1
    for r0 in range(0, requests, batch):
        bsz = min(batch, requests - r0)
        prompt = synth_train_batch(cfg, bsz, prompt_len, seed=seed + r0)
        tok_next, cache = prefill_step(params, prompt)
        tok = tok_next[:, None]
        if cfg.family == "audio":
            # enc-dec prefill returns a decode-ready cache (BOS consumed)
            lengths = jnp.ones((bsz,), jnp.int32)
        else:
            # decoder-only: extend the prefilled cache to serving length
            cache = _grow_cache(model, cache, max_len)
            lengths = jnp.full((bsz,), prompt_len, jnp.int32)

        gen = [np.asarray(tok)]
        for _ in range(max_new):
            tok, cache, lengths = serve_step(params, tok, cache, lengths)
            gen.append(np.asarray(tok))
            n_steps += 1
        outs.append(np.concatenate(gen, axis=1))

    wall = time.perf_counter() - t0
    toks = sum(o.size for o in outs)
    stats = {"wall_s": wall, "tokens": toks,
             "tok_per_s": toks / max(wall, 1e-9),
             "decode_steps": n_steps}
    return np.concatenate(outs, axis=0)[:requests], stats


def _grow_cache(model, cache, max_len: int):
    """Pad every leaf's sequence axis to max_len.

    The sequence axis is identified structurally via cache_specs
    (seq_sharded=True labels it "seq"); leaves without one (SSM/conv
    states) pass through untouched.
    """
    specs = model.cache_specs(seq_sharded=True)

    def grow(ax, a):
        if "seq" not in ax:
            return a
        i = ax.index("seq")
        if a.shape[i] >= max_len:
            return a
        pad = [(0, 0)] * a.ndim
        pad[i] = (0, max_len - a.shape[i])
        return jnp.pad(a, pad)

    return jax.tree.map(
        grow, specs, cache,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, (str, tuple)) for a in x))


class SyntheticAcquisitionSource:
    """Host-side RF batch source (stand-in for a probe front end).

    Pre-generates a pool of distinct (batch, n_l, n_c, n_f) acquisitions
    (a probe sweep) and cycles it — generation cost stays out of the
    streaming window, while every dispatch still uploads a fresh
    host->device buffer like a real acquisition stream would.

    Frame seeds come from `repro.data.traces.seed_space`, so sources
    with different base seeds occupy disjoint seed spaces: the old
    additive ``seed + b * batch + i`` scheme made two sources whose
    base seeds differed by less than ``pool * batch`` stream
    byte-identical RF.
    """

    def __init__(self, cfg, batch: int, *, pool: int = 4, seed: int = 0):
        from repro.data import seed_space, synth_rf
        self.cfg = cfg
        self.batch = batch
        self._pool = [
            np.stack([synth_rf(
                cfg, seed=seed_space("source", seed, b * batch + i))
                for i in range(batch)])
            for b in range(pool)]
        self._i = 0

    def next(self) -> np.ndarray:
        rf = self._pool[self._i % len(self._pool)]
        self._i += 1
        return rf


def serve_ultrasound_stream(cfg, *, batch: int = 4, n_batches: int = 32,
                            depth: int = 2, pool: int = 4, seed: int = 0,
                            deadline_s=None, source=None,
                            plan=None, policy=None) -> dict:
    """Stream RF batches through the stage-graph engine, `depth` in flight.

    Dispatches are asynchronous; the loop only blocks on the *oldest*
    in-flight batch once `depth` are queued, so host-side source work and
    device compute overlap. Completion-to-completion intervals form the
    latency samples; the per-batch deadline budget is
    ``batch * deadline_s`` (deadline_s is the per-acquisition frame
    budget — see EXPERIMENTS.md).

    `plan` / `policy` resolve the executor's variant and exec_map
    (repro.core.plan); the resolved plan is stamped into the stats so
    streaming telemetry stays attributable. ``Variant.AUTO`` configs
    resolve heuristically when neither is given.

    Returns a stats dict with sustained throughput and a LatencyStats.
    """
    from repro.bench.harness import latency_stats
    from repro.bench.resources import ResourceMeter, devices_of
    from repro.core.executor import BatchedExecutor

    if batch < 1 or n_batches < 1 or depth < 1:
        raise ValueError(
            f"batch, n_batches, depth must be >= 1 "
            f"(got {batch}, {n_batches}, {depth})")

    engine = BatchedExecutor(cfg, plan=plan, policy=policy)
    cfg = engine.cfg                 # plan-resolved (concrete variant)
    if source is None:
        source = SyntheticAcquisitionSource(cfg, batch, pool=pool, seed=seed)

    # Meter built BEFORE warm-up so the NVML idle baseline sees the
    # board cold; scoped to the engine's device — a sharded neighbor's
    # buffers on other devices must not pollute this single-device stamp.
    meter = ResourceMeter(devices=devices_of(engine.consts))

    # warm-up: compile + one full round trip, excluded from timing
    jax.block_until_ready(engine(jnp.asarray(source.next())))
    meter.start()
    in_flight: collections.deque = collections.deque()
    intervals = []
    t0 = time.perf_counter()
    last = t0
    for _ in range(n_batches):
        in_flight.append(engine(jnp.asarray(source.next())))
        while len(in_flight) >= depth:
            jax.block_until_ready(in_flight.popleft())
            now = time.perf_counter()
            intervals.append(now - last)
            last = now
            meter.sample()
    while in_flight:
        jax.block_until_ready(in_flight.popleft())
        now = time.perf_counter()
        intervals.append(now - last)
        last = now
        meter.sample()
    wall = time.perf_counter() - t0

    acqs = n_batches * batch
    budget = batch * deadline_s if deadline_s is not None else None
    return {
        "name": f"stream/{cfg.name}/{cfg.variant.value}/b{batch}",
        "batch": batch, "n_batches": n_batches, "depth": depth,
        "plan": engine.plan.json_dict(),
        "wall_s": wall,
        "acquisitions": acqs,
        "frames": acqs * cfg.n_f,
        "sustained_mbps": acqs * cfg.input_bytes / (wall * 1e6),
        "fps": acqs * cfg.n_f / wall,
        "acq_per_s": acqs / wall,
        "latency": latency_stats(intervals, budget_s=budget),
        "resources": meter.stop().json_dict(),
    }


def serve_ultrasound_sharded(cfg, *, batch_per_device: int = 4,
                             n_batches: int = 32, depth: int = 2,
                             pool: int = 4, seed: int = 0,
                             deadline_s=None, devices=None, source=None,
                             plan=None, policy=None,
                             baseline_fps=None,
                             measure_baseline: bool = True) -> dict:
    """Stream RF through the `ShardedExecutor`, per-device in-flight queues.

    Every dispatch carries ``batch_per_device * n_devices`` acquisitions,
    split across the mesh by the executor's batch sharding. The output
    stays sharded; each device's output shard goes onto that device's
    own in-flight queue, and once ``depth`` dispatches are queued the
    loop blocks on the *oldest shard of each device* — so per-device
    completion intervals (and stragglers) are observable individually
    while dispatch stays global and asynchronous.

    Scale efficiency: ``baseline_fps`` is the single-device sustained
    FPS at the same per-device batch (measured via
    `serve_ultrasound_stream` when not supplied and
    ``measure_baseline``); the stats report
    ``speedup_vs_single = fps / baseline_fps`` and
    ``scale_efficiency = speedup_vs_single / n_devices`` (1.0 = perfect
    linear scaling). Both are None when no baseline is available.

    Returns a stats dict shaped like `serve_ultrasound_stream`'s plus
    ``devices``, ``per_device_latency``, ``speedup_vs_single``,
    ``scale_efficiency``; ``plan`` carries the mesh topology and
    ``resources`` the measured peak memory / energy.
    """
    from repro.bench.harness import latency_stats
    from repro.bench.resources import ResourceMeter
    from repro.core.executor import ShardedExecutor

    if batch_per_device < 1 or n_batches < 1 or depth < 1:
        raise ValueError(
            f"batch_per_device, n_batches, depth must be >= 1 "
            f"(got {batch_per_device}, {n_batches}, {depth})")

    engine = ShardedExecutor(cfg, devices=devices, plan=plan, policy=policy)
    cfg = engine.cfg                 # plan-resolved (concrete variant)
    n_dev = engine.n_devices
    batch = batch_per_device * n_dev

    # Meter built first — before the single-device baseline stream and
    # the warm-up run heat the boards — so the NVML idle baseline
    # actually sees them cold.
    meter = ResourceMeter(devices=engine.devices)

    if baseline_fps is None and measure_baseline:
        # Same resolved decisions, single-device topology stamp: the
        # baseline's telemetry must not claim the mesh it didn't use.
        baseline_plan = dataclasses.replace(
            engine.plan, devices=1, mesh_shape=None)
        baseline_fps = serve_ultrasound_stream(
            cfg, batch=batch_per_device, n_batches=n_batches, depth=depth,
            pool=pool, seed=seed, deadline_s=deadline_s,
            plan=baseline_plan, policy=None)["fps"]

    if source is None:
        source = SyntheticAcquisitionSource(cfg, batch, pool=pool, seed=seed)

    # warm-up: compile + one full sharded round trip, excluded from timing
    jax.block_until_ready(engine.dispatch(jnp.asarray(source.next())))

    dev_index = {d: i for i, d in enumerate(engine.devices)}
    queues = [collections.deque() for _ in engine.devices]
    dev_intervals = [[] for _ in engine.devices]
    intervals = []                     # global: all devices of a dispatch

    meter.start()
    t0 = time.perf_counter()
    last_dev = [t0] * n_dev
    last_global = t0

    def drain_one():
        """Retire the oldest in-flight shard of every device.

        Completion times are observed by polling shard readiness, not
        by blocking in device order — a straggling device must not
        inflate the recorded completion time of devices that already
        finished (its stall shows up in *its own* interval only).
        """
        nonlocal last_global
        pending = {i: q.popleft() for i, q in enumerate(queues)}
        while pending:
            for i in list(pending):
                sh = pending[i]
                ready = sh.is_ready() if hasattr(sh, "is_ready") else True
                if ready:
                    jax.block_until_ready(sh)     # settled: returns at once
                    now = time.perf_counter()
                    dev_intervals[i].append(now - last_dev[i])
                    last_dev[i] = now
                    del pending[i]
            if pending:
                time.sleep(1e-4)
        now = time.perf_counter()
        intervals.append(now - last_global)
        last_global = now
        meter.sample()

    for _ in range(n_batches):
        out = engine.dispatch(jnp.asarray(source.next()))
        for sh in out.addressable_shards:
            queues[dev_index[sh.device]].append(sh.data)
        while len(queues[0]) >= depth:
            drain_one()
    while queues[0]:
        drain_one()
    wall = time.perf_counter() - t0

    acqs = n_batches * batch
    fps = acqs * cfg.n_f / wall
    budget = batch * deadline_s if deadline_s is not None else None
    speedup = fps / baseline_fps if baseline_fps else None
    return {
        "name": (f"stream/{cfg.name}/{cfg.variant.value}"
                 f"/b{batch_per_device}xd{n_dev}"),
        "devices": n_dev,
        "batch_per_device": batch_per_device,
        "batch": batch, "n_batches": n_batches, "depth": depth,
        "plan": engine.plan.json_dict(),
        "wall_s": wall,
        "acquisitions": acqs,
        "frames": acqs * cfg.n_f,
        "sustained_mbps": acqs * cfg.input_bytes / (wall * 1e6),
        "fps": fps,
        "acq_per_s": acqs / wall,
        "latency": latency_stats(intervals, budget_s=budget),
        "per_device_latency": {
            str(d): latency_stats(dev_intervals[i]).json_dict()
            for i, d in enumerate(engine.devices)},
        "baseline_fps": baseline_fps,
        "speedup_vs_single": speedup,
        "scale_efficiency": (speedup / n_dev
                             if speedup is not None else None),
        "resources": meter.stop().json_dict(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ultrasound", action="store_true",
                    help="stream RF through the batched stage-graph engine")
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--batches", type=int, default=32,
                    help="ultrasound: RF batches to stream")
    ap.add_argument("--depth", type=int, default=2,
                    help="ultrasound: max batches in flight")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="ultrasound: per-acquisition frame budget")
    ap.add_argument("--devices", type=int, default=None,
                    help="ultrasound: shard each batch across N local "
                         "devices (--batch becomes per-device; with "
                         "--multitenant, --max-batch must divide by N; "
                         "CPU hosts need XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--plan", default=None,
                    choices=["fixed", "heuristic", "autotune"],
                    help="ultrasound: variant-resolution policy")
    ap.add_argument("--variant", default=None,
                    choices=["dynamic", "cnn", "sparse", "auto"],
                    help="ultrasound: operator variant (auto = planner)")
    ap.add_argument("--multitenant", action="store_true",
                    help="ultrasound: N mixed-modality clients through "
                         "the dynamic-batching scheduler "
                         "(repro.launch.scheduler; docs/serving.md)")
    ap.add_argument("--clients", type=int, default=4,
                    help="multitenant: number of probe clients")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="multitenant: coalescing ceiling (padded "
                         "dispatch shape)")
    ap.add_argument("--queue-delay-ms", type=float, default=5.0,
                    help="multitenant: max wait of the oldest queued "
                         "frame before a partial batch flushes")
    ap.add_argument("--frames", type=int, default=24,
                    help="multitenant: acquisitions per client")
    ap.add_argument("--in-flight", type=int, default=2,
                    help="multitenant: dispatch-pipelining depth (1 = "
                         "synchronous launch-block-retire)")
    ap.add_argument("--drain", default="async",
                    choices=["async", "block"],
                    help="multitenant: host-transfer retirement mode "
                         "(async = copy_to_host_async off the admit "
                         "loop's critical path, block = legacy "
                         "blocking harvest; bit-identical outputs)")
    args = ap.parse_args()
    from repro.core.aot import configure_persistent_cache
    configure_persistent_cache()

    if args.variant == "auto" and args.plan == "fixed":
        ap.error("--variant auto needs --plan heuristic or autotune")

    def cli_devices():
        """Validated --devices -> prefix of local devices (None = unset).

        Shared by the sharded-stream and multitenant paths so the range
        checks (and the XLA_FLAGS hint) cannot drift between them.
        """
        if args.devices is None:
            return None
        local = jax.local_devices()
        if args.devices < 1:
            ap.error(f"--devices must be >= 1 (got {args.devices})")
        if args.devices > len(local):
            ap.error(f"--devices {args.devices} > {len(local)} local "
                     "devices (CPU hosts: set XLA_FLAGS="
                     "--xla_force_host_platform_device_count="
                     f"{args.devices})")
        return local[:args.devices]

    if args.multitenant:                 # implies --ultrasound
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
            devices=cli_devices(), plan_policy=args.plan)
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
                  f"batches={g['batches']} "
                  f"fill={g['occupancy']['mean_fill']:.2f}")
        return

    if args.ultrasound:
        from repro.core import Variant, tiny_config
        cfg = tiny_config(nz=32, nx=32, n_f=8, n_c=16)
        if args.variant is not None:
            cfg = cfg.with_(variant=Variant(args.variant))
        deadline_s = (args.deadline_ms / 1e3
                      if args.deadline_ms is not None else None)
        devices = cli_devices()
        if devices is not None:
            stats = serve_ultrasound_sharded(
                cfg, batch_per_device=args.batch, n_batches=args.batches,
                depth=args.depth, policy=args.plan,
                devices=devices, deadline_s=deadline_s)
        else:
            stats = serve_ultrasound_stream(
                cfg, batch=args.batch, n_batches=args.batches,
                depth=args.depth, policy=args.plan, deadline_s=deadline_s)
        lat = stats["latency"]
        plan = stats["plan"]
        print(f"plan: policy={plan['policy']} backend={plan['backend']} "
              f"variant={plan['variant']} exec_map={plan['exec_map']} "
              f"devices={plan['devices']} ({plan['provenance']})")
        print(f"{stats['name']}: {stats['acquisitions']} acquisitions "
              f"({stats['frames']} frames) in {stats['wall_s']:.2f}s = "
              f"{stats['sustained_mbps']:.2f} MB/s, {stats['fps']:.1f} FPS; "
              f"p50={lat.p50_s * 1e3:.2f}ms p95={lat.p95_s * 1e3:.2f}ms "
              f"p99={lat.p99_s * 1e3:.2f}ms jitter={lat.jitter_s * 1e3:.2f}ms "
              f"miss_rate={lat.miss_rate:.3f}")
        res = stats.get("resources") or {}
        peak = res.get("peak_memory_bytes")
        joules = res.get("energy_joules")
        print("resources: "
              f"peak_mem={peak / 1e6:.1f}MB ({res.get('memory_source')}) "
              if peak is not None else "resources: peak_mem=n/a ",
              end="")
        print(f"energy={joules:.2f}J" if joules is not None
              else "energy=n/a (no NVML)")
        if stats.get("speedup_vs_single") is not None:
            print(f"scaling: {stats['devices']} devices, "
                  f"baseline_fps={stats['baseline_fps']:.1f}, "
                  f"speedup={stats['speedup_vs_single']:.2f}x, "
                  f"scale_efficiency={stats['scale_efficiency']:.2f}")
        return

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    out, stats = serve_session(
        cfg, requests=args.requests, batch=args.batch,
        prompt_len=args.prompt_len, max_new=args.max_new)
    print(f"served {args.requests} requests: {stats['tokens']} tokens in "
          f"{stats['wall_s']:.2f}s = {stats['tok_per_s']:,.0f} tok/s")


if __name__ == "__main__":
    main()
