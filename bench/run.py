"""Run one cell of the benchmark once, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--trace-out events.json]

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
One run:

  1. set-up: builds the probes' tenants from the configuration file and
     their arrival schedule from the traffic file, makes each probe's
     pool of RF acquisitions on the device from ``--seed`` (bench/rf.py),
     and warms the program's executors (`repro.core.aot.warm_pool`: its
     constants and its one padded program per group, from the compile
     cache in ``.jax_cache`` or ``JAX_COMPILATION_CACHE_DIR``);
  2. the window: one call of the program's serving entry,
     `repro.launch.scheduler.serve_multitenant`, which admits every
     acquisition at its scheduled instant, batches, dispatches and
     drains. The benchmark keeps its own clock: the window opens when
     the serving loop starts its meter, and each batch's images land
     when the loop brings them to the host. No compilation may happen
     in the window; the run fails without a result if one does;
  3. after the window: reads the device's peak memory, frees the
     program's state, computes the plain reference (bench/reference.py)
     of every distinct acquisition on the device, and compares every
     served image with it (bench/check.py);
  4. prints the cell's end-to-end metrics (``--trace 0``) or, from a run
     under the profiler, its per-layer metrics (``--trace 1``), each
     read by ``bench/metrics/<name>.py``.

The last line of standard output is the result as one JSON object; the
numbers compared, each with its limit, are the last lines of standard
error and the result's last key. Without a TPU, or with fewer chips than
the cell asks for, the run exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if sys.path and os.path.abspath(sys.path[0] or ".") == BENCH_DIR:
    sys.path.pop(0)          # bench/trace.py must not shadow a module
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, spec, traffic, work  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Compiles:
    """Counts tracing, lowering and compilation events while active."""

    def __init__(self):
        self.on = False
        self.count = 0

    def _listen(self, event, duration_s, **kw):
        if self.on and event in COMPILE_EVENTS:
            self.count += 1

    @contextlib.contextmanager
    def active(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self.on = True
        try:
            yield self
        finally:
            self.on = False
            jax.monitoring.unregister_event_duration_listener(self._listen)


class Landed:
    """One dispatch's device output. Records the instant the serving
    loop brings its images to the host (its ``np.asarray``)."""

    def __init__(self, out, log, i, traced):
        self._out, self._log, self._i, self._traced = out, log, i, traced
        self._host = None

    def __array__(self, dtype=None, copy=None):
        if self._host is None:
            self._host = np.asarray(self._out)
            self._log[self._i] = time.perf_counter()
            if self._traced:
                from bench import trace
                trace.marker(trace.LANDED)
        return self._host if dtype is None else self._host.astype(dtype)

    def __getattr__(self, name):
        if name.startswith("__"):    # numpy must convert through __array__
            raise AttributeError(name)
        return getattr(self._out, name)


def watch_dispatches(engine, log, traced) -> None:
    """Wrap the executor's launch so each output reports its landing."""
    launch = engine.dispatch_staged

    def dispatch_staged(dev_batch, pad_to):
        log.append(None)
        return Landed(launch(dev_batch, pad_to), log, len(log) - 1, traced)

    engine.dispatch_staged = dispatch_staged


def build_specs(cell, seed: int, seconds: float):
    """The probes' tenants and their arrival schedule."""
    from repro.core.config import Modality, UltrasoundConfig, Variant
    from repro.data.traces import TraceArrival
    from repro.launch.scheduler import StreamSpec
    conf = cell.config
    pipe = dict(conf["pipeline"])
    base = UltrasoundConfig(**{k: v for k, v in pipe.items()
                               if k != "variant"},
                            variant=Variant(pipe["variant"]))
    probes = conf["probes"]
    sched = traffic.schedule(cell.traffic, len(probes), seconds,
                             conf["serving"]["max_batch"])
    rates = traffic.rates(cell.traffic, len(probes))
    specs = [StreamSpec(stream_id=f"probe{i}",
                        cfg=base.with_(modality=Modality(m)),
                        fps=rates[i], n_frames=len(sched[i]), seed=seed,
                        pool=conf["rf"]["pool_per_probe"],
                        arrival=TraceArrival(tuple(sched[i])))
             for i, m in enumerate(probes)]
    return specs, sched


def compare(specs, outputs, pool_rf, pipe):
    """Every served image against the plain reference of its own
    acquisition (stream, seq mod pool); the worst numbers per modality.
    The reference runs once per distinct acquisition, on the device."""
    from bench import reference
    ref = reference.Reference(pipe, "highest")
    per_image = []
    for s in specs:
        m = min(s.pool, s.n_frames)
        modality = s.cfg.modality.value
        want = [ref(modality, pool_rf[s.frame_seed(k)]) for k in range(m)]
        for seq, img in enumerate(outputs.get(s.stream_id, [])):
            per_image.append((modality, check.image_numbers(
                modality, img, want[seq % m], s.cfg.smooth_kernel)))
    return check.worst(per_image)


def run_cell(cell, seed: int, seconds: float, traced: bool, *, devices,
             trace_out=None, t_start: float = T_START, log=print,
             engine_hook=None):
    """One run of ``cell`` on ``devices``; returns the result dict.

    ``engine_hook(engine)``, if given, is applied to every warmed
    executor before the window (the control and the tests replace the
    timed path through it). Raises RuntimeError when something compiled
    inside the window or the window could not be observed.
    """
    import jax
    import repro.bench.resources as resources
    import repro.data
    from repro.core.aot import configure_persistent_cache, warm_pool
    from repro.launch.scheduler import BatchPolicy, serve_multitenant
    from bench import rf
    from bench import trace as tr

    cache = configure_persistent_cache()
    conf, serving = cell.config, cell.config["serving"]
    pipe = conf["pipeline"]
    specs, sched = build_specs(cell, seed, seconds)

    t_rf0 = time.perf_counter()
    # RF: every probe's pool, made on the device in one call, served
    # through the program's RF seam.
    slots = [(s, k) for s in specs for k in range(min(s.pool, s.n_frames))]
    seeds = [s.frame_seed(k) for s, k in slots]
    t_phase = time.perf_counter()
    pool_rf = dict(zip(seeds, rf.make_pool(pipe, conf["rf"], seeds)))
    rf_s = time.perf_counter() - t_phase

    def synth_rf(cfg, seed=0, **_):
        return pool_rf[seed]

    dev_arg = list(devices) if len(devices) > 1 else None
    t_phase = time.perf_counter()
    pool = warm_pool(specs, max_batch=serving["max_batch"], devices=dev_arg,
                     plan_policy=serving["plan_policy"])
    warm_s = time.perf_counter() - t_phase
    landings: list = []
    for key in pool.keys():
        if engine_hook is not None:
            engine_hook(pool.get(key).engine)
        watch_dispatches(pool.get(key).engine, landings, traced)

    opened: list = []
    meter_cls = resources.ResourceMeter

    class WindowMeter(meter_cls):
        def start(self):
            super().start()
            opened.append(time.perf_counter())
            if traced:
                tr.marker(tr.OPEN)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    compiles = Compiles()
    policy = BatchPolicy(max_batch=serving["max_batch"],
                         max_queue_delay_ms=serving["max_queue_delay_ms"])
    program_rf = repro.data.synth_rf
    resources.ResourceMeter, repro.data.synth_rf = WindowMeter, synth_rf
    try:
        with compiles.active(), (tr.Profile(trace_dir) if traced
                                 else contextlib.nullcontext()):
            stats = serve_multitenant(
                specs, policy=policy, in_flight=serving["in_flight"],
                devices=dev_arg, plan_policy=serving["plan_policy"],
                collect_outputs=True, pool=pool, drain=serving["drain"])
    finally:
        resources.ResourceMeter, repro.data.synth_rf = meter_cls, program_rf
    if len(opened) != 1 or not landings or None in landings:
        raise RuntimeError("the window's opening or a batch's landing was "
                           "not observed")
    if len(landings) != len(stats["dispatch_order"]):
        raise RuntimeError(f"{len(landings)} dispatches observed, the "
                           f"program reports {len(stats['dispatch_order'])}")
    log(f"compilations_in_window={compiles.count}")
    if compiles.count:
        raise RuntimeError(f"{compiles.count} compilation events inside "
                           "the window")

    t_open = opened[0]
    window_s = max(landings) - t_open
    arrival = {(s.stream_id, k): sched[i][k]
               for i, s in enumerate(specs) for k in range(s.n_frames)}
    latencies = [landings[b] - t_open - arrival[(sid, seq)]
                 for b, batch in enumerate(stats["dispatch_order"])
                 for sid, seq in batch]
    served = {s.stream_id: len(stats["outputs"].get(s.stream_id, []))
              for s in specs}
    n_served = sum(served.values())
    attempted = sum(s.n_frames for s in specs)
    n_f = specs[0].cfg.n_f
    order = np.argsort([arrival[(sid, seq)] for batch in
                        stats["dispatch_order"] for sid, seq in batch])
    quarter = max(1, len(order) // 4)
    lat = np.asarray(latencies)[order]
    log(f"setup: before_rf_s={t_rf0 - t_start!r} rf_s={rf_s!r} "
        f"warm_s={warm_s!r} compile_s="
        f"{[round(pool.get(k).program.compile_s, 3) for k in pool.keys()]} "
        f"latency_p50_first_quarter_ms="
        f"{float(np.median(lat[:quarter])) * 1e3!r} "
        f"latency_p50_last_quarter_ms="
        f"{float(np.median(lat[-quarter:])) * 1e3!r}")
    log(f"window_s={window_s!r} served={n_served} attempted={attempted} "
        f"acq_per_s={n_served / window_s!r} "
        f"fps={n_served * n_f / window_s!r} "
        f"batches={len(landings)} setup_s={t_open - t_start!r} "
        f"compile_cache={cache}")

    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices]
    for key in pool.keys():
        pool.get(key).engine.consts = None
    del pool
    gc.collect()

    summary = None
    if traced:
        ev = tr.events(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if trace_out:
            with open(trace_out, "w") as f:
                json.dump(ev, f)
        summary = tr.summarize(ev)

    t_ref = time.perf_counter()
    missing = attempted - n_served
    numbers = compare(specs, stats["outputs"], pool_rf, pipe)
    log("numbers " + " ".join(f"{k}={v!r}" for k, v in
                               sorted(numbers.items())))
    ok, compared = check.judge(numbers, conf["limits"], missing)
    log(f"reference_s={time.perf_counter() - t_ref!r}")

    ctx = types.SimpleNamespace(
        stats=stats, window_s=window_s, latencies_s=latencies,
        served=n_served,
        # The bytes of the RF the benchmark made, never the program's
        # own count of its input.
        served_bytes=sum(served[s.stream_id]
                         * pool_rf[s.frame_seed(0)].nbytes for s in specs),
        setup_s=t_open - t_start,
        flops=sum(served[s.stream_id]
                  * work.flops_per_acquisition(pipe, s.cfg.modality.value)
                  for s in specs),
        trace=summary, device_kind=devices[0].device_kind,
        chips=len(devices))
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(max(mem))}
    result = {"correct": ok, "attempted": attempted, "failed": missing,
              "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="write the trace's reduced events (JSON) here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    cell = spec.cell(spec.load_benchmark(), args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (jax.devices()[0].platform is "
              f"{devices[0].platform!r}); this benchmark measures the chip "
              "only", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.kernels.pallas_compat import auto_interpret
    if auto_interpret(None):
        print("bench: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 2
    print(f"jax {jax.__version__} device_kind={devices[0].device_kind!r} "
          f"workload={args.workload} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace}", flush=True)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices=devices[:cell.chips],
                          trace_out=args.trace_out,
                          log=lambda s: print(s, flush=True))
    except RuntimeError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
