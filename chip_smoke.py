"""Serve the paper's full acquisition through `serve_multitenant` on a TPU.

    python chip_smoke.py              # one chip: five tenant groups
    python chip_smoke.py --chips 4    # batch-sharded serving on four chips,
                                      # compared with one chip

One process, no fallback: it exits non-zero, without a result line, when
JAX finds no TPU. The geometry is `paper_config()` uncut — int16 RF of
(1336, 64, 32) = 5.472 MB per acquisition, a 128x128 grid. Tenants are
open-loop streams:

  * ``auto-{bmode,power_doppler,doppler}`` — `Variant.AUTO` under the
    ``heuristic`` plan policy, the path a user gets by default (on the
    TPU: the CNN formulation, XLA lowerings);
  * ``dynamic-bmode`` — dynamic beamform, lowered to the das_beamform
    Pallas kernel;
  * ``sparse-bmode`` — sparse beamform, lowered to the bsr_spmm Pallas
    kernel.

Each group is served in its own `serve_multitenant` window (AOT-warmed
through `warm_pool`, ``max_batch=4``, ``in_flight=2``, async drain), and
its executors are released before the next window starts: the CNN
operator alone is 2.8 GB per group, and five groups' operators plus
their temporaries do not fit one chip's 16 GB of HBM together.

Every served image is compared, within the per-modality tolerances in
`TOLERANCES`, with two references run on the same chip under
``jax.default_matmul_precision("highest")``: `monolithic_pipeline_fn` of
the group's own variant and constants, and `monolithic_pipeline_fn` of
the dynamic variant (the explicit gather on its own small delay tables),
which shares no operator with the CNN or sparse groups. Each window must
leave its operators resident on the device: bytes in use grow over the
window's starting baseline. With ``--chips 4`` the same streams are
served on ``jax.local_devices()[:4]`` and compared with a one-chip
window of the same frames; every device must hold its own batch shard,
and its bytes in use must grow over its own baseline.

The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

MAX_BATCH = 4
IN_FLIGHT = 2
FRAMES = 8          # acquisitions per stream
POOL = 2            # distinct RF acquisitions per stream (cycled)
FPS = 1000.0        # open-loop arrival rate per stream (batches fill)

# Image-level bounds on |served - reference|, images in [0, 1] (B-mode,
# power Doppler) or [-1, 1] (color Doppler, Nyquist-normalized). Both
# sides compute in f32 with different fusion and summation orders; the
# bounds are set from that, not from the result.
TOLERANCES = {
    # db20_approx takes 16 square roots and then (y - 1) * 2^16: a
    # 1-ulp difference in the envelope becomes up to ~4e-3 in ln, i.e.
    # 0.035 dB = 5.8e-4 of the 60 dB range. Three ulps of headroom.
    "bmode": 2e-3,
    # 10*log10_approx halves that amplification; the 3x3 box smooth
    # averages it. 5e-4 is ~1.7x the one-ulp step (2.9e-4).
    "power_doppler": 5e-4,
    # Phase from atan2 of the lag-one autocorrelation, /pi; f32 order
    # differences move it by ~1e-5. A phase at +-pi (the aliasing
    # boundary) may flip by 2 under a last-bit difference; the 3x3 box
    # smooth spreads a flip as steps of 2/9. Those steps are removed
    # before the bound applies, and at most 0.1% of pixels may carry one.
    "doppler": 5e-4,
}
MAX_WRAPPED_FRACTION = 1e-3


def _say(**kv) -> None:
    print("  " + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def tenant_groups(base):
    """(name, config, expected variant, expected stage lowerings).

    The expected lowerings are written out here from the TPU plan's
    intent, not read back from the planner, so a capability predicate
    that silently turns ``pallas`` into ``xla`` fails the smoke.
    """
    from repro.core.config import Modality, Variant
    groups = []
    for m in (Modality.BMODE, Modality.POWER_DOPPLER, Modality.DOPPLER):
        groups.append((f"auto-{m.value}",
                       base.with_(variant=Variant.AUTO, modality=m), "cnn",
                       {"demod": "xla", "beamform": "xla", m.value: "xla"}))
    for v in (Variant.DYNAMIC, Variant.SPARSE):
        groups.append((f"{v.value}-bmode",
                       base.with_(variant=v, modality=Modality.BMODE),
                       v.value, {"demod": "xla", "beamform": "pallas",
                                 "bmode": "xla"}))
    return groups


def compare(modality: str, got: np.ndarray, want: np.ndarray,
            smooth_kernel: int) -> dict:
    """Max abs / max rel difference, and the verdict under TOLERANCES."""
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    out = {"max_abs": float(np.abs(d).max()),
           "max_rel": float(np.abs(d).max()
                            / max(float(np.abs(want).max()), 1e-30)),
           "wrapped_fraction": 0.0}
    if modality == "doppler":
        q = 2.0 / smooth_kernel ** 2
        residual = (d + q / 2) % q - q / 2
        out["wrapped_fraction"] = float((np.abs(d) > q / 2).mean())
        out["max_abs_unwrapped"] = float(np.abs(residual).max())
        err = out["max_abs_unwrapped"]
    else:
        err = out["max_abs"]
    out["ok"] = bool(err <= TOLERANCES[modality]
                     and out["wrapped_fraction"] <= MAX_WRAPPED_FRACTION)
    return out


def check_tpu_program(expected_lowerings: dict, program) -> bool:
    """Pallas groups must carry a compiled Mosaic kernel."""
    has_kernel = "tpu_custom_call" in program.executable.as_text()
    _say(tpu_custom_call=has_kernel)
    wants_kernel = "pallas" in expected_lowerings.values()
    return has_kernel or not wants_kernel


def serve_window(name: str, cfg, *, seed: int, devices=None,
                 max_batch: int = MAX_BATCH):
    """Warm one group and serve its stream in one window.

    Returns (spec, warm entry, window stats).
    """
    from repro.core.aot import warm_pool
    from repro.launch.scheduler import (BatchPolicy, StreamSpec,
                                        serve_multitenant)
    spec = StreamSpec(stream_id=name, cfg=cfg, fps=FPS, n_frames=FRAMES,
                      seed=seed, pool=POOL)
    pool = warm_pool([spec], max_batch=max_batch, devices=devices,
                     plan_policy="heuristic")
    (entry,) = [pool.get(k) for k in pool.keys()]
    stats = serve_multitenant(
        [spec], policy=BatchPolicy(max_batch=max_batch), in_flight=IN_FLIGHT,
        devices=devices, plan_policy="heuristic", collect_outputs=True,
        pool=pool, drain="async")
    return spec, entry, stats


def check_plan(engine, variant: str, lowerings: dict) -> bool:
    plan = engine.plan
    _say(variant=plan.variant.value,
         stage_lowerings=json.dumps(dict(plan.stage_lowerings)),
         fusion=plan.fusion, backend=plan.backend)
    return (plan.variant.value == variant and plan.fusion == "none"
            and dict(plan.stage_lowerings) == lowerings
            and plan.backend == "tpu")


def bytes_in_use(devices) -> list:
    """Allocator bytes in use per device (not a peak)."""
    from repro.bench.resources import device_memory_stats_list
    stats = device_memory_stats_list(devices)
    if stats is None:
        raise RuntimeError("device memory statistics are unavailable")
    return [u for _, u in stats]


def check_memory(stats, devices, baseline) -> bool:
    """The scheduler's meter reports a number, and the window still
    holds memory of its own on every device.

    The meter's ``peak_memory_bytes`` is the allocator's process-lifetime
    peak only where the window set a new one (``device_memory_stats``);
    after the first CNN window it is bytes in use sampled during the
    window (``device_bytes_in_use``, a lower bound). Bytes in use now,
    with the window's executors alive, against the window's starting
    baseline say what this window itself holds on each device.
    """
    res = stats["resources"]
    held = [u - b for u, b in zip(bytes_in_use(devices), baseline)]
    _say(peak_memory_bytes=res["peak_memory_bytes"],
         memory_source=res["memory_source"],
         held_bytes_per_device=json.dumps(held),
         energy="not measured" if res["energy_joules"] is None
         else res["energy_joules"])
    return (res["peak_memory_bytes"] is not None
            and res["memory_source"] in ("device_memory_stats",
                                         "device_bytes_in_use")
            and all(h > 0 for h in held)
            and res["energy_joules"] is None)


def reference_images(spec, cfg, consts) -> list:
    """`monolithic_pipeline_fn` of ``cfg`` at the highest matmul
    precision, one image per distinct RF of the stream's pool."""
    import jax
    import jax.numpy as jnp
    from repro.core.pipeline import monolithic_pipeline_fn
    from repro.data import synth_rf
    fn = jax.jit(monolithic_pipeline_fn(cfg))
    images = []
    with jax.default_matmul_precision("highest"):
        for i in range(min(POOL, FRAMES)):
            rf = jnp.asarray(synth_rf(spec.cfg, seed=spec.frame_seed(i)))
            images.append(np.asarray(fn(consts, rf)))
    return images


def dynamic_reference_images(spec, engine) -> list:
    """The same frames through the dynamic variant's XLA gather, built
    from its own delay tables: independent of the CNN operator and of the
    sparse blocks."""
    from repro.core.config import Variant
    from repro.core.pipeline import init_pipeline
    if engine.cfg.variant == Variant.DYNAMIC:
        cfg = engine.cfg
    else:
        cfg = engine.cfg.with_(variant=Variant.DYNAMIC, stage_lowerings=())
    return reference_images(spec, cfg, init_pipeline(cfg))


def compare_stream(modality, outputs, references, smooth_kernel, *,
                   against: str) -> bool:
    """Compare every served image; report the worst (a failing one
    first)."""
    cs = [compare(modality, got, references[k % len(references)],
                  smooth_kernel) for k, got in enumerate(outputs)]
    worst = max(cs, key=lambda c: (not c["ok"], c["max_abs"]))
    _say(against=against, tolerance=TOLERANCES[modality], compared=len(cs),
         **{k: v for k, v in worst.items() if k != "ok"},
         within=worst["ok"])
    return all(c["ok"] for c in cs)


def smoke_one_chip(base) -> bool:
    import jax
    devices = jax.devices()[:1]
    ok = True
    for i, (name, cfg, variant, lowerings) in enumerate(tenant_groups(base)):
        print(f"group {name}: window {i + 1} of 5", flush=True)
        t0 = time.perf_counter()
        baseline = bytes_in_use(devices)
        spec, entry, stats = serve_window(name, cfg, seed=i)
        engine, prog = entry.engine, entry.program
        checks = {
            "plan": check_plan(engine, variant, lowerings),
            "kernel": check_tpu_program(lowerings, prog),
            "memory": check_memory(stats, devices, baseline),
        }
        _say(compile_s=f"{prog.compile_s:.3f}",
             warmup_s=f"{prog.warmup_s:.3f}", cache_dir=prog.cache_dir,
             acquisitions=stats["acquisitions"],
             batches=sum(g["batches"] for g in stats["groups"].values()))
        checks["served"] = stats["acquisitions"] == FRAMES
        outputs = stats["outputs"][name]
        refs = reference_images(spec, engine.cfg, engine.consts)
        checks["reference"] = compare_stream(
            cfg.modality.value, outputs, refs, cfg.smooth_kernel,
            against=f"monolithic-{engine.cfg.variant.value}")
        refs = dynamic_reference_images(spec, engine)
        checks["dynamic_reference"] = compare_stream(
            cfg.modality.value, outputs, refs, cfg.smooth_kernel,
            against="monolithic-dynamic")
        failed = sorted(k for k, v in checks.items() if not v)
        _say(group_s=f"{time.perf_counter() - t0:.1f}",
             result="ok" if not failed else f"FAILED {failed}")
        ok = ok and not failed
        del spec, entry, engine, prog, stats, outputs, refs   # free HBM
        gc.collect()
    return ok


def smoke_four_chips(base) -> bool:
    """Batch-sharded serving on four chips vs one chip, same frames."""
    import jax
    devices = jax.local_devices()[:4]
    ok = True
    for i, (name, cfg, variant, lowerings) in enumerate(tenant_groups(base)):
        print(f"group {name}: 4 chips vs 1 chip", flush=True)
        baseline = bytes_in_use(devices)
        _, entry, sharded = serve_window(name, cfg, seed=i, devices=devices)
        engine = entry.engine
        checks = {"plan": check_plan(engine, variant, lowerings),
                  "kernel": check_tpu_program(lowerings, entry.program)}
        _say(compile_s=f"{entry.program.compile_s:.3f}",
             devices=engine.n_devices,
             acquisitions=sharded["acquisitions"])
        # One padded batch through the sharded program, its output held:
        # each device must hold its own row of the batch and of the
        # output, and memory of its own beyond the window's baseline.
        batch = np.zeros((MAX_BATCH,) + cfg.rf_shape, cfg.rf_dtype)
        out = jax.block_until_ready(engine.dispatch_padded(batch, MAX_BATCH))
        shards = {s.device: s.data.shape[0] for s in out.addressable_shards}
        _say(shard_rows=json.dumps([shards.get(d) for d in devices]))
        checks["memory"] = check_memory(sharded, devices, baseline)
        checks["shards"] = (set(shards) == set(devices)
                            and all(n == MAX_BATCH // 4
                                    for n in shards.values()))
        del entry, engine, out
        gc.collect()
        # The one-chip run of the same frames dispatches them one at a
        # time (max_batch=1): the smallest one-chip program to compile.
        _, entry, single = serve_window(name, cfg, seed=i, max_batch=1)
        identical = all(np.array_equal(a, b) for a, b in zip(
            sharded["outputs"][name], single["outputs"][name]))
        _say(vs_one_chip="bit-identical" if identical else "differs")
        checks["vs_one_chip"] = compare_stream(
            cfg.modality.value, sharded["outputs"][name],
            single["outputs"][name], cfg.smooth_kernel, against="one-chip")
        failed = sorted(k for k, v in checks.items() if not v)
        _say(result="ok" if not failed else f"FAILED {failed}")
        ok = ok and not failed
        del entry, sharded, single
        gc.collect()
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve batch-sharded on four chips and compare "
                         "with one chip (that phase only)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0].platform is "
              f"{devices[0].platform!r}); this smoke never falls back to "
              "another backend", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(REPO, "src"))
    import repro.data
    from repro.core.aot import configure_persistent_cache
    from repro.core.config import paper_config
    from repro.kernels.pallas_compat import auto_interpret

    cache = configure_persistent_cache()
    # RF synthesis is deterministic in (config, seed) and takes seconds
    # per paper-size acquisition on the host: memoize it so a window and
    # its comparison share one synthesis.
    repro.data.synth_rf = functools.lru_cache(maxsize=None)(
        repro.data.synth_rf)
    env_cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    print(f"jax {jax.__version__} device_kind={devices[0].device_kind!r} "
          f"devices={len(devices)} compile_cache={cache} "
          f"(JAX_COMPILATION_CACHE_DIR {'set' if env_cache else 'unset'})",
          flush=True)
    if auto_interpret(None):
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    base = paper_config()
    print(f"geometry: rf {base.rf_shape} {base.rf_dtype} "
          f"({base.input_bytes} B/acquisition), grid {base.nz}x{base.nx}; "
          f"{FRAMES} acquisitions per stream, max_batch={MAX_BATCH}, "
          f"in_flight={IN_FLIGHT}; one serve_multitenant window per group, "
          "executors released between windows (HBM)", flush=True)
    t0 = time.perf_counter()
    ok = smoke_four_chips(base) if args.chips == 4 else smoke_one_chip(base)
    print(f"total_s={time.perf_counter() - t0:.1f}", flush=True)
    if not ok:
        print("chip_smoke: FAILED (see the group lines above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
