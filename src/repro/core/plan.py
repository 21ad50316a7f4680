"""Backend-aware pipeline planning (variant auto-selection).

The paper's portability contract says ONE source tree runs on every
backend — but *which* operator formulation (dynamic / cnn / sparse) wins
is backend-dependent (paper Table I; ConvBench), so leaving the variant
choice to the user reintroduces exactly the hardware-specific decision
the contract is supposed to eliminate. This module closes that gap:

    plan = plan_pipeline(cfg.with_(variant=Variant.AUTO), policy=...)

produces a frozen `PipelinePlan` — the resolved variant plus every other
execution decision (exec_map, donation, per-stage jit toggles) and full
provenance — which `UltrasoundPipeline`, `BatchedExecutor`, the serve
loop, and the benchmark drivers all accept and stamp into telemetry, so
every reported MB/s–FPS row is attributable to an exact
(backend, variant, exec_map) decision.

Three policies:

  * ``fixed``     — today's behavior: honor ``cfg.variant`` verbatim.
    Refuses ``Variant.AUTO`` (a fixed plan has nothing to resolve it with).
  * ``heuristic`` — resolve AUTO from a per-backend preference registry
    encoding the structure of the paper's Table I: gather-friendly
    backends (cpu/gpu) run V1-dynamic fastest; matmul-unit backends
    (tpu) want the dense CNN formulation. Deterministic and free.
  * ``autotune``  — measure every concrete variant end-to-end for a few
    warm runs through the bench harness and pick the winner. Memoized per
    (config-hash-ignoring-variant, backend) so sweeps pay the search once.

Planning is two-level. The variant decides the *math formulation*; the
planner then resolves one operator *lowering* per stage (``xla`` or a
Pallas kernel — the per-stage registry in repro.core.lowering) into
``PipelinePlan.stage_lowerings``. Explicit ``cfg.stage_lowerings``
entries are always honored (and refused loudly when unregistered for
the resolved variant); unspecified stages consult the per-backend
lowering preference table under fixed/heuristic, or are measured per
stage via the bench harness's stage breakdown under autotune — memoized
alongside the variant memo so sweeps pay each search once.

The registries and the autotune memos are process-global; all are plain
dicts so tests (and future multi-backend sweeps) can inspect or reset
them.

Public API
----------
`plan_pipeline(cfg, policy=...)`        — resolve a config into a plan.
`PipelinePlan`                          — the frozen result; consumed by
    `UltrasoundPipeline`, `BatchedExecutor`, `ShardedExecutor`,
    `serve_ultrasound_stream` and stamped (``json_dict()``) into every
    telemetry record. ``with_devices(n, mesh_shape)`` derives the
    multi-device form the `ShardedExecutor` stamps — ``devices``/
    ``mesh_shape`` keep every NDJSON row attributable to the exact
    device topology that produced it.
`register_backend_preference`           — extend the heuristic registry.
`clear_autotune_memo`                   — reset the measurement memo.

Invariants: plans are frozen; ``variant`` is always concrete (never
AUTO); ``matches(cfg)`` gates consumption so a plan's telemetry stamp
can never be attached to a pipeline with different geometry; the
planner never decides ``exec_map`` or ``devices`` — it records what the
config/executor chose.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.core.config import UltrasoundConfig, Variant, config_hash

POLICIES = ("fixed", "heuristic", "autotune")

# Paper Table I structure, keyed on jax.default_backend(): backends with a
# fast irregular-access path prefer the explicit-gather formulation; systolic
# matmul-unit backends prefer the dense CNN re-expression. Extendable via
# register_backend_preference (e.g. a future backend measured differently).
BACKEND_VARIANT_PREFERENCE: Dict[str, Variant] = {
    "cpu": Variant.DYNAMIC,
    "gpu": Variant.DYNAMIC,
    "cuda": Variant.DYNAMIC,
    "rocm": Variant.DYNAMIC,
    "tpu": Variant.CNN,
}
# Unknown backends get the portable dense formulation (runs everywhere the
# paper tested, never pathological — the conservative Table I read).
DEFAULT_PREFERENCE = Variant.CNN

CONCRETE_VARIANTS = (Variant.DYNAMIC, Variant.CNN, Variant.SPARSE)

# (geometry key, explicit stage_lowerings, backend, runs, warmup)
#   -> tuple of (variant value, t_avg_s)
_AUTOTUNE_MEMO: Dict[Tuple, Tuple[Tuple[str, float], ...]] = {}

# Per-backend lowering preference, consulted for stages the config leaves
# open under fixed/heuristic: backend -> {(stage, variant value or None)
# -> lowering name}. Anything unlisted runs the "xla" reference. The TPU
# rows encode the kernels' design intent — the fused DAS kernel keeps the
# dynamic gather in VMEM, and the scalar-prefetched BSR SpMM is the
# paper's V3-on-TPU story — gated by each lowering's capability
# predicate, so an unsatisfiable tile constraint falls back to xla.
BACKEND_LOWERING_PREFERENCE: Dict[str, Dict[Tuple[str, Optional[str]],
                                            str]] = {
    "tpu": {
        ("beamform", Variant.DYNAMIC.value): "pallas",
        ("beamform", Variant.SPARSE.value): "pallas",
    },
}

# (resolved-config key sans lowerings, explicit stage_lowerings, backend,
#  runs, warmup) -> tuple of ("stage:lowering", t_avg_s)
_LOWERING_MEMO: Dict[Tuple, Tuple[Tuple[str, float], ...]] = {}

# Pixel-tile candidates the autotune policy probes for the fused
# megakernel's block size (cfg.fusion_block left open). The per-stage
# autotune memo generalizes to fusion groups: probes key into
# _LOWERING_MEMO as "<group>:<name>@bp<bp>" so sweeps pay the search
# once per geometry.
FUSION_BLOCK_CANDIDATES = (64, 128, 256)


def register_backend_preference(backend: str, variant: Variant) -> None:
    """Extend/override the heuristic registry (measured, not assumed)."""
    if not variant.concrete:
        raise ValueError("preference must be a concrete variant")
    BACKEND_VARIANT_PREFERENCE[backend] = variant


def register_lowering_preference(backend: str, stage: str,
                                 variant: Optional[Variant],
                                 lowering_name: str) -> None:
    """Extend/override the per-backend lowering preference table."""
    BACKEND_LOWERING_PREFERENCE.setdefault(backend, {})[
        (stage, variant.value if variant is not None else None)] = \
        lowering_name


def clear_autotune_memo() -> None:
    _AUTOTUNE_MEMO.clear()
    _LOWERING_MEMO.clear()


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """A fully resolved execution plan for one pipeline config.

    Everything a consumer needs to build and run the pipeline — and
    everything telemetry needs to attribute a throughput number to an
    exact decision. ``variant`` is always concrete (never AUTO).
    """

    variant: Variant
    exec_map: str                                  # "vmap" | "map"
    donate: Optional[bool]                         # None = backend default
    jit_stages: Tuple[Tuple[str, bool], ...]       # (stage name, jit?) pairs
    backend: str                                   # jax.default_backend()
    policy: str                                    # member of POLICIES
    config_key: str                                # hash of the REQUESTED cfg
    geometry_key: str                              # hash sans planned axes
    provenance: str                                # how the variant was chosen
    # One resolved operator lowering per stage of the graph ("xla" or
    # "pallas"; repro.core.lowering) — concretize() writes these into
    # cfg.stage_lowerings so the executed config, its canonical hash
    # (multi-tenant grouping), and every telemetry stamp agree.
    stage_lowerings: Tuple[Tuple[str, str], ...] = ()
    # Fusion/precision contract stamp. ``fusion``/``precision`` echo the
    # config's request (both are geometry-key axes — a fused plan can
    # never be consumed by an unfused pipeline or vice versa);
    # ``fusion_group`` names the claimed span ("demod+beamform+bmode");
    # ``fusion_block`` is the planner-DECIDED pixel-tile size (None =
    # kernel default), excluded from the geometry key like the other
    # planned axes.
    fusion: str = "none"
    precision: str = "f32"
    fusion_group: Optional[str] = None
    fusion_block: Optional[int] = None
    autotune_t_s: Optional[Tuple[Tuple[str, float], ...]] = None
    # Per-stage lowering timings when autotune had to measure (pairs of
    # ("stage:lowering", t_avg_s)); None when the table decided.
    lowering_t_s: Optional[Tuple[Tuple[str, float], ...]] = None
    # Device topology the plan executes on. 1/None = single-device (the
    # BatchedExecutor default); the ShardedExecutor stamps its mesh via
    # with_devices() so every telemetry record names its topology.
    devices: int = 1
    mesh_shape: Optional[Tuple[Tuple[str, int], ...]] = None
    # Serving-mode stamps (with_serving): how the program got warm
    # (None = live first-dispatch compile, "aot" = ahead-of-time
    # lower+compile this window — repro.core.aot — possibly against the
    # persistent compilation cache, "pool" = reused an already-warm
    # WarmPool executor) and the scheduler's in-flight dispatch depth
    # (None = offline / one-at-a-time semantics). Neither is a
    # planning decision — like devices/mesh_shape they record the
    # execution context so every NDJSON row stays attributable.
    warm_start: Optional[str] = None
    in_flight: Optional[int] = None

    def __post_init__(self):
        assert self.variant.concrete, "plan must carry a concrete variant"
        assert self.devices >= 1, "plan needs at least one device"
        if self.fusion == "fused":
            assert self.fusion_group, \
                "a fused plan must name its fusion group"
        else:
            assert self.fusion_group is None and self.fusion_block is None, \
                "an unfused plan cannot carry fusion_group/fusion_block"
        jitted = {name for name, _ in self.jit_stages}
        lowered = {name for name, _ in self.stage_lowerings}
        assert lowered == jitted, (
            f"plan must resolve a lowering for every stage of the graph "
            f"(got {sorted(lowered)}, graph has {sorted(jitted)})")
        if self.mesh_shape is not None:
            n = 1
            for _, extent in self.mesh_shape:
                n *= extent
            assert n == self.devices, \
                f"mesh_shape {self.mesh_shape} != devices {self.devices}"

    def with_devices(self, devices: int,
                     mesh_shape: Optional[Tuple[Tuple[str, int], ...]] = None
                     ) -> "PipelinePlan":
        """This plan, stamped with the executing device topology.

        The decision axes (variant/exec_map/policy/provenance) are
        unchanged — sharding scales the plan out, it never re-plans.
        """
        if mesh_shape is None:
            mesh_shape = (("data", devices),)
        return dataclasses.replace(self, devices=devices,
                                   mesh_shape=mesh_shape)

    def with_serving(self, *, warm_start: Optional[str],
                     in_flight: Optional[int]) -> "PipelinePlan":
        """This plan, stamped with its serving execution context.

        Like `with_devices`, a pure telemetry stamp: the scheduler
        records how the group's program was warmed ("aot" / "pool")
        and the window's in-flight dispatch depth so overlap numbers
        stay attributable. Decision axes unchanged.
        """
        if in_flight is not None and in_flight < 1:
            raise ValueError(f"in_flight must be >= 1 (got {in_flight})")
        return dataclasses.replace(self, warm_start=warm_start,
                                   in_flight=in_flight)

    def matches(self, cfg: UltrasoundConfig) -> bool:
        """True iff this plan was built for ``cfg``'s geometry.

        Variant, exec_map, and stage_lowerings are the axes the plan
        itself decides, so they are excluded — a plan built on an AUTO
        config matches the resolved config and vice versa. Everything
        else differing means the plan's decision (and its telemetry
        stamp) belongs to some other pipeline.
        """
        return self.geometry_key == _geometry_key(cfg)

    def concretize(self, cfg: UltrasoundConfig) -> UltrasoundConfig:
        """The requested config with every planned decision applied."""
        return cfg.with_(variant=self.variant, exec_map=self.exec_map,
                         stage_lowerings=self.stage_lowerings,
                         fusion_block=self.fusion_block)

    def stage_jit(self, stage_name: str) -> bool:
        return dict(self.jit_stages).get(stage_name, True)

    def json_dict(self) -> dict:
        d = {
            "policy": self.policy,
            "backend": self.backend,
            "variant": self.variant.value,
            "exec_map": self.exec_map,
            "donate": self.donate,
            "jit_stages": {k: v for k, v in self.jit_stages},
            "stage_lowerings": {k: v for k, v in self.stage_lowerings},
            "fusion": self.fusion,
            "precision": self.precision,
            "fusion_group": self.fusion_group,
            "fusion_block": self.fusion_block,
            "config_key": self.config_key,
            "geometry_key": self.geometry_key,
            "provenance": self.provenance,
            "devices": self.devices,
            "mesh_shape": ([[name, extent] for name, extent
                            in self.mesh_shape]
                           if self.mesh_shape is not None else None),
            "warm_start": self.warm_start,
            "in_flight": self.in_flight,
        }
        if self.autotune_t_s is not None:
            d["autotune_t_s"] = {k: v for k, v in self.autotune_t_s}
        if self.lowering_t_s is not None:
            d["lowering_t_s"] = {k: v for k, v in self.lowering_t_s}
        return d


def _geometry_key(cfg: UltrasoundConfig) -> str:
    # fusion/precision stay IN the key (user-requested program axes: the
    # scheduler must never batch fused and unfused — or f32 and bf16 —
    # acquisitions into one program); fusion_block joins the excluded
    # planner-decided axes.
    return config_hash(cfg,
                       exclude=("variant", "exec_map", "stage_lowerings",
                                "fusion_block"))


def _default_measure(cfg: UltrasoundConfig, variant: Variant, *,
                     runs: int, warmup: int) -> float:
    """T_avg of one concrete variant, via the paper's bench methodology."""
    import jax.numpy as jnp

    from repro.bench.harness import bench_callable
    from repro.core import lowering as lowering_lib
    from repro.core.pipeline import UltrasoundPipeline
    from repro.data import synth_rf

    c = cfg.with_(variant=variant)
    # Explicit lowering entries the probed variant does not register
    # (e.g. a pallas beamform while probing CNN) must not crash the
    # probe; the final plan still validates against the winner.
    c = c.with_(stage_lowerings=lowering_lib.supported_subset(c))
    pipe = UltrasoundPipeline(c)                  # consts cached; untimed
    rf = jnp.asarray(synth_rf(c, seed=0))
    res = bench_callable(
        f"autotune/{c.name}/{variant.value}", None, (pipe.consts, rf),
        input_bytes=c.input_bytes, warmup=warmup, runs=runs,
        jitted=pipe.jitted)
    return res.t_avg_s


def _default_stage_measure(cfg: UltrasoundConfig, stage: str, *,
                           runs: int, warmup: int) -> float:
    """Mean per-stage time of ``stage`` under ``cfg``'s lowerings, via
    the existing bench_stages breakdown (§II-E per-stage protocol)."""
    import jax.numpy as jnp

    from repro.bench.harness import bench_stages
    from repro.data import synth_rf

    rf = jnp.asarray(synth_rf(cfg, seed=0))
    breakdown = bench_stages(cfg, rf, warmup=warmup, runs=runs)
    return breakdown[stage].mean_s


def _variant_candidates(cfg: UltrasoundConfig,
                        backend: str) -> Tuple[Variant, ...]:
    """Concrete variants able to honor every explicit lowering entry.

    With no explicit entries this is all three; a pinned pallas
    beamform excludes CNN (nothing registered) so AUTO resolution can
    never land on a variant that would refuse the pin. A
    ``fusion='fused'`` config additionally filters to variants whose
    (variant, modality) cell has a runnable fused registration.
    """
    from repro.core import lowering as lowering_lib
    candidates = tuple(
        v for v in CONCRETE_VARIANTS
        if lowering_lib.supports_explicit(cfg.with_(variant=v), backend)
        and (cfg.fusion != "fused"
             or lowering_lib.fused_supported(cfg.with_(variant=v),
                                             backend)))
    if not candidates:
        raise ValueError(
            f"no concrete variant supports the explicit stage_lowerings "
            f"{dict(cfg.stage_lowerings)}"
            + (" with fusion='fused'" if cfg.fusion == "fused" else "")
            + f" on backend {backend!r} — drop "
            "an override or register the missing lowering")
    return candidates


def _autotune_timings(cfg: UltrasoundConfig, backend: str, *,
                      variants: Tuple[Variant, ...],
                      runs: int, warmup: int,
                      measure: Optional[Callable]
                      ) -> Tuple[Tuple[str, float], ...]:
    # Probe settings are part of the key (2-run timings must not answer a
    # 50-run request); an injected `measure` is not — tests that swap
    # probes call clear_autotune_memo(). exec_map is excluded too: the
    # probe times single-acquisition pipelines, which never read it.
    # Explicit stage_lowerings ARE part of the key: the probe runs under
    # them, so timings measured with a pallas beamform must not answer
    # for a plain config (telemetry stays attributable). So is the
    # candidate set — registry extensions change it without changing
    # the config.
    memo_key = (_geometry_key(cfg), cfg.stage_lowerings,
                tuple(v.value for v in variants), backend, runs, warmup)
    if memo_key in _AUTOTUNE_MEMO:
        return _AUTOTUNE_MEMO[memo_key]
    measure = measure or _default_measure
    timings = tuple(
        (v.value, float(measure(cfg, v, runs=runs, warmup=warmup)))
        for v in variants)
    _AUTOTUNE_MEMO[memo_key] = timings
    return timings


def _stage_jit_defaults(cfg: UltrasoundConfig) -> Tuple[Tuple[str, bool],
                                                        ...]:
    # Imported here: stages imports config, and plan must stay importable
    # from config-only contexts.
    from repro.core.stages import build_graph
    return tuple((s.name, True) for s in build_graph(cfg))


def _preferred_lowering(cfg: UltrasoundConfig, stage: str,
                        backend: str, candidates: Dict) -> str:
    """Table pick among ``candidates`` (available lowerings), else xla."""
    from repro.core import lowering as lowering_lib
    table = BACKEND_LOWERING_PREFERENCE.get(backend, {})
    for op_key in ((stage, cfg.variant.value), (stage, None)):
        want = table.get(op_key)
        if want is not None and want in candidates:
            return want
    return (lowering_lib.DEFAULT_LOWERING
            if lowering_lib.DEFAULT_LOWERING in candidates
            else sorted(candidates)[0])


def _resolve_stage_lowerings(cfg: UltrasoundConfig, backend: str, *,
                             policy: str, runs: int, warmup: int,
                             measure_stage: Optional[Callable]
                             ) -> Tuple[Tuple[Tuple[str, str], ...],
                                        Optional[Tuple[Tuple[str, float],
                                                       ...]]]:
    """One lowering per stage of ``cfg``'s (variant-resolved) graph.

    Explicit ``cfg.stage_lowerings`` entries are honored verbatim —
    and refused loudly at plan time when the registry has no such
    lowering for the resolved variant, or when its capability predicate
    rejects this backend/geometry (an explicit ask must run or fail
    here, never silently fall back or die deep inside kernel
    compilation). Open stages consult the per-backend preference table
    (fixed/heuristic) or measure every available candidate through the
    per-stage bench breakdown (autotune, memoized). Returns the
    resolved pairs plus the ("stage:lowering", t) timings when autotune
    measured (None otherwise).

    Under ``fusion='fused'`` the resolved fused lowering CLAIMS its
    span: every spanned stage is stamped with the fused lowering's name
    (an explicit pin naming anything else is a contradiction and fails
    here), and only the stages outside the span go through per-stage
    resolution.
    """
    from repro.core import lowering as lowering_lib
    from repro.core.stages import build_graph

    fused = (lowering_lib.resolve_fused(cfg, backend)
             if cfg.fusion == "fused" else None)
    explicit = dict(cfg.stage_lowerings)
    graph_stages = {s.name for s in build_graph(cfg)}
    stray = sorted(set(explicit) - graph_stages)
    if stray:
        # A pin for a stage this modality's graph never runs would be
        # silently dropped by concretize() — a typo like pinning "bmode"
        # on a doppler config must fail here, not run something else.
        raise ValueError(
            f"stage_lowerings pins stage(s) {stray} that are not in "
            f"this pipeline's graph ({sorted(graph_stages)} for "
            f"modality {cfg.modality.value!r})")
    resolved = []
    to_tune = []
    for stage in build_graph(cfg):
        if fused is not None and stage.name in fused.stages:
            pin = explicit.get(stage.name)
            if pin is not None and pin != fused.name:
                raise ValueError(
                    f"stage_lowerings pins {stage.name!r} to {pin!r}, "
                    f"but fusion='fused' claims the "
                    f"{fused.group!r} span with the {fused.name!r} "
                    "lowering — drop the pin or set fusion='none'")
            resolved.append((stage.name, fused.name))
            continue
        if stage.name in explicit:
            name = explicit[stage.name]
            registered = lowering_lib.registered_lowerings(cfg, stage.name)
            if name not in registered:
                raise ValueError(
                    f"config requests lowering {name!r} for stage "
                    f"{stage.name!r}, but the registry has no such "
                    f"lowering for variant {cfg.variant.value!r} — "
                    "register one (repro.core.lowering) or drop the "
                    "override")
            if not registered[name].available(cfg, backend):
                raise ValueError(
                    f"lowering {name!r} for stage {stage.name!r} is "
                    f"registered but not available on backend "
                    f"{backend!r} for this geometry (capability "
                    "predicate failed — see docs/kernels.md for the "
                    "tile constraints)")
            resolved.append((stage.name, name))
            continue
        candidates = lowering_lib.available_lowerings(cfg, stage.name,
                                                      backend)
        if not candidates:
            # Reachable under reduced precision: the f32-only xla
            # reference drops out of the candidate set, so any stage
            # without a reduced-precision kernel fails here loudly.
            raise ValueError(
                f"no available lowering for stage {stage.name!r} on "
                f"backend {backend!r} at precision {cfg.precision!r} — "
                "reduced precision needs a kernel that declares it "
                "(set fusion='fused' for the megakernel, or "
                "precision='f32')")
        if policy == "autotune" and len(candidates) > 1:
            to_tune.append((stage.name, sorted(candidates)))
            resolved.append((stage.name, None))      # filled below
        else:
            resolved.append((stage.name, _preferred_lowering(
                cfg, stage.name, backend, candidates)))

    timings: Optional[Tuple[Tuple[str, float], ...]] = None
    if to_tune:
        timings = _lowering_timings(
            cfg, backend,
            base=tuple((s, n) for s, n in resolved if n is not None),
            to_tune=tuple((s, tuple(c)) for s, c in to_tune),
            runs=runs, warmup=warmup, measure_stage=measure_stage)
        winners = {}
        for key, t in timings:
            stage_name, low_name = key.split(":", 1)
            if (stage_name not in winners
                    or t < winners[stage_name][1]):
                winners[stage_name] = (low_name, t)
        resolved = [(s, n if n is not None else winners[s][0])
                    for s, n in resolved]
    return tuple(resolved), timings


def _lowering_timings(cfg: UltrasoundConfig, backend: str, *,
                      base: Tuple[Tuple[str, str], ...],
                      to_tune: Tuple[Tuple[str, Tuple[str, ...]], ...],
                      runs: int, warmup: int,
                      measure_stage: Optional[Callable]
                      ) -> Tuple[Tuple[str, float], ...]:
    """Measured ("stage:lowering", t_avg_s) pairs, memoized like the
    variant search. The memo keys on the explicit-entry set AND the
    contested (stage, candidates) set itself — `register_lowering` can
    grow the latter at any time without touching the config, and a
    stale entry missing a newly contested stage must miss, not crash.
    Injected probes are not part of the key (tests that swap them call
    clear_autotune_memo())."""
    memo_key = (config_hash(cfg, exclude=("exec_map", "stage_lowerings")),
                cfg.stage_lowerings, to_tune, backend, runs, warmup)
    if memo_key in _LOWERING_MEMO:
        return _LOWERING_MEMO[memo_key]
    measure_stage = measure_stage or _default_stage_measure
    explicit = dict(cfg.stage_lowerings)
    timings = []
    for stage_name, candidates in to_tune:
        for name in candidates:
            assignment = dict(base)
            assignment.update(explicit)
            assignment[stage_name] = name
            probe_cfg = cfg.with_(
                stage_lowerings=tuple(sorted(assignment.items())))
            t = float(measure_stage(probe_cfg, stage_name,
                                    runs=runs, warmup=warmup))
            timings.append((f"{stage_name}:{name}", t))
    result = tuple(timings)
    _LOWERING_MEMO[memo_key] = result
    return result


def _fusion_block_timings(cfg: UltrasoundConfig, backend: str, fused, *,
                          stage_lowerings: Tuple[Tuple[str, str], ...],
                          runs: int, warmup: int,
                          measure_stage: Optional[Callable]
                          ) -> Tuple[Tuple[str, float], ...]:
    """Measured ("<group>:<name>@bp<bp>", t_avg_s) pairs for the fused
    megakernel's pixel-tile candidates — the per-stage autotune memo
    generalized to a fusion group. The probe times the group entry of
    the bench_stages breakdown (stage_fns exposes the span under its
    group key), memoized per geometry like the per-stage search. Only
    the candidates the fused lowering's capability predicate admits on
    this backend are probed (the TPU takes whole 128-lane tiles only)."""
    from repro.core import lowering as lowering_lib
    from repro.kernels.pallas_compat import next_multiple

    n_pix = cfg.nz * cfg.nx
    cap = next_multiple(n_pix, 8)
    bps = tuple(sorted(
        bp for bp in {min(bp, cap) for bp in FUSION_BLOCK_CANDIDATES}
        if lowering_lib.fused_supported(cfg.with_(fusion_block=bp),
                                        backend)))
    memo_key = (config_hash(cfg, exclude=("exec_map", "stage_lowerings",
                                          "fusion_block")),
                cfg.stage_lowerings, fused.group, bps, backend, runs,
                warmup)
    if memo_key in _LOWERING_MEMO:
        return _LOWERING_MEMO[memo_key]
    measure_stage = measure_stage or _default_stage_measure
    timings = []
    for bp in bps:
        probe_cfg = cfg.with_(stage_lowerings=stage_lowerings,
                              fusion_block=bp)
        t = float(measure_stage(probe_cfg, fused.group,
                                runs=runs, warmup=warmup))
        timings.append((f"{fused.group}:{fused.name}@bp{bp}", t))
    result = tuple(timings)
    _LOWERING_MEMO[memo_key] = result
    return result


def plan_pipeline(cfg: UltrasoundConfig, policy: str = "fixed", *,
                  donate: Optional[bool] = None,
                  autotune_runs: int = 3, autotune_warmup: int = 1,
                  measure: Optional[Callable] = None,
                  measure_stage: Optional[Callable] = None) -> PipelinePlan:
    """Resolve a config (possibly ``Variant.AUTO``) into a PipelinePlan.

    ``measure(cfg, variant, runs=, warmup=)`` overrides the autotune
    variant probe and ``measure_stage(cfg, stage, runs=, warmup=)`` the
    per-stage lowering probe (tests inject deterministic timings through
    both). An explicitly concrete ``cfg.variant`` — and any explicit
    ``cfg.stage_lowerings`` entry — is honored under every policy; the
    planner only ever decides what the user left open.
    """
    if policy not in POLICIES:
        raise ValueError(f"unknown plan policy: {policy!r} "
                         f"(expected one of {POLICIES})")
    backend = jax.default_backend()
    key = config_hash(cfg)
    autotune_t_s = None

    if cfg.variant.concrete:
        variant = cfg.variant
        provenance = f"explicit:{variant.value}"
    elif policy == "fixed":
        raise ValueError(
            "policy 'fixed' cannot resolve Variant.AUTO — pass a concrete "
            "variant or use policy='heuristic' / 'autotune'")
    elif policy == "heuristic":
        candidates = _variant_candidates(cfg, backend)
        variant = BACKEND_VARIANT_PREFERENCE.get(backend, DEFAULT_PREFERENCE)
        known = backend in BACKEND_VARIANT_PREFERENCE
        provenance = (f"heuristic:{backend}->{variant.value}"
                      f"{'' if known else ' (default: unknown backend)'}")
        if variant not in candidates:
            # The preferred variant cannot honor an explicit lowering
            # pin — fall to the first candidate that can, and say so.
            variant = candidates[0]
            provenance += (f" -> {variant.value} (preference cannot honor "
                           f"explicit stage_lowerings)")
    else:  # autotune
        autotune_t_s = _autotune_timings(
            cfg, backend, variants=_variant_candidates(cfg, backend),
            runs=autotune_runs, warmup=autotune_warmup,
            measure=measure)
        winner = min(autotune_t_s, key=lambda kv: kv[1])
        variant = Variant(winner[0])
        provenance = (f"autotune:{backend}->{variant.value} "
                      f"(t_avg={winner[1]:.3e}s over "
                      f"{len(autotune_t_s)} variants)")

    # The modality decides the head stage, so jit toggles (and the
    # per-stage lowering resolution) come from the resolved graph.
    # Default: jit every stage (today's behavior).
    resolved = cfg.with_(variant=variant)
    stage_lowerings, lowering_t_s = _resolve_stage_lowerings(
        resolved, backend, policy=policy,
        runs=autotune_runs, warmup=autotune_warmup,
        measure_stage=measure_stage)

    # Fusion-group resolution: the fused lowering was validated inside
    # _resolve_stage_lowerings; here the planner decides the block size
    # (explicit cfg.fusion_block honored, autotune measures the
    # candidates, fixed/heuristic defer to the kernel default).
    fusion_group = None
    fusion_block = None
    if cfg.fusion == "fused":
        from repro.core import lowering as lowering_lib
        fused = lowering_lib.resolve_fused(resolved, backend)
        fusion_group = fused.group
        fusion_block = cfg.fusion_block
        if fusion_block is None and policy == "autotune":
            bp_t = _fusion_block_timings(
                resolved, backend, fused, stage_lowerings=stage_lowerings,
                runs=autotune_runs, warmup=autotune_warmup,
                measure_stage=measure_stage)
            best = min(bp_t, key=lambda kv: kv[1])
            fusion_block = int(best[0].rsplit("@bp", 1)[1])
            lowering_t_s = (lowering_t_s or ()) + bp_t

    return PipelinePlan(
        variant=variant, exec_map=cfg.exec_map, donate=donate,
        jit_stages=_stage_jit_defaults(resolved), backend=backend,
        policy=policy, config_key=key, geometry_key=_geometry_key(cfg),
        provenance=provenance, stage_lowerings=stage_lowerings,
        fusion=cfg.fusion, precision=cfg.precision,
        fusion_group=fusion_group, fusion_block=fusion_block,
        autotune_t_s=autotune_t_s, lowering_t_s=lowering_t_s)
