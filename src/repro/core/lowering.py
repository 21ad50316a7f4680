"""Per-stage operator-lowering registry (xla / pallas, planned per stage).

The paper's central claim is that *operator formulation* decides
throughput per backend — and TINA/ConvBench show the win comes from
choosing the right primitive lowering per operator, not per pipeline.
The variant (dynamic / cnn / sparse) picks the *math formulation*; this
module picks, per stage, the *lowering* that executes it:

  * ``xla``    — the plain jax.numpy formulation (portable baseline;
    every stage op registers one).
  * ``pallas`` — a hand-tiled Pallas kernel (repro.kernels): the fused
    ``das_beamform`` kernel lowers the dynamic beamform, the
    scalar-prefetched ``bsr_spmm`` kernel lowers the sparse beamform.
    Compiled on TPU, interpret-mode everywhere else (the shared
    ``repro.kernels.pallas_compat.auto_interpret`` fallback).

Each registration carries a capability predicate ``available(cfg,
backend)`` (backend support, shape/tile constraints), so the planner
(repro.core.plan) only ever considers lowerings that can actually run.
`plan_pipeline` resolves one lowering per stage — preference table or
per-stage autotune — and `PipelinePlan.concretize` writes the mapping
into ``cfg.stage_lowerings``, from where `apply_stage` dispatches at
trace time. The resolved mapping participates in the canonical config
hash, so the multi-tenant scheduler never shares a compiled program
across different lowerings, and it is stamped into every NDJSON record
via the plan.

Invariants: every (stage, variant) op has an ``xla`` lowering (the
numeric reference — all lowerings of one op are allclose, asserted in
tests/test_lowering.py); registration is idempotent per key; the
registry is process-global and inspectable (tests extend it freely).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.core import beamform, bmode, demod, doppler
from repro.core.config import (LOWERING_NAMES, Modality, PRECISION_NAMES,
                               STAGE_NAMES, UltrasoundConfig, Variant)

__all__ = ["Lowering", "register_lowering", "registered_lowerings",
           "available_lowerings", "resolve_apply", "apply_stage",
           "supported_subset", "DEFAULT_LOWERING", "FusedLowering",
           "register_fused_lowering", "registered_fused_lowerings",
           "resolve_fused"]

DEFAULT_LOWERING = "xla"


@dataclasses.dataclass(frozen=True)
class Lowering:
    """One way to execute a stage op.

    ``apply(cfg, consts, x) -> y`` is the runtime transform (same
    contract as `repro.core.stages.Stage.apply`); ``available(cfg,
    backend)`` gates it on backend support and shape/tile constraints.
    ``variant`` scopes the registration: None applies to every variant
    (demod, the heads), a concrete Variant only to that formulation of
    the stage (the three beamformers are three distinct ops).
    ``precisions`` names the compute precisions the lowering implements
    (config.PRECISION_NAMES); the xla references compute in f32 only,
    so a reduced-precision config resolves only onto kernels that
    declare it — the planner refuses anything else loudly.
    """

    stage: str
    name: str
    apply: Callable[[UltrasoundConfig, Dict, jnp.ndarray], jnp.ndarray]
    available: Callable[[UltrasoundConfig, str], bool]
    variant: Optional[Variant] = None
    precisions: Tuple[str, ...] = ("f32",)


# (stage, variant value or None) -> {lowering name -> Lowering}
_REGISTRY: Dict[Tuple[str, Optional[str]], Dict[str, Lowering]] = {}


def _always(cfg: UltrasoundConfig, backend: str) -> bool:
    return True


def register_lowering(stage: str, name: str, apply: Callable, *,
                      variant: Optional[Variant] = None,
                      available: Optional[Callable] = None,
                      precisions: Tuple[str, ...] = ("f32",)) -> Lowering:
    """Register (or replace) one lowering of a stage op."""
    if stage not in STAGE_NAMES:
        raise ValueError(f"unknown stage: {stage!r} "
                         f"(expected one of {STAGE_NAMES})")
    if name not in LOWERING_NAMES:
        raise ValueError(f"unknown lowering name: {name!r} "
                         f"(expected one of {LOWERING_NAMES})")
    _check_precisions(precisions)
    low = Lowering(stage=stage, name=name, apply=apply,
                   available=available or _always, variant=variant,
                   precisions=tuple(precisions))
    key = (stage, variant.value if variant is not None else None)
    _REGISTRY.setdefault(key, {})[name] = low
    return low


def _check_precisions(precisions) -> None:
    bad = sorted(set(precisions) - set(PRECISION_NAMES))
    if bad or not precisions:
        raise ValueError(f"invalid precisions {tuple(precisions)!r} "
                         f"(expected a non-empty subset of "
                         f"{PRECISION_NAMES})")


def _op_key(cfg: UltrasoundConfig, stage: str) -> Tuple[str, Optional[str]]:
    """The registry key for ``stage`` under ``cfg``'s variant.

    Variant-scoped registrations (the beamformers) win over
    variant-independent ones; the beamform stage of an AUTO config has
    no op until the planner resolves the variant.
    """
    if cfg.variant.concrete and (stage, cfg.variant.value) in _REGISTRY:
        return (stage, cfg.variant.value)
    return (stage, None)


def registered_lowerings(cfg: UltrasoundConfig,
                         stage: str) -> Dict[str, Lowering]:
    """Every lowering registered for this (stage, cfg.variant) op."""
    return dict(_REGISTRY.get(_op_key(cfg, stage), {}))


def available_lowerings(cfg: UltrasoundConfig, stage: str,
                        backend: str) -> Dict[str, Lowering]:
    """The registered lowerings whose capability predicate passes AND
    that implement ``cfg.precision`` — under reduced precision the xla
    references (f32-only) drop out, so resolution fails loudly for any
    stage no kernel covers rather than silently computing in f32."""
    return {n: low for n, low in registered_lowerings(cfg, stage).items()
            if cfg.precision in low.precisions
            and low.available(cfg, backend)}


def resolve_apply(cfg: UltrasoundConfig, stage: str) -> Callable:
    """The apply callable for ``cfg``'s chosen lowering of ``stage``.

    Stages left unspecified in ``cfg.stage_lowerings`` run the ``xla``
    reference — plan-resolved configs always specify every stage, so
    the default only serves raw (planner-less) graph construction.
    """
    name = cfg.stage_lowering(stage, DEFAULT_LOWERING)
    lows = registered_lowerings(cfg, stage)
    if name not in lows:
        have = sorted(lows) or ["<none>"]
        op = (f"{stage}/{cfg.variant.value}"
              if _op_key(cfg, stage)[1] is not None else stage)
        raise ValueError(
            f"no {name!r} lowering registered for stage op {op!r} "
            f"(registered: {have})")
    if cfg.precision not in lows[name].precisions:
        raise ValueError(
            f"lowering {name!r} for stage {stage!r} computes in "
            f"{lows[name].precisions} only, but the config requests "
            f"precision={cfg.precision!r} — reduced precision needs a "
            "kernel that declares it (set fusion='fused' for the "
            "megakernel, or precision='f32')")
    return lows[name].apply


def apply_stage(cfg: UltrasoundConfig, stage: str, consts: Dict,
                x: jnp.ndarray) -> jnp.ndarray:
    """Dispatch one stage through its configured lowering."""
    return resolve_apply(cfg, stage)(cfg, consts, x)


def supported_subset(cfg: UltrasoundConfig,
                     backend: Optional[str] = None
                     ) -> Tuple[Tuple[str, str], ...]:
    """``cfg.stage_lowerings`` pruned to entries this variant registers
    AND whose capability predicate passes on ``backend``.

    Used when probing concrete variants on behalf of ``Variant.AUTO``:
    an explicit {"beamform": "pallas"} must not crash the CNN probe
    (which registers no pallas beamform) — the final plan still
    validates explicit entries strictly against the resolved variant.
    """
    if backend is None:
        import jax
        backend = jax.default_backend()
    return tuple((stage, name) for stage, name in cfg.stage_lowerings
                 if name in available_lowerings(cfg, stage, backend))


def supports_explicit(cfg: UltrasoundConfig, backend: str) -> bool:
    """True iff every explicit ``cfg.stage_lowerings`` entry is
    registered for this variant and available on this backend — the
    planner's variant-candidate filter (an AUTO config pinned to a
    pallas beamform must never resolve to a variant that cannot honor
    the pin)."""
    return supported_subset(cfg, backend) == cfg.stage_lowerings


# ---------------------------------------------------------------------------
# Fused (stage-span) lowerings
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedLowering:
    """One lowering claiming a contiguous SPAN of stages.

    ``apply(cfg, consts, x) -> y`` maps the first spanned stage's input
    straight to the last spanned stage's output (for the full-pipeline
    span: RF -> image) — the per-stage dispatch never runs inside the
    span. A fused lowering is scoped to one (variant, modality) cell:
    the span's math is the composition of that variant's stage ops, so
    a single registration cannot honestly serve two formulations.

    ``stages`` must be a contiguous run of the modality's graph order
    ``(demod, beamform, <head>)``, length >= 2 — a 1-stage "span" is a
    per-stage lowering and belongs in the flat registry.
    """

    stages: Tuple[str, ...]
    name: str
    variant: Variant
    modality: Modality
    apply: Callable[[UltrasoundConfig, Dict, jnp.ndarray], jnp.ndarray]
    available: Callable[[UltrasoundConfig, str], bool]
    precisions: Tuple[str, ...] = ("f32",)

    @property
    def group(self) -> str:
        """Canonical fusion-group label, e.g. ``demod+beamform+bmode`` —
        the plan stamp, NDJSON field, and stage_fns key for the span."""
        return "+".join(self.stages)


# (variant value, modality value) -> {lowering name -> FusedLowering}
_FUSED_REGISTRY: Dict[Tuple[str, str], Dict[str, FusedLowering]] = {}


def _graph_order(modality: Modality) -> Tuple[str, ...]:
    # Mirrors stages.build_graph without importing it (stages imports us).
    return ("demod", "beamform", modality.value)


def register_fused_lowering(stages: Tuple[str, ...], name: str,
                            apply: Callable, *, variant: Variant,
                            modality: Modality,
                            available: Optional[Callable] = None,
                            precisions: Tuple[str, ...] = ("f32",)
                            ) -> FusedLowering:
    """Register (or replace) a fused lowering for one (variant, modality)."""
    if name not in LOWERING_NAMES:
        raise ValueError(f"unknown lowering name: {name!r} "
                         f"(expected one of {LOWERING_NAMES})")
    if not variant.concrete:
        raise ValueError("fused lowerings are scoped to concrete variants")
    _check_precisions(precisions)
    order = _graph_order(modality)
    stages = tuple(stages)
    runs = [tuple(order[i:i + len(stages)])
            for i in range(len(order) - len(stages) + 1)]
    if len(stages) < 2 or stages not in runs:
        raise ValueError(
            f"fused span {stages!r} is not a contiguous run (length >= 2) "
            f"of the {modality.value!r} graph {order!r}")
    fused = FusedLowering(stages=stages, name=name, apply=apply,
                          variant=variant, modality=modality,
                          available=available or _always,
                          precisions=tuple(precisions))
    key = (variant.value, modality.value)
    _FUSED_REGISTRY.setdefault(key, {})[name] = fused
    return fused


def registered_fused_lowerings(cfg: UltrasoundConfig
                               ) -> Dict[str, FusedLowering]:
    """Every fused lowering registered for (cfg.variant, cfg.modality)."""
    if not cfg.variant.concrete:
        return {}
    return dict(_FUSED_REGISTRY.get(
        (cfg.variant.value, cfg.modality.value), {}))


def resolve_fused(cfg: UltrasoundConfig, backend: str) -> FusedLowering:
    """THE fused lowering a ``fusion='fused'`` config executes, or a
    loud error naming exactly which gate failed (registration,
    precision, capability) — a fused request must run or fail at plan
    time, never silently fall back to per-stage dispatch."""
    cell = f"({cfg.variant.value}, {cfg.modality.value})"
    registered = registered_fused_lowerings(cfg)
    if not registered:
        raise ValueError(
            f"fusion='fused' but no fused lowering is registered for "
            f"{cell} — set fusion='none' or register one "
            "(repro.core.lowering.register_fused_lowering)")
    usable = {n: f for n, f in registered.items()
              if cfg.precision in f.precisions}
    if not usable:
        raise ValueError(
            f"no fused lowering for {cell} implements "
            f"precision={cfg.precision!r} "
            f"(registered: { {n: f.precisions for n, f in registered.items()} })")
    live = {n: f for n, f in usable.items() if f.available(cfg, backend)}
    if not live:
        raise ValueError(
            f"fused lowering(s) {sorted(usable)} for {cell} are "
            f"registered but not available on backend {backend!r} for "
            "this geometry (capability predicate failed — see "
            "docs/kernels.md for the tile constraints)")
    # One fused lowering per cell today; deterministic pick if extended.
    return live[sorted(live)[0]]


def fused_supported(cfg: UltrasoundConfig, backend: str) -> bool:
    """True iff ``resolve_fused`` would succeed (planner candidate
    filter — AUTO resolution must never land on a variant whose fused
    cell cannot run)."""
    try:
        resolve_fused(cfg, backend)
        return True
    except ValueError:
        return False


# ---------------------------------------------------------------------------
# Default registrations: the stage-op x lowering matrix
# ---------------------------------------------------------------------------


def _beamform_dynamic_pallas(cfg, consts, iq):
    """Fused DAS gather+lerp+rotate+reduce in one Pallas kernel
    (repro.kernels.das_beamform; docs/kernels.md has the tile contract)."""
    from repro.kernels.das_beamform import das_beamform
    return das_beamform(consts["idx"], consts["frac"], consts["apod"],
                        consts["rot"], iq, precision=cfg.precision)


def _beamform_sparse_pallas(cfg, consts, iq):
    """Banded BSR SpMM via the scalar-prefetched Pallas kernel — the
    paper's V3-on-TPU story (repro.kernels.bsr_spmm). The wrapper owns
    the IQ sample-axis blocking; the kernel owns the block gather."""
    from repro.kernels.bsr_spmm import bsr_beamform
    from repro.kernels.pallas_compat import block_sample_axis
    blocks = consts["bsr_blocks"]                       # (n_c,n_pb,K,bp,2bs)
    cols = consts["bsr_col_idx"]                        # (n_c, n_pb, K)
    iq_b = block_sample_axis(iq, blocks.shape[4] // 2)
    return bsr_beamform(cols, blocks, iq_b,
                        precision=cfg.precision)[: cfg.n_pix]


def _fused_dynamic_bmode_pallas(cfg, consts, rf):
    """demod→DAS beamform→envelope in ONE Pallas megakernel, then the
    reference global epilogue (normalize + dB compression) — the fusion
    boundary documented in repro.kernels.fused_pipeline.kernel."""
    from repro.kernels.fused_pipeline import fused_rf_to_envelope
    env = fused_rf_to_envelope(
        consts["carrier"], consts["lpf"], consts["idx"], consts["frac"],
        consts["apod"], consts["rot"], rf, decim=cfg.decim,
        bp=cfg.fusion_block, precision=cfg.precision)
    return bmode.compress_envelope(cfg, env)


def _fused_dynamic_power_pallas(cfg, consts, rf):
    """demod→DAS beamform→wall filter→R0 in ONE Pallas megakernel, then
    the reference global epilogue (normalize + dB + spatial smooth)."""
    from repro.kernels.fused_pipeline import fused_rf_to_power
    r0 = fused_rf_to_power(
        consts["carrier"], consts["lpf"], consts["idx"], consts["frac"],
        consts["apod"], consts["rot"], consts["wall_taps"], rf,
        decim=cfg.decim, bp=cfg.fusion_block, precision=cfg.precision)
    return doppler.power_compress(cfg, consts, r0)


def _das_pallas_available(cfg: UltrasoundConfig, backend: str) -> bool:
    # The wrapper pads pixels and channels to the tile sizes and streams
    # one channel chunk of IQ per grid step, so the DAS kernel fits VMEM
    # at every geometry (interpret mode off-TPU).
    return True


def _fused_pallas_available(cfg: UltrasoundConfig, backend: str) -> bool:
    # The fused megakernel keeps the whole RF block and the IQ cube
    # resident in VMEM: on the TPU it is refused where that estimate
    # exceeds the kernels' scoped-VMEM budget (the paper geometry needs
    # ~52 MiB against 32 MiB) or where the pixel tile is not a whole
    # number of 128-lane vregs.
    if backend != "tpu":
        return True
    from repro.kernels.fused_pipeline.kernel import (DEFAULT_BP,
                                                     fused_vmem_bytes)
    from repro.kernels.pallas_compat import VMEM_LIMIT_BYTES
    bp = cfg.fusion_block or DEFAULT_BP
    return (bp % 128 == 0 and fused_vmem_bytes(
        cfg.n_l, cfg.n_c, cfg.n_f, cfg.decim) <= VMEM_LIMIT_BYTES)


def _bsr_pallas_available(cfg: UltrasoundConfig, backend: str) -> bool:
    # Interpret mode accepts any block shape; the compiled TPU kernel
    # feeds (bp x bs) blocks straight to the MXU, so sublane alignment
    # (the config's documented "MXU-aligned multiples of 8" contract —
    # the shipped defaults satisfy it) is a hard tile constraint.
    if backend != "tpu":
        return True
    return cfg.sparse_block_p % 8 == 0 and cfg.sparse_block_s % 8 == 0


def _register_defaults() -> None:
    register_lowering(
        "demod", "xla",
        lambda cfg, consts, rf: demod.rf_to_iq(consts, rf, cfg.decim))
    for variant, fn in beamform.BEAMFORMERS.items():
        # each beamformer already has the Lowering.apply signature
        register_lowering("beamform", "xla", fn, variant=variant)
    register_lowering("beamform", "pallas", _beamform_dynamic_pallas,
                      variant=Variant.DYNAMIC,
                      available=_das_pallas_available,
                      precisions=("f32", "bf16", "f16"))
    register_lowering("beamform", "pallas", _beamform_sparse_pallas,
                      variant=Variant.SPARSE,
                      available=_bsr_pallas_available,
                      precisions=("f32", "bf16", "f16"))
    register_lowering(
        "bmode", "xla",
        lambda cfg, consts, bf: bmode.bmode_image(cfg, bf))
    register_lowering(
        "doppler", "xla",
        lambda cfg, consts, bf:
            doppler.color_doppler_image(cfg, consts, bf))
    register_lowering(
        "power_doppler", "xla",
        lambda cfg, consts, bf:
            doppler.power_doppler_image(cfg, consts, bf))
    register_fused_lowering(
        ("demod", "beamform", "bmode"), "pallas",
        _fused_dynamic_bmode_pallas,
        variant=Variant.DYNAMIC, modality=Modality.BMODE,
        available=_fused_pallas_available,
        precisions=("f32", "bf16", "f16"))
    register_fused_lowering(
        ("demod", "beamform", "power_doppler"), "pallas",
        _fused_dynamic_power_pallas,
        variant=Variant.DYNAMIC, modality=Modality.POWER_DOPPLER,
        available=_fused_pallas_available,
        precisions=("f32", "bf16", "f16"))


_register_defaults()
