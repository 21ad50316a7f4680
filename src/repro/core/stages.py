"""Stage graph for the RF-to-image pipelines (execution substrate).

The pipeline is declared as an ordered graph of named stages

    demod -> beamform -> {bmode | doppler | power_doppler}

Each stage exposes two pure functions:

  * ``init_consts(cfg)``  — precompute that stage's constants (numpy,
    untimed, deterministic; the paper's §II-C module-initialization
    contract, now attributable per stage), and
  * ``apply(cfg, consts, x)`` — the stage's runtime transform. ``consts``
    is the *merged* graph constant dict so stages stay composable with
    the legacy monolithic function signature.

`graph_fn(cfg)` composes the stages back into the monolithic
(consts, rf) -> image function — same jaxpr as the legacy monolith when
every stage runs its ``xla`` lowering, so jit/pjit callers are
unchanged — while `stage_fns(cfg)` returns each stage as its own
(consts, x) -> y callable so stages can be jitted and timed
individually (per-stage telemetry, §II-E breakdown).

Each stage's runtime transform dispatches through the per-stage
operator-lowering registry (repro.core.lowering): the lowering named in
``cfg.stage_lowerings`` (plan-resolved) executes; stages left
unspecified run the ``xla`` reference formulation.

`graph_fn` runs each stage under ``jax.named_scope(stage.name)`` (a
fused span under its fusion group), so every operation of the compiled
program carries its stage in its ``op_name`` metadata and a profiler
trace can attribute device time to ``demod``, ``beamform`` and the
head. The scopes change only metadata, never the computation.

When ``cfg.fusion == "fused"`` the composition routes the registered
fused lowering's stage SPAN through its single apply (the megakernel)
and composes the remaining stages around it; ``stage_fns`` then exposes
the span under its fusion-group key (e.g. ``demod+beamform+bmode``) so
the per-stage telemetry and the bench breakdown never pretend the fused
stages were timed individually.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import delays, demod, doppler, lowering
from repro.core.config import Modality, UltrasoundConfig, Variant


@dataclasses.dataclass(frozen=True)
class Stage:
    """One named node of the pipeline graph."""

    name: str
    init_consts: Callable[[UltrasoundConfig], Dict[str, np.ndarray]]
    apply: Callable[[UltrasoundConfig, Dict, jnp.ndarray], jnp.ndarray]


# ---------------------------------------------------------------------------
# Stage definitions
# ---------------------------------------------------------------------------


def _dispatch(stage_name):
    """Bind a stage's apply to the lowering registry at call time, so a
    plan-resolved ``cfg.stage_lowerings`` decides which formulation
    traces (xla reference or Pallas kernel)."""
    return (lambda cfg, consts, x:
            lowering.apply_stage(cfg, stage_name, consts, x))


def _beamform_consts(cfg: UltrasoundConfig) -> Dict[str, np.ndarray]:
    if not cfg.variant.concrete:
        raise ValueError(
            "Variant.AUTO has no constants — resolve it with "
            "repro.core.plan.plan_pipeline before building the graph")
    consts: Dict[str, np.ndarray] = {}
    tables = delays.compute_delay_tables(cfg)
    if cfg.variant == Variant.DYNAMIC:
        consts.update(idx=tables.idx, frac=tables.frac,
                      apod=tables.apod, rot=tables.rot)
    elif cfg.variant == Variant.CNN:
        consts["interp_re"], consts["interp_im"] = \
            delays.interp_operator(cfg, tables)
    elif cfg.variant == Variant.SPARSE:
        op = delays.bsr_operator(cfg, tables)
        consts["bsr_blocks"] = op.blocks
        consts["bsr_col_idx"] = op.col_idx
    else:  # pragma: no cover
        raise ValueError(cfg.variant)
    return consts


def _doppler_consts(cfg: UltrasoundConfig) -> Dict[str, np.ndarray]:
    return {"wall_taps": doppler.wall_filter_taps(cfg),
            "smooth": doppler.smoothing_kernel(cfg)}


DEMOD = Stage("demod", lambda cfg: dict(demod.demod_consts(cfg)),
              _dispatch("demod"))

BEAMFORM = Stage("beamform", _beamform_consts, _dispatch("beamform"))

HEADS: Dict[Modality, Stage] = {
    Modality.BMODE: Stage("bmode", lambda cfg: {}, _dispatch("bmode")),
    Modality.DOPPLER: Stage("doppler", _doppler_consts,
                            _dispatch("doppler")),
    Modality.POWER_DOPPLER: Stage("power_doppler", _doppler_consts,
                                  _dispatch("power_doppler")),
}


# ---------------------------------------------------------------------------
# Graph construction / composition
# ---------------------------------------------------------------------------


def build_graph(cfg: UltrasoundConfig) -> Tuple[Stage, ...]:
    """Ordered stage graph for the configured modality."""
    if cfg.modality not in HEADS:  # pragma: no cover
        raise ValueError(cfg.modality)
    return (DEMOD, BEAMFORM, HEADS[cfg.modality])


def init_graph_consts(cfg: UltrasoundConfig) -> Dict[str, np.ndarray]:
    """Merged constants of every stage (untimed, deterministic)."""
    consts: Dict[str, np.ndarray] = {}
    for stage in build_graph(cfg):
        news = stage.init_consts(cfg)
        dup = set(news) & set(consts)
        assert not dup, f"stage {stage.name} redefines consts {dup}"
        consts.update(news)
    return consts


def _fused_span(cfg: UltrasoundConfig):
    """The FusedLowering a ``fusion='fused'`` config routes through
    (None for ``fusion='none'``). Resolution is loud: a fused request
    with no runnable registration raises here rather than silently
    composing per-stage."""
    if cfg.fusion != "fused":
        return None
    return lowering.resolve_fused(cfg, jax.default_backend())


def _split_span(stages: Tuple[Stage, ...], fused):
    """(prefix stages, suffix stages) around the fused lowering's span."""
    names = [stage.name for stage in stages]
    i0 = names.index(fused.stages[0])
    assert tuple(names[i0:i0 + len(fused.stages)]) == fused.stages, (
        names, fused.stages)  # registration validated contiguity
    return stages[:i0], stages[i0 + len(fused.stages):]


def graph_fn(cfg: UltrasoundConfig) -> Callable:
    """Pure (consts, rf) -> image composition of the stage graph."""
    stages = build_graph(cfg)
    fused = _fused_span(cfg)
    if fused is None:
        def run(consts, rf):
            x = rf
            for stage in stages:
                with jax.named_scope(stage.name):
                    x = stage.apply(cfg, consts, x)
            return x
        return run

    prefix, suffix = _split_span(stages, fused)

    def run_fused(consts, rf):
        x = rf
        for stage in prefix:
            with jax.named_scope(stage.name):
                x = stage.apply(cfg, consts, x)
        with jax.named_scope(fused.group):
            x = fused.apply(cfg, consts, x)
        for stage in suffix:
            with jax.named_scope(stage.name):
                x = stage.apply(cfg, consts, x)
        return x

    return run_fused


def stage_fns(cfg: UltrasoundConfig) -> Dict[str, Callable]:
    """Each schedulable unit as its own jittable (consts, x) -> y callable.

    Insertion order is execution order (bench_stages chains the dict).
    Under ``fusion='fused'`` the spanned stages collapse into ONE entry
    keyed by the fusion group (``'+'.join(span)``) — the megakernel is
    the timeable unit; its interior stages have no individual timings.
    """
    def bind(stage):
        return lambda consts, x: stage.apply(cfg, consts, x)

    stages = build_graph(cfg)
    fused = _fused_span(cfg)
    if fused is None:
        return {stage.name: bind(stage) for stage in stages}

    prefix, suffix = _split_span(stages, fused)
    fns: Dict[str, Callable] = {stage.name: bind(stage) for stage in prefix}
    fns[fused.group] = lambda consts, x: fused.apply(cfg, consts, x)
    fns.update({stage.name: bind(stage) for stage in suffix})
    return fns
