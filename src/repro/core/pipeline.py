"""End-to-end RF-to-image pipelines (paper §II-A modalities).

Built on the stage graph in `repro.core.stages`:
`init_pipeline(cfg)` merges every stage's precomputed constants (geometry
tables, FIR taps, interpolation operators) — module initialization,
excluded from timing. `pipeline_fn(cfg)` is the stage-graph composition:
a pure (consts, rf) -> image function suitable for jax.jit / pjit; rf is
the only runtime input.

The SAME code runs every variant and every backend; variant selection is
configuration — `Variant.AUTO` additionally delegates the choice to the
backend-aware planner (`repro.core.plan`), preserving the paper's
"no backend-specific rewrites" invariant (§II-E) without a hand-picked
variant. `monolithic_pipeline_fn` keeps the pre-stage-graph
single-function form as a reference oracle (tests assert the graph
composition reproduces it exactly).

Constants are served through a two-tier cache — an in-process dict plus
an optional on-disk ``.npz`` store, both keyed by the canonical config
hash — so the delay-table / interp-matrix precompute is paid once across
variant sweeps, repeated benchmarks, and serve restarts. The disk tier
reads `REPRO_CONSTS_CACHE_DIR` (set to "" / "0" to disable); entries are
bit-exact round trips of the numpy constants.

For batched multi-acquisition execution see `repro.core.executor`.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import tempfile
from typing import Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import beamform, bmode, demod, doppler, stages
from repro.core.config import Modality, UltrasoundConfig, Variant, \
    config_hash


# ---------------------------------------------------------------------------
# Two-tier constants cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ConstsCacheStats:
    """Hit/miss counters for the constants cache (reset per process).

    ``misses`` counts actual delay-table recomputations — the acceptance
    check "repeated init for the same config recomputes nothing" is
    literally `misses` staying flat.
    """

    mem_hits: int = 0
    disk_hits: int = 0
    misses: int = 0

    def reset(self) -> None:
        self.mem_hits = self.disk_hits = self.misses = 0


CONSTS_CACHE_STATS = ConstsCacheStats()

# Bump whenever the *meaning* of precomputed constants changes (delay-table
# math, interp-matrix layout, BSR packing) — the config hash alone cannot
# see code changes, and a stale disk entry would silently corrupt images.
CONSTS_SCHEMA = "consts-v3"     # v3: CNN operator as re, im halves

# LRU memory tier, bounded in bytes: a paper-scale variant sweep must not
# pin multi-GB CNN operators for process lifetime. Entries larger than the
# budget are served uncached.
MEM_CACHE_MAX_BYTES = int(os.environ.get(
    "REPRO_CONSTS_CACHE_MEM_MAX_BYTES", 1024 * 1024 * 1024))
_MEM_CACHE: "collections.OrderedDict[str, Dict[str, np.ndarray]]" = \
    collections.OrderedDict()

# Per-ENTRY disk cap: paper-scale CNN operators reach GBs and are cheaper
# to recompute than to read back. The directory's total is NOT bounded —
# entries are never evicted (wipe with clear_consts_cache(disk=True)).
DISK_CACHE_MAX_BYTES = int(os.environ.get(
    "REPRO_CONSTS_CACHE_MAX_BYTES", 256 * 1024 * 1024))


def _consts_nbytes(consts: Dict[str, np.ndarray]) -> int:
    return sum(a.nbytes for a in consts.values())


def _mem_put(key: str, consts: Dict[str, np.ndarray]) -> None:
    if _consts_nbytes(consts) > MEM_CACHE_MAX_BYTES:
        return
    _MEM_CACHE[key] = consts
    _MEM_CACHE.move_to_end(key)
    while (len(_MEM_CACHE) > 1 and
           sum(map(_consts_nbytes, _MEM_CACHE.values()))
           > MEM_CACHE_MAX_BYTES):
        _MEM_CACHE.popitem(last=False)         # evict least-recently used

_UNSET = object()
_disk_cache_dir: Optional[str] = None
_disk_cache_resolved = False


def _default_disk_dir() -> Optional[str]:
    env = os.environ.get("REPRO_CONSTS_CACHE_DIR", _UNSET)
    if env is _UNSET:
        from repro.core.aot import CHECKOUT_DIR
        return os.path.join(CHECKOUT_DIR, ".consts_cache")   # gitignored
    return env if env and env != "0" else None


def consts_cache_dir() -> Optional[str]:
    """Active on-disk cache directory (None = disk tier disabled)."""
    global _disk_cache_dir, _disk_cache_resolved
    if not _disk_cache_resolved:
        _disk_cache_dir = _default_disk_dir()
        _disk_cache_resolved = True
    return _disk_cache_dir


def set_consts_cache_dir(path: Optional[str]) -> None:
    """Point the disk tier somewhere else (tests), or disable it (None)."""
    global _disk_cache_dir, _disk_cache_resolved
    _disk_cache_dir = path
    _disk_cache_resolved = True


def clear_consts_cache(*, memory: bool = True, disk: bool = False) -> None:
    if memory:
        _MEM_CACHE.clear()
    if disk and consts_cache_dir() and os.path.isdir(consts_cache_dir()):
        for name in os.listdir(consts_cache_dir()):
            if name.endswith(".npz"):
                os.remove(os.path.join(consts_cache_dir(), name))


def _disk_path(key: str) -> Optional[str]:
    d = consts_cache_dir()
    return os.path.join(d, f"{key}.npz") if d else None


def _disk_load(key: str) -> Optional[Dict[str, np.ndarray]]:
    path = _disk_path(key)
    if path is None or not os.path.exists(path):
        return None
    try:
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    except Exception:   # noqa: BLE001 — corrupt entry: recompute, rewrite
        return None


def _disk_store(key: str, consts: Dict[str, np.ndarray]) -> None:
    path = _disk_path(key)
    if path is None:
        return
    if sum(a.nbytes for a in consts.values()) > DISK_CACHE_MAX_BYTES:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   suffix=".npz.tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **consts)
            os.replace(tmp, path)   # atomic publish: readers never see partials
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    except OSError:
        pass                        # cache is best-effort; compute still wins


def _freeze(consts: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Make cached arrays read-only: they are shared across every consumer
    of this config hash, so in-place mutation would corrupt the process."""
    for a in consts.values():
        a.flags.writeable = False
    return consts


def init_pipeline(cfg: UltrasoundConfig, *,
                  cache: bool = True) -> Dict[str, np.ndarray]:
    """Precompute all pipeline constants (untimed, deterministic, cached).

    Memory tier first, then disk, then recompute (populating both). The
    returned dict is a fresh shallow copy — add/remove keys freely — but
    the arrays themselves are the cached (read-only) buffers; copy one
    before mutating it. ``exec_map``, ``stage_lowerings``, ``fusion``,
    ``precision``, and ``fusion_block`` are excluded from the cache key:
    they change how the graph is mapped / which kernels execute it /
    what the matmul operands are cast to, never its constants (the
    Pallas lowerings — fused included — consume the same delay tables
    as their xla references).
    """
    if not cfg.variant.concrete:
        raise ValueError(
            "cannot build constants for Variant.AUTO — resolve it first "
            "via repro.core.plan.plan_pipeline")
    if not cache:
        return stages.init_graph_consts(cfg)

    excl = ("exec_map", "stage_lowerings", "fusion", "precision",
            "fusion_block")
    key = f"{CONSTS_SCHEMA}-{config_hash(cfg, exclude=excl)}"
    if key in _MEM_CACHE:
        CONSTS_CACHE_STATS.mem_hits += 1
        _MEM_CACHE.move_to_end(key)
        return dict(_MEM_CACHE[key])

    consts = _disk_load(key)
    if consts is not None:
        CONSTS_CACHE_STATS.disk_hits += 1
        _mem_put(key, _freeze(consts))
        return dict(consts)

    CONSTS_CACHE_STATS.misses += 1
    consts = stages.init_graph_consts(cfg)
    _mem_put(key, _freeze(consts))
    _disk_store(key, consts)
    return dict(consts)


# ---------------------------------------------------------------------------
# Pipeline functions
# ---------------------------------------------------------------------------


def pipeline_fn(cfg: UltrasoundConfig) -> Callable:
    """Pure (consts, rf) -> image function for the configured modality."""
    return stages.graph_fn(cfg)


def monolithic_pipeline_fn(cfg: UltrasoundConfig) -> Callable:
    """Legacy single-function pipeline, kept as the reference oracle."""

    def run(consts, rf):
        iq = demod.rf_to_iq(consts, rf, cfg.decim)       # (n_s, n_c, n_f, 2)
        bf = beamform.beamform(cfg, consts, iq)          # (n_pix, n_f, 2)
        if cfg.modality == Modality.BMODE:
            return bmode.bmode_image(cfg, bf)            # (nz, nx, n_f)
        if cfg.modality == Modality.DOPPLER:
            return doppler.color_doppler_image(cfg, consts, bf)
        if cfg.modality == Modality.POWER_DOPPLER:
            return doppler.power_doppler_image(cfg, consts, bf)
        raise ValueError(cfg.modality)  # pragma: no cover

    return run


def _resolve_plan(cfg: UltrasoundConfig, plan, policy: Optional[str],
                  donate: Optional[bool] = None):
    """Shared plan resolution for the pipeline/executor constructors.

    No plan + no policy keeps today's behavior for concrete variants
    ("fixed") and falls back to the free deterministic resolver
    ("heuristic") when the config says AUTO — so
    `UltrasoundPipeline(cfg.with_(variant=Variant.AUTO))` just works.
    """
    from repro.core import plan as plan_lib
    if plan is not None:
        if policy is not None and policy != plan.policy:
            raise ValueError(
                f"both plan (policy={plan.policy!r}) and policy="
                f"{policy!r} given — pass one")
        if not plan.matches(cfg):
            raise ValueError(
                "plan was built for a different config geometry "
                f"(plan geometry_key={plan.geometry_key}) — its telemetry "
                "stamp would misattribute this pipeline; re-plan with "
                "plan_pipeline(cfg)")
        if cfg.variant.concrete and cfg.variant != plan.variant:
            raise ValueError(
                f"cfg explicitly requests variant={cfg.variant.value!r} but "
                f"the plan resolved {plan.variant.value!r} — an explicit "
                "variant is always honored, so pass a matching plan (or an "
                "AUTO config)")
        planned = dict(plan.stage_lowerings)
        for stage, name in cfg.stage_lowerings:
            if planned.get(stage, name) != name:
                raise ValueError(
                    f"cfg explicitly requests lowering {name!r} for stage "
                    f"{stage!r} but the plan resolved "
                    f"{planned[stage]!r} — an explicit lowering is always "
                    "honored, so pass a matching plan (or drop the "
                    "override)")
        if (cfg.fusion_block is not None
                and cfg.fusion_block != plan.fusion_block):
            raise ValueError(
                f"cfg explicitly requests fusion_block="
                f"{cfg.fusion_block} but the plan resolved "
                f"{plan.fusion_block} — an explicit block size is always "
                "honored, so pass a matching plan (or drop the override)")
        if plan.exec_map != cfg.exec_map:
            # The planner never decides exec_map (it copies the config's);
            # an explicit cfg.exec_map — e.g. "map" to bound peak memory —
            # must win over the value recorded at planning time, and the
            # telemetry stamp must reflect what actually runs.
            plan = dataclasses.replace(plan, exec_map=cfg.exec_map)
        return plan
    if policy is None:
        policy = "fixed" if cfg.variant.concrete else "heuristic"
    return plan_lib.plan_pipeline(cfg, policy=policy, donate=donate)


class UltrasoundPipeline:
    """Convenience wrapper: plan once, init once, jit once, call many times.

    Accepts an explicit `PipelinePlan` (or a `policy` name to build one);
    `self.cfg` is the plan-resolved config (concrete variant), `self.plan`
    records the decision for telemetry.
    """

    def __init__(self, cfg: UltrasoundConfig, *, plan=None,
                 policy: Optional[str] = None):
        self.plan = _resolve_plan(cfg, plan, policy)
        self.cfg = self.plan.concretize(cfg)
        self.consts = jax.tree.map(jnp.asarray, init_pipeline(self.cfg))
        self._fn = jax.jit(pipeline_fn(self.cfg))

    def __call__(self, rf: jnp.ndarray) -> jnp.ndarray:
        return self._fn(self.consts, rf)

    @property
    def jitted(self) -> Callable:
        """The compiled (consts, rf) -> image callable (public handle)."""
        return self._fn

    def stage_callables(self) -> Dict[str, Callable]:
        """Per-stage (consts, x) -> y functions, in graph order.

        Feeding each stage's output to the next reproduces `__call__`;
        used for the per-stage timing breakdown (§II-E telemetry). Each
        stage is jitted unless the plan toggles it off.
        """
        return {name: jax.jit(fn) if self.plan.stage_jit(name) else fn
                for name, fn in stages.stage_fns(self.cfg).items()}

    @property
    def input_bytes(self) -> int:
        return self.cfg.input_bytes

    @property
    def name(self) -> str:
        return f"{self.cfg.name}:{self.cfg.variant.value}"
