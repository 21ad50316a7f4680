"""AOT warm-start compilation + the persistent compilation cache.

The multi-tenant scheduler pays one compilation per config group — and
before this module it paid it *live*, on the group's first dispatch (or
a zero-batch warm-up run), silently excluded from every metric. The
ROADMAP's serving tier wants the production shape instead: compile each
group's ONE padded program ahead of time (`jax.jit(...).lower()
.compile()`), measure and stamp the cost, and back the whole thing with
JAX's persistent compilation cache so a *fresh process* — a serve
restart, a new autoscaled replica — starts warm instead of re-paying
XLA from scratch.

Three pieces:

  * `configure_persistent_cache()` — points JAX's persistent
    compilation cache at ``JAX_COMPILATION_CACHE_DIR`` when the
    environment sets it, and otherwise at one fixed directory inside
    the checkout (``<repo>/.jax_cache``, gitignored). The path is part
    of every entry's key, so it never depends on a temp name, a pid or
    the time. Entry points (``chip_smoke.py``, ``repro.launch.serve``,
    the benchmark mains) call it first thing; the warm-up path calls it
    again, which is a no-op once configured. JAX owns the entry format
    and writes entries atomically, so concurrent serve processes can
    share one directory. When JAX already memoized its "is the cache
    enabled?" decision (it checks once, at first compile), the memo is
    reset so the new directory takes effect.
  * `aot_warm(engine, pad_to)` — lower + compile the executor's
    fixed-shape padded dispatch program for ``(pad_to, *rf_shape)``
    WITHOUT executing it, install the executable on the engine (its
    ``dispatch_padded`` / ``call_padded`` prefer it over re-tracing
    through jit), optionally run one zero batch to pre-touch the
    allocator, and return an `AotProgram` carrying the measured
    ``compile_s`` / ``warmup_s`` — the number the scheduler stamps
    instead of silently excluding.
  * `warm_pool(specs, ...)` — the serving front door: one warm
    executor per distinct plan-resolved config group of a stream set,
    keyed exactly like the scheduler groups
    (canonical config hash, pad_to, n_devices, donate), so
    `serve_multitenant` and `benchmarks/multitenant.py` can build the
    pool once and start every window — every sweep cell — warm.

Keying: programs are keyed by the *plan geometry* — the canonical hash
of the plan-concretized config (every field that reaches the compiled
program: geometry, modality, resolved variant, lowerings, fusion,
precision) plus the padded batch shape, the device count, and the
resolved input-donation signature (donate_argnums is baked into the
compiled executable). Two specs that the scheduler would coalesce
share one pool entry; two that it would not can never collide.

Invariants (tests/test_aot.py): an AOT-warmed executor's outputs are
bit-identical to the un-warmed jit path; ``compile_s > 0`` and is
actually ahead of the serving window; with a populated persistent
cache a fresh process's warm-up is cheaper than the cold one and its
first dispatch shows no compile spike.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax

__all__ = ["AotProgram", "WarmPool", "aot_warm", "compile_cache_dir",
           "configure_persistent_cache", "set_compile_cache_dir",
           "warm_pool"]

# The checkout this module runs from (src/repro/core/aot.py -> <repo>):
# on-disk caches default to fixed, gitignored directories inside it.
CHECKOUT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_DIR, ".jax_cache")

_UNSET = object()
_cache_override = _UNSET
_cache_configured = _UNSET


def compile_cache_dir() -> Optional[str]:
    """Active persistent-compilation-cache dir (None = disabled).

    ``JAX_COMPILATION_CACHE_DIR`` when set, else `DEFAULT_CACHE_DIR`;
    `set_compile_cache_dir` overrides both (tests sandbox the cache).
    """
    if _cache_override is not _UNSET:
        return _cache_override
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def set_compile_cache_dir(path: Optional[str]) -> None:
    """Point the compile cache somewhere else (tests), or disable (None).

    Takes effect at the next `configure_persistent_cache()` call — the
    warm-pool builders call it on every pool, so in practice the next
    warm-up.
    """
    global _cache_override
    _cache_override = path


def configure_persistent_cache() -> Optional[str]:
    """Wire JAX's persistent compilation cache to `compile_cache_dir()`.

    Returns the directory in effect (None = disabled). Idempotent and
    cheap when nothing changed. JAX checks "should I use the cache?"
    once, at the first compilation of the process, and memoizes the
    answer — so enabling the cache *after* something already compiled
    needs that memo reset, which this does.
    """
    global _cache_configured
    from jax.experimental.compilation_cache import compilation_cache

    d = compile_cache_dir()
    if d == _cache_configured:
        return d
    if d is not None:
        os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    if d is not None:
        # Cache every program: serve programs are small and tiny-geometry
        # CI programs compile fast — the default size/time floors would
        # skip exactly the entries the warm-start contract needs.
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    _cache_configured = d
    return d


@dataclasses.dataclass(frozen=True)
class AotProgram:
    """One ahead-of-time-compiled padded serve program, with its cost."""

    key: str                 # canonical hash of the concretized config
    pad_to: int              # padded dispatch shape (rows)
    devices: int             # device count the program was built for
    compile_s: float         # lower+compile wall time (this process)
    warmup_s: float          # compile_s + the optional first execution
    cache_dir: Optional[str]     # persistent cache in effect, if any
    # The compiled executable (``as_text()`` shows whether a Pallas
    # kernel made it into the program: ``tpu_custom_call``).
    executable: object = dataclasses.field(default=None, repr=False,
                                           compare=False)


def aot_warm(engine, pad_to: int, *, execute: bool = True) -> AotProgram:
    """AOT-compile ``engine``'s fixed-shape padded program; install it.

    Lowers and compiles ``engine.jitted`` for a ``(pad_to, *rf_shape)``
    RF batch via the AOT path (`.lower().compile()`), so the cost is
    paid — and *measured* — here, never on a tenant's first frame. The
    executable is installed on the engine: `dispatch_padded` /
    `call_padded` at this shape run it directly, skipping jit's
    trace-cache lookup. With ``execute`` (default) one zero batch runs
    through the fresh executable so first-dispatch allocator work is
    also out of the serving window; both costs land in ``warmup_s``.
    """
    if pad_to < 1:
        raise ValueError(f"pad_to must be >= 1 (got {pad_to})")
    cache_dir = configure_persistent_cache()
    shape = (pad_to,) + engine.cfg.rf_shape
    dtype = np.dtype(engine.cfg.rf_dtype)
    t0 = time.perf_counter()
    compiled = engine.jitted.lower(
        engine.consts, jax.ShapeDtypeStruct(shape, dtype)).compile()
    compile_s = time.perf_counter() - t0
    engine.install_aot(pad_to, compiled)
    if execute:
        jax.block_until_ready(
            engine.dispatch_padded(np.zeros(shape, dtype), pad_to))
    warmup_s = time.perf_counter() - t0
    return AotProgram(
        key=engine.cfg.canonical_hash(), pad_to=pad_to,
        devices=getattr(engine, "n_devices", 1),
        compile_s=compile_s, warmup_s=warmup_s, cache_dir=cache_dir,
        executable=compiled)


@dataclasses.dataclass
class WarmEntry:
    """One warm executor + the measured cost of making it warm."""

    engine: object           # Batched/ShardedExecutor, AOT program installed
    program: AotProgram


# (config hash, pad_to, n_devices, donate). Donation is part of the
# COMPILED program — donate_argnums changes the executable's aliasing
# contract — so a warm executor is only cache-valid for callers that
# resolved the same donation signature.
PoolKey = Tuple[str, int, int, bool]


class WarmPool:
    """Plan-geometry-keyed pool of AOT-warmed serve executors.

    Keys are ``(canonical config hash of the plan-concretized config,
    pad_to, n_devices, donate)`` — exactly the scheduler's grouping
    plus the compiled shape and donation signature, so a pool built
    once serves every window (every sweep cell) that would have built
    the same executors, and a donating window can never be handed a
    non-donating executable (or vice versa).
    """

    def __init__(self):
        self._entries: Dict[PoolKey, WarmEntry] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: PoolKey) -> bool:
        return key in self._entries

    def keys(self) -> Tuple[PoolKey, ...]:
        return tuple(self._entries)

    def get(self, key: PoolKey) -> Optional[WarmEntry]:
        return self._entries.get(key)

    def put(self, key: PoolKey, entry: WarmEntry) -> None:
        self._entries[key] = entry

    @property
    def warmup_s(self) -> float:
        """Total measured warm-up cost across every pooled program."""
        return sum(e.program.warmup_s for e in self._entries.values())


def warm_pool(specs: Sequence, *, max_batch: int, devices=None,
              plan_policy: Optional[str] = None,
              pool: Optional[WarmPool] = None,
              donate: Optional[bool] = None) -> WarmPool:
    """One AOT-warmed executor per distinct config group of ``specs``.

    ``specs`` are `repro.launch.scheduler.StreamSpec`s (anything with a
    ``.cfg``); grouping matches `serve_multitenant` exactly — the
    plan-resolved canonical hash — at the padded dispatch shape
    ``max_batch`` over ``devices`` with the donation signature
    ``donate`` (None resolves through the plan / backend default,
    exactly as the executors themselves do). Pass an existing ``pool``
    to extend it incrementally (already-warm groups are not
    recompiled), e.g. across the cells of a benchmark sweep.
    """
    from repro.core.executor import (BatchedExecutor, ShardedExecutor,
                                     _resolve_donate)
    from repro.core.pipeline import _resolve_plan

    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1 (got {max_batch})")
    sharded = devices is not None and len(devices) > 1
    n_devices = len(devices) if sharded else 1
    if sharded and max_batch % n_devices:
        raise ValueError(
            f"max_batch={max_batch} must be a multiple of "
            f"n_devices={n_devices} for sharded dispatch")
    pool = pool if pool is not None else WarmPool()
    for spec in specs:
        plan = _resolve_plan(spec.cfg, None, plan_policy)
        key = (plan.concretize(spec.cfg).canonical_hash(), max_batch,
               n_devices, _resolve_donate(donate, plan))
        if key in pool:
            continue
        engine = (ShardedExecutor(spec.cfg, devices=devices, plan=plan,
                                  donate=donate)
                  if sharded else BatchedExecutor(spec.cfg, plan=plan,
                                                  donate=donate))
        program = aot_warm(engine, max_batch)
        pool.put(key, WarmEntry(engine=engine, program=program))
    return pool
