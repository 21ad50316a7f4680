"""Multi-tenant dynamic-batching serve scheduler: the serving loop.

Real deployments (the ROADMAP north star) are N independent probes —
mixed modalities, mixed geometries, mixed frame rates — each producing
acquisitions on its own clock, all contending for the same
accelerator. Accelerator serving throughput is won or lost in the
batching-under-latency-bound policy (Jouppi et al.: datacenter
inference batches aggressively but bounds queue delay), and the
determinism contract (TINA lineage, paper §II-C) requires that none of
that batching changes a single output bit.

This module is that frontend:

  * `StreamSpec` — one client: an `UltrasoundConfig`, an arrival
    process (`repro.data.traces.ArrivalProcess` — uniform open-loop
    ``phase_s + k / fps`` by default, or a `TraceArrival` replaying
    recorded timestamps bit-identically), a frame count, a seed, an
    optional connect/disconnect window (``start_s`` / ``stop_s`` —
    churn: frames whose arrival falls outside the window are dropped
    deterministically at admission), and an optional per-frame
    completion deadline.
  * `BatchPolicy` — the two knobs of dynamic batching: ``max_batch``
    (coalescing ceiling = the padded dispatch shape) and
    ``max_queue_delay_ms`` (the longest any frame may wait for
    companions; 0 = greedy dispatch-on-arrival).
  * `serve_multitenant` — per-config queues: frames are grouped by the
    full canonical config hash (only identical pipelines may share a
    compiled program), coalesced into batches under the policy, and
    launched through the executors' ``place`` + async
    ``dispatch_staged`` (over a mesh when ``devices`` spans one) at ONE
    fixed compiled shape per group — occupancy varies, the program
    never recompiles. Among queues eligible to flush (full, or oldest
    frame past the delay bound) the oldest head dispatches first — ties
    on identical head arrival times resolve to the first group in
    construction order — so a saturated tenant never starves a sparse
    one (FIFO fairness; frames of one stream never reorder).

Dispatch is PIPELINED: up to ``in_flight`` launched batches ride a
bounded ring as pending completions while the host keeps admitting
arrivals, coalescing queues, and launching the next eligible batch, so
the device does not idle during host-side bookkeeping and vice versa.
A single probe is one stream: ``serve_multitenant([spec])``.
Completions drain via non-blocking readiness checks, oldest-first *per
group* (a later batch of a group never retires before an earlier one,
so a stream's frames can never reorder no matter which ring slot
settles first); outputs are keyed by
(stream, seq), so even a cross-group out-of-order drain leaves no trace
in the pixels — the determinism oracle holds bit-for-bit at every
depth. Every group's padded program is compiled AHEAD of the window
(`repro.core.aot`: `jax.jit(...).lower().compile()` + the persistent
compilation cache), and the cost is measured and stamped
(``warmup_s``), never silently excluded.

The HOST TRANSFER path is zero-copy and (by default) asynchronous:
each group coalesces admitted frames straight into a preallocated
`repro.core.staging.StagingRing` slot (no stack, no pad concatenate —
the pad region was zeroed once at construction), the slot is committed
H2D through the executor's timed ``place`` and launched with
``dispatch_staged`` (optionally donating the device buffer —
``donate``), and retirements start their D2H with
``copy_to_host_async()`` the moment compute is detected settled, so
the admit loop never blocks on a transfer (``drain="async"``;
``drain="block"`` keeps the synchronous control path the benchmarks
gate against). The costs are stamped per window: ``stage_copy_s``,
``h2d_s``, ``d2h_s``, ``transfer_frac``.

Telemetry per window (stamped into the established NDJSON records by
`benchmarks/multitenant.py`): per-frame queue delay (dispatch − arrival)
and completion latency (done − arrival) distributions, aggregate and
per-stream (LatencyStats: p50/p95/p99, jitter, deadline-miss rate
against each stream's own budget), per-dispatch batch occupancy
(`OccupancyStats`: mean fill, full-batch rate), `InFlightStats` of the
ring, per-group resolved `PipelinePlan` stamps (serving context included:
warm_start, in_flight), warm-up seconds total and per group, and the
`ResourceStats` of the window (sampled at drain time, so peak-memory
telemetry sees overlapped batches live together).

Device time is not measured here but by the profiler, on the clock of
the device trace: every launched batch leaves ``serve.*`` spans
(`jax.profiler.TraceAnnotation`, carrying the batch's launch index
``idx`` and its group key ``group``) around the loop's steps, and the
compiled program names each stage (`repro.core.stages.graph_fn`). With
no trace running a span costs under a microsecond of host time.
docs/serving.md#tracing-a-serving-window lists them.

Invariants (asserted in tests/test_scheduler.py):

  * determinism oracle — every frame served through the coalescing
    scheduler is bit-identical (`np.array_equal`) to the same frame run
    alone through `monolithic_pipeline_fn`, across all three variants
    and both modalities, at in-flight depth 1 and >= 2, and under
    adversarially out-of-order completion drains: batching composition,
    padding, arrival order, and drain order leave no trace in the
    pixels;
  * a lone frame flushes once its queue delay reaches the policy bound
    — it never waits for companions that are not coming;
  * occupancy never exceeds ``max_batch``; the ring never exceeds
    ``in_flight``; warm-up compilation happens before the window opens
    and is *stamped* (``warmup_s``) rather than silently excluded;
  * the idle path never busy-spins: a non-positive sleep horizon always
    means an arrival or a flush is already due.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.profiler import TraceAnnotation

from repro.core.config import UltrasoundConfig
from repro.core.staging import StagingRing
from repro.data.traces import (ArrivalProcess, StreamTrace, Trace,
                               TraceArrival, mixed_phase, mixed_rate,
                               seed_space)

__all__ = ["BatchPolicy", "StreamSpec", "make_mixed_streams",
           "make_trace_streams", "trace_of_streams",
           "serve_multitenant"]


@dataclasses.dataclass(frozen=True)
class BatchPolicy:
    """Dynamic-batching policy: coalescing ceiling + queue-delay bound.

    ``max_batch`` is both the coalescing limit and the padded dispatch
    shape (one compiled program per config group). ``max_queue_delay_ms``
    bounds how long the OLDEST queued frame may wait for companions
    before the batch is flushed partial; 0 means dispatch whatever is
    queued the moment the device is free (greedy, lowest latency,
    worst occupancy).
    """

    max_batch: int = 4
    max_queue_delay_ms: float = 5.0

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1 "
                             f"(got {self.max_batch})")
        if self.max_queue_delay_ms < 0:
            raise ValueError(f"max_queue_delay_ms must be >= 0 "
                             f"(got {self.max_queue_delay_ms})")

    def json_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class StreamSpec:
    """One tenant: a probe configuration plus its arrival process.

    Arrivals: with the default ``arrival=None`` the stream is uniform
    open-loop — frame k arrives at ``phase_s + k / fps`` on the window
    clock (``phase_s`` staggers tenants so their bursts de-synchronize).
    Any `repro.data.traces.ArrivalProcess` plugs in instead: a
    `TraceArrival` replays recorded timestamps bit-identically.

    Connect window (churn): frames whose arrival timestamp falls before
    ``start_s`` or at/after ``stop_s`` are DROPPED deterministically at
    admission — the probe is not connected — and counted in the
    ``dropped`` telemetry. The decision uses only arrival timestamps,
    never wall clock, so a replay drops the same frames.

    RF content: ``pool`` pre-generated acquisitions cycle, so host-side
    synthesis stays out of the serving window. The cycle period is
    ``min(pool, n_frames)`` (never more pools than frames are
    synthesized); frame k carries RF
    ``synth_rf(cfg, seed=self.frame_seed(k))``, where `frame_seed`
    derives a per-(stream_id, seed) disjoint seed space via
    `repro.data.traces.seed_space` — two tenants never share a
    byte-identical frame just because their base seeds sit close.
    """

    stream_id: str
    cfg: UltrasoundConfig
    fps: float = 100.0
    n_frames: int = 16
    seed: int = 0
    pool: int = 4
    phase_s: float = 0.0
    deadline_ms: Optional[float] = None   # per-frame completion budget
    arrival: Optional[ArrivalProcess] = None
    start_s: float = 0.0                  # connect instant
    stop_s: Optional[float] = None        # disconnect instant (churn)

    def __post_init__(self):
        if self.fps <= 0:
            raise ValueError(f"fps must be > 0 (got {self.fps})")
        if self.n_frames < 1:
            raise ValueError(f"n_frames must be >= 1 "
                             f"(got {self.n_frames})")
        if self.pool < 1:
            raise ValueError(f"pool must be >= 1 (got {self.pool})")
        if self.start_s < 0:
            raise ValueError(f"start_s must be >= 0 "
                             f"(got {self.start_s})")
        if self.stop_s is not None and self.stop_s <= self.start_s:
            raise ValueError(f"stop_s={self.stop_s} must be > "
                             f"start_s={self.start_s}")
        if self.arrival is not None:
            try:
                n = len(self.arrival)          # type: ignore[arg-type]
            except TypeError:
                n = None
            if n is not None and self.n_frames > n:
                raise ValueError(
                    f"n_frames={self.n_frames} exceeds the arrival "
                    f"process's {n} recorded timestamps")

    def arrival_s(self, k: int) -> float:
        if self.arrival is not None:
            return self.arrival.arrival_s(k)
        return self.phase_s + k / self.fps

    def frame_seed(self, k: int) -> int:
        """The `synth_rf` seed of frame k: the pool cycles with period
        ``min(pool, n_frames)``, each slot in a seed space disjoint
        per (seed, stream_id)."""
        return seed_space("stream", self.seed, self.stream_id,
                          k % min(self.pool, self.n_frames))

    def in_window(self, t: float) -> bool:
        """Is the probe connected at window-clock time t?"""
        return t >= self.start_s and (self.stop_s is None
                                      or t < self.stop_s)


def make_mixed_streams(n_clients: int, cfg_bmode: UltrasoundConfig,
                       cfg_doppler: UltrasoundConfig, *,
                       base_fps: float = 120.0, n_frames: int = 24,
                       deadline_ms: Optional[float] = 100.0
                       ) -> List[StreamSpec]:
    """Mixed-tenant traffic: alternating modalities, staggered rates.

    Client i runs B-mode (even) or Color Doppler (odd) at
    ``base_fps / (1 + i/2)`` — tenants never share a clock, so the
    scheduler's coalescing has to earn its occupancy from genuinely
    unaligned arrivals. Phases stagger by 1/4 of the fastest period.
    Rates/phases come from `repro.data.traces.mixed_rate` /
    `mixed_phase` — the SAME helpers the ``steady`` trace generator
    uses, so a generated steady trace replays this schedule
    bit-identically (equal floats, equal trace_sha256).
    Used by the serving CLI (`repro.launch.serve`) and
    `benchmarks/multitenant.py`.
    """
    if n_clients < 1:
        raise ValueError(f"n_clients must be >= 1 (got {n_clients})")
    return [
        StreamSpec(
            stream_id=f"probe{i}",
            cfg=cfg_bmode if i % 2 == 0 else cfg_doppler,
            fps=mixed_rate(i, base_fps), n_frames=n_frames,
            seed=17 * i, phase_s=mixed_phase(i, base_fps),
            deadline_ms=deadline_ms)
        for i in range(n_clients)]


def make_trace_streams(trace: Trace, cfg_bmode: UltrasoundConfig,
                       cfg_doppler: UltrasoundConfig, *,
                       deadline_ms: Optional[float] = 100.0,
                       pool: int = 4) -> List[StreamSpec]:
    """Bind a recorded/generated `Trace` to the mixed-tenant configs.

    Stream i of the trace gets the same modality assignment (B-mode
    even, Doppler odd) and the same RF seed (``17 * i``) as
    `make_mixed_streams` client i, but its arrivals come from a
    `TraceArrival` — replayed bit-identically — and its connect window
    from the trace's ``start_s`` / ``stop_s``. Replaying a ``steady``
    trace therefore serves the exact frames `make_mixed_streams` would.
    """
    return [
        StreamSpec(
            stream_id=st.stream_id,
            cfg=cfg_bmode if i % 2 == 0 else cfg_doppler,
            fps=st.fps, n_frames=len(st.arrivals),
            seed=17 * i, pool=pool, deadline_ms=deadline_ms,
            arrival=TraceArrival(st.arrivals),
            start_s=st.start_s, stop_s=st.stop_s)
        for i, st in enumerate(trace.streams)]


def trace_of_streams(specs: Sequence[StreamSpec], *,
                     profile: Optional[str] = None,
                     seed: Optional[int] = None) -> Trace:
    """The `Trace` a set of specs will replay — uniform or recorded.

    Materializes every spec's arrival process into timestamps, so the
    uniform open-loop default and a `TraceArrival` replay of its saved
    copy produce the same trace — and therefore the same ``sha256``
    provenance stamp in the telemetry.
    """
    return Trace(
        streams=tuple(StreamTrace(
            stream_id=s.stream_id,
            arrivals=tuple(s.arrival_s(k) for k in range(s.n_frames)),
            fps=s.fps, start_s=s.start_s, stop_s=s.stop_s)
            for s in specs),
        profile=profile, seed=seed)


@dataclasses.dataclass
class _Frame:
    """One enqueued acquisition, tracked from arrival to completion."""

    stream: int            # index into the spec list
    seq: int               # per-stream sequence number
    rf: np.ndarray
    t_arrival: float       # window clock (s)
    t_dispatch: float = -1.0
    t_done: float = -1.0


class _Group:
    """All streams sharing one canonical config hash -> one executor."""

    def __init__(self, key: str, cfg: UltrasoundConfig, engine):
        self.key = key
        self.cfg = cfg
        self.engine = engine
        self.queue: collections.deque = collections.deque()
        self.stream_ids: List[str] = []
        self.occupancies: List[int] = []
        self.depths: List[int] = []       # ring depth at each launch
        self.n_pending = 0                # this group's batches in flight
        self.warm_source = "aot"          # "aot" | "pool"
        self.warmup_s = 0.0               # warm cost paid by THIS window
        self.ring: Optional[StagingRing] = None   # built per window


@dataclasses.dataclass
class _Pending:
    """One launched batch riding the in-flight ring until it settles."""

    group: _Group
    batch: List[_Frame]
    out: object                # device array, possibly still computing
    t_dispatch: float
    idx: int                   # launch index (position in dispatch_order)


def _ready(out) -> bool:
    """Non-blocking: has this dispatched batch's device buffer settled?

    Module-level so the out-of-order-drain determinism test can
    monkeypatch it with a seeded gate that delays arbitrary pendings.
    """
    try:
        return bool(out.is_ready())
    except AttributeError:     # plain numpy (already settled)
        return True


def _build_groups(specs: Sequence[StreamSpec], policy: BatchPolicy, *,
                  devices, plan_policy, pool=None, donate=None
                  ) -> Tuple[List["_Group"], List["_Group"]]:
    """Group specs by full config hash and build one executor each.

    Returns (groups, group_of_stream). Grouping uses the PLAN-RESOLVED
    config's canonical hash: two tenants may share a compiled program
    only when every config field agrees — same geometry, same modality,
    same resolved variant, same exec_map. `Variant.AUTO` tenants
    resolve through ``plan_policy`` first, so an AUTO B-mode probe and
    an explicit one land in the same group when the planner agrees.

    A `repro.core.aot.WarmPool` supplies already-warm executors: a pool
    hit (same hash, same padded shape, same device count, same resolved
    donation signature) reuses the pooled engine — AOT program
    installed, compilation already paid — and the group is marked
    ``warm_source="pool"`` with zero warm cost charged to this window.
    ``donate`` resolves exactly as the executor constructors resolve it
    (arg > plan > backend default), so a lookup and the engine it would
    otherwise build can never disagree on the donation signature.
    """
    from repro.core.executor import (_resolve_donate, make_executor,
                                     mesh_size)
    from repro.core.pipeline import _resolve_plan

    n_devices = mesh_size(devices, policy.max_batch)

    groups: Dict[str, _Group] = {}
    group_of_stream: List[_Group] = []
    for spec in specs:
        # Resolve the plan (cheap, cached) BEFORE building anything —
        # duplicate configs must share the group's one executor, not
        # construct a throwaway each.
        plan = _resolve_plan(spec.cfg, None, plan_policy)
        key = plan.concretize(spec.cfg).canonical_hash()
        if key not in groups:
            entry = (pool.get((key, policy.max_batch, n_devices,
                               _resolve_donate(donate, plan)))
                     if pool is not None else None)
            if entry is not None:
                g = _Group(key, entry.engine.cfg, entry.engine)
                g.warm_source = "pool"
            else:
                engine = make_executor(spec.cfg, devices=devices,
                                       max_batch=policy.max_batch,
                                       plan=plan, donate=donate)
                g = _Group(key, engine.cfg, engine)
            groups[key] = g
        groups[key].stream_ids.append(spec.stream_id)
        group_of_stream.append(groups[key])
    return list(groups.values()), group_of_stream


def _make_frames(specs: Sequence[StreamSpec]
                 ) -> Tuple[List[_Frame], List[int]]:
    """Pre-generate every in-window frame (arrival-sorted) + drops.

    Synthesis is untimed. Frames whose arrival falls outside the
    stream's connect window are dropped HERE — the admit/retire
    decision depends only on trace timestamps, never on wall clock, so
    replays drop identically. Returns (frames, dropped-per-stream).
    The sort key ``(t_arrival, stream, seq)`` makes simultaneous
    arrivals (equal timestamps — bursts, trace replays) admit in
    deterministic spec order.
    """
    from repro.data import synth_rf

    pools = []
    for spec in specs:
        n = min(spec.pool, spec.n_frames)
        pools.append([synth_rf(spec.cfg, seed=spec.frame_seed(i))
                      for i in range(n)])
    frames: List[_Frame] = []
    dropped = [0] * len(specs)
    for si, spec in enumerate(specs):
        for k in range(spec.n_frames):
            t = spec.arrival_s(k)
            if not spec.in_window(t):
                dropped[si] += 1
                continue
            frames.append(_Frame(stream=si, seq=k,
                                 rf=pools[si][k % len(pools[si])],
                                 t_arrival=t))
    frames.sort(key=lambda f: (f.t_arrival, f.stream, f.seq))
    return frames, dropped


def _pick_group(groups: List[_Group], now: float,
                policy: BatchPolicy) -> Optional[_Group]:
    """The group to flush now, or None if every queue may keep waiting.

    A queue becomes *eligible* when it is full (occupancy is free
    throughput) or when its oldest frame has waited max_queue_delay.
    Among eligible queues the OLDEST head wins — bounded queue delay
    beats occupancy, so a saturated tenant whose queue is always full
    can never starve a sparse tenant's expired frame past the bound by
    more than the in-service dispatch ahead of it.
    """
    delay_s = policy.max_queue_delay_ms / 1e3
    best, best_head = None, None
    for g in groups:
        if not g.queue:
            continue
        head = g.queue[0].t_arrival
        if len(g.queue) >= policy.max_batch or now - head >= delay_s:
            # Strict < keeps ties deterministic: equal heads resolve to
            # the FIRST group in construction (= spec) order, so a rerun
            # with identical arrivals replays identical dispatch order.
            if best is None or head < best_head:
                best, best_head = g, head
    return best


_POLL_S = 2e-4       # base readiness-poll grain (REPRO_POLL_S overrides)
_POLL_CAP_S = 5e-3   # adaptive-grain ceiling: completion-detection bound


def _poll_base() -> float:
    """The busy-poll base grain: ``REPRO_POLL_S`` env override or the
    built-in default. Invalid / non-positive values fall back rather
    than crash a serving window."""
    env = os.environ.get("REPRO_POLL_S")
    if env:
        try:
            v = float(env)
            if v > 0:
                return v
        except ValueError:
            pass
    return _POLL_S


def _poll_grain(now: float, horizon: Optional[float], *,
                base: float, cap: float = _POLL_CAP_S) -> float:
    """Adaptive busy-poll sleep while dispatches are in flight.

    A fixed fine grain spins the host core the staging ring now shares
    even when the next scheduling decision is provably far away (a
    low-rate stream whose next arrival is milliseconds out). Instead
    the grain stretches toward the `_idle_horizon` — there is nothing
    to admit or flush before it — but never past ``cap``, which bounds
    how late a completion can be detected, and never below ``base``,
    the spin floor when work is imminent (horizon past due, or no
    horizon at all while the final batches settle).
    """
    if horizon is None:
        return base
    return min(max(base, horizon - now), cap)


def _idle_horizon(frames: List[_Frame], ai: int, groups: List[_Group],
                  delay_s: float) -> Optional[float]:
    """Next window-clock instant at which the idle loop can act.

    Either the next un-admitted arrival or the earliest queue-delay
    expiry (head arrival + delay bound), whichever is sooner; None when
    neither exists. No-busy-spin invariant (tested directly): whenever
    the horizon is <= now, progress is already available — an arrival
    is due for admission, or some queue head has waited past the delay
    bound and `_pick_group` returns it. The serving loop therefore only
    sleeps on a strictly positive horizon gap, and a non-positive gap
    always precedes an admission or a launch, never a spin.
    """
    horizon = []
    if ai < len(frames):
        horizon.append(frames[ai].t_arrival)
    horizon.extend(g.queue[0].t_arrival + delay_s
                   for g in groups if g.queue)
    return min(horizon) if horizon else None


def serve_multitenant(streams: Sequence[StreamSpec], *,
                      policy: BatchPolicy = BatchPolicy(),
                      in_flight: int = 2,
                      devices=None, plan_policy: Optional[str] = None,
                      collect_outputs: bool = False,
                      pool=None, load_profile: str = "steady",
                      drain: str = "async",
                      donate: Optional[bool] = None) -> dict:
    """Serve N open-loop tenants through coalescing dynamic batching.

    Runs one serving window: every frame of every stream is admitted at
    its scheduled arrival time (uniform or trace-replayed — see
    `StreamSpec.arrival`), queued per config group, coalesced
    under ``policy``, executed at the group's fixed padded shape, and
    timed from arrival to completion. Frames arriving outside a
    stream's connect window are dropped deterministically at admission
    (churn); frames admitted before a disconnect always drain.
    Dispatch is PIPELINED to depth
    ``in_flight``: launched batches ride a bounded ring as pending
    completions while the host keeps admitting, coalescing, and
    launching; completions drain via non-blocking readiness checks,
    oldest-first per group, so frames of one stream never reorder.
    ``in_flight=1`` recovers the synchronous launch-block-retire loop
    exactly (the ring holds one slot).

    Every group's padded program is AOT-compiled before the window
    opens (`repro.core.aot.aot_warm`, persistent compilation cache
    included) and the cost is stamped into the stats (``warmup_s``).
    Pass a `repro.core.aot.WarmPool` (built by
    `repro.core.aot.warm_pool`) to start warm: pool hits reuse the
    pooled executor and charge zero warm cost to this window.

    HOST TRANSFER PATH (docs/serving.md#host-transfer-path): each
    group coalesces straight into a preallocated `StagingRing` slot
    (zero extra host copies — no stack, no pad concatenate), the slot
    is committed H2D by the executor's ``place`` (timed: ``h2d_s``)
    and launched via ``dispatch_staged``. Retirement is governed by
    ``drain``:

      * ``"async"`` (default) — when a batch's compute is detected
        settled it leaves the in-flight ring immediately (the next
        launch may proceed) and ``copy_to_host_async()`` starts its
        D2H in the background; the images are harvested on a LATER
        drain pass, so only the residual transfer tail is ever waited
        on (``d2h_s``). Group-FIFO retirement order is preserved:
        detection scans in launch order and skips a group whose older
        batch is still pending, and harvests happen in detection
        order.
      * ``"block"`` — the pre-staging behavior: detection immediately
        blocks on the compute and performs a synchronous D2H before
        the loop continues. Kept as the control cell the benchmarks
        gate the async win against.

    ``donate`` opts the compiled programs into consuming their device
    input buffer (donate_argnums; None = plan / backend default —
    False on CPU where XLA cannot alias). Safe with the staging ring:
    ``place`` always produces a fresh device array, the reused host
    slot is never donated.

    ``devices``: a sequence of >= 2 local devices routes dispatch
    through a `ShardedExecutor` over them (``max_batch`` must divide
    evenly; `repro.core.executor.make_executor` makes the choice).
    ``plan_policy`` resolves `Variant.AUTO` tenants (repro.core.plan).
    ``collect_outputs=True`` additionally returns every served image
    (``outputs[stream_id][seq]``, numpy) — the hook the
    determinism-oracle tests compare against the per-frame monolithic
    reference.

    Returns a stats dict (schema: `repro.bench.schema`, kind
    "multitenant" once the benchmark stamps it): aggregate + per-stream
    latency and queue-delay LatencyStats, OccupancyStats,
    ``in_flight_occupancy``, warm-up seconds, per-group plan stamps,
    ResourceStats, sustained MB/s / FPS / acq/s. Load provenance is
    stamped on every window: ``load_profile`` (the scenario name —
    part of the gate's cell identity), ``trace_sha256`` (the
    `trace_of_streams` hash of the exact arrival schedule served),
    ``dropped`` (out-of-window frames, aggregate and per stream), and
    ``dispatch_order`` (the launched batches as ``[stream_id, seq]``
    lists, in launch order — what the trace-replay determinism oracle
    compares across reruns).
    """
    from repro.bench.harness import (in_flight_stats, latency_stats,
                                     occupancy_stats)
    from repro.bench.resources import ResourceMeter
    from repro.bench.stats import bootstrap_ci
    from repro.core.aot import WarmEntry, aot_warm

    if not streams:
        raise ValueError("serve_multitenant needs at least one stream")
    if in_flight < 1:
        raise ValueError(f"in_flight must be >= 1 (got {in_flight})")
    if drain not in ("async", "block"):
        raise ValueError(f"drain must be 'async' or 'block' "
                         f"(got {drain!r})")
    ids = [s.stream_id for s in streams]
    if len(set(ids)) != len(ids):
        raise ValueError(f"duplicate stream_id in {ids}")

    specs = list(streams)
    groups, group_of_stream = _build_groups(
        specs, policy, devices=devices, plan_policy=plan_policy,
        pool=pool, donate=donate)
    frames, dropped_per_stream = _make_frames(specs)
    if not frames:
        raise ValueError(
            "every frame falls outside its stream's connect window — "
            "nothing to serve (check the trace's start_s/stop_s)")
    trace_sha256 = trace_of_streams(specs).sha256()

    # Meter before warm-up: the NVML idle baseline must see the board
    # cold; one meter spans every group's devices.
    meter = ResourceMeter()

    # Warm-up: AOT-compile each group's ONE padded program (occupancy 1
    # and max_batch hit the same shape) ahead of the window — measured
    # and stamped, not silently excluded. Pool hits already paid;
    # misses are warmed here and published back so the next window
    # (the next sweep cell) starts from "pool".
    for g in groups:
        if g.warm_source == "pool":
            continue
        prog = aot_warm(g.engine, policy.max_batch)
        g.warmup_s = prog.warmup_s
        if pool is not None:
            pool.put((g.key, policy.max_batch, g.engine.n_devices,
                      g.engine.donate),
                     WarmEntry(engine=g.engine, program=prog))
    warmup_s = sum(g.warmup_s for g in groups)

    # Staging rings: per group, in_flight+1 preallocated padded host
    # buffers (the minimum that can never alias a slot the device is
    # still reading — see repro.core.staging). Built per window so ring
    # depth tracks this window's in_flight; pooled engines are shared,
    # rings are not.
    for g in groups:
        g.ring = StagingRing(policy.max_batch, g.cfg.rf_shape,
                             g.cfg.rf_dtype, depth=in_flight)

    outputs: Dict[str, dict] = {s.stream_id: {} for s in specs}
    delay_s = policy.max_queue_delay_ms / 1e3

    # In-flight ring. ``landing`` holds batches whose COMPUTE is known
    # settled (they no longer occupy the in-flight ring) but whose
    # images are still crossing D2H (async drain).
    pending: collections.deque = collections.deque()
    landing: collections.deque = collections.deque()
    dispatch_order: List[List[List[object]]] = []   # [[stream_id, seq]]
    depth_samples: List[int] = []
    h2d_s = 0.0               # timed `place` (host buffer -> device)
    d2h_s = 0.0               # residual wait for images to land on host
    poll_base = _poll_base()

    meter.start()
    t0 = time.perf_counter()

    def clk() -> float:
        return time.perf_counter() - t0

    def harvest(p: _Pending) -> int:
        """Copy a settled batch's images to host; retire its frames.

        Under the async drain the D2H was started at detection time,
        so the ``np.asarray`` here pays only the residual transfer
        tail — that residual is what ``d2h_s`` measures. ``t_done`` is
        stamped once the images are ON THE HOST: completion latency
        includes the transfer, exactly as the blocking drain counts it.
        """
        nonlocal d2h_s
        with TraceAnnotation("serve.harvest", idx=p.idx, group=p.group.key):
            t = time.perf_counter()
            out = np.asarray(p.out)
            d2h_s += time.perf_counter() - t
        t_done = clk()
        p.group.n_pending -= 1
        p.group.occupancies.append(len(p.batch))
        for i, f in enumerate(p.batch):
            f.t_dispatch, f.t_done = p.t_dispatch, t_done
            if collect_outputs:
                outputs[specs[f.stream].stream_id][f.seq] = out[i]
        return len(p.batch)

    def drain_pending(block: bool) -> int:
        """Retire settled pendings, oldest-first per group.

        Scanning the ring in launch order and skipping any group whose
        older batch is still pending guarantees a later batch of a
        group never retires before an earlier one — out-of-order
        settlement across groups is harvested, within a group it is
        serialized (outputs are keyed by (stream, seq) regardless, so
        this is a latency-accounting discipline, not a correctness
        crutch). With ``block`` the oldest pending of each group is
        waited on (final flush).

        Async mode splits retirement in two: detection frees the
        in-flight slot and starts the D2H in the background; the
        harvest (above) runs at the START of the next drain pass, so
        the admit/launch work in between is the transfer's head start.
        Harvests run in detection order — group-FIFO is preserved
        end to end.
        """
        retired = 0
        while landing:
            retired += harvest(landing.popleft())
        seen: set = set()
        for p in list(pending):
            if id(p.group) in seen:
                continue
            seen.add(id(p.group))
            if not (block or _ready(p.out)):
                continue
            if block:
                jax.block_until_ready(p.out)
            meter.sample()     # detection: overlapped batches are live
            pending.remove(p)
            with TraceAnnotation("serve.detect", idx=p.idx,
                                 group=p.group.key):
                pass
            if drain == "block":
                retired += harvest(p)
            else:
                try:
                    p.out.copy_to_host_async()
                except AttributeError:   # backend without async D2H
                    pass
                landing.append(p)
        return retired

    ai, done = 0, 0
    while done < len(frames):
        now = clk()
        while ai < len(frames) and frames[ai].t_arrival <= now:
            f = frames[ai]
            ai += 1
            group_of_stream[f.stream].queue.append(f)

        done += drain_pending(block=False)

        if len(pending) < in_flight:
            g = _pick_group(groups, clk(), policy)
            if g is not None:
                batch = [g.queue.popleft()
                         for _ in range(min(len(g.queue),
                                            policy.max_batch))]
                # Zero-copy launch: coalesce straight into the group's
                # staging-ring slot (pad rows pre-zeroed; ring depth
                # covers the in-flight bound so the slot cannot alias a
                # batch the device still reads), timed H2D commit, then
                # launch-only dispatch.
                idx = len(dispatch_order)
                t_dispatch = clk()
                with TraceAnnotation("serve.stage", idx=idx, group=g.key):
                    buf, _ = g.ring.stage([f.rf for f in batch])
                with TraceAnnotation("serve.place", idx=idx, group=g.key):
                    t = time.perf_counter()
                    dev = g.engine.place(buf)
                    h2d_s += time.perf_counter() - t
                with TraceAnnotation("serve.launch", idx=idx, group=g.key):
                    out = g.engine.dispatch_staged(dev, policy.max_batch)
                pending.append(_Pending(group=g, batch=batch, out=out,
                                        t_dispatch=t_dispatch, idx=idx))
                dispatch_order.append(
                    [[specs[f.stream].stream_id, f.seq] for f in batch])
                g.n_pending += 1
                g.depths.append(len(pending))
                depth_samples.append(len(pending))
                continue          # keep launching while the ring has room

        if landing:
            # Nothing to admit or launch right now, but images are in
            # flight D2H: finish them instead of sleeping on top of
            # them, so frames retire no later than the blocking drain
            # would have retired them.
            done += drain_pending(block=False)
            continue

        if pending:
            # Device busy: poll readiness. The grain adapts — fine
            # (``poll_base``) while the next scheduling decision is
            # imminent, stretching toward the idle horizon (capped)
            # when it is not, so low-rate streams stop spinning the
            # core the staging ring shares.
            dt = _poll_grain(clk(),
                             _idle_horizon(frames, ai, groups, delay_s),
                             base=poll_base)
            with TraceAnnotation("serve.poll"):
                time.sleep(dt)
            continue

        # Fully idle: sleep to the next arrival or the earliest
        # queue-delay expiry, whichever comes first. A non-positive gap
        # means progress is already due (see `_idle_horizon`) — loop.
        hz = _idle_horizon(frames, ai, groups, delay_s)
        if hz is not None:
            dt = hz - clk()
            if dt > 0:
                with TraceAnnotation("serve.idle"):
                    time.sleep(min(dt, 0.05))

    wall = clk()
    resources = meter.stop()
    stage_copy_s = sum(g.ring.stage_copy_s for g in groups)

    # ---- telemetry ----------------------------------------------------
    def budget(spec):
        return (spec.deadline_ms / 1e3
                if spec.deadline_ms is not None else None)

    per_stream = {}
    misses, with_budget = 0, 0
    for si, spec in enumerate(specs):
        fs = [f for f in frames if f.stream == si]
        # A fully-dropped stream (disconnected before its first
        # arrival) has no latency distribution — the blocks are None
        # (nullable in the schema), never empty stats.
        lat = (latency_stats([f.t_done - f.t_arrival for f in fs],
                             budget_s=budget(spec)) if fs else None)
        qd = (latency_stats([f.t_dispatch - f.t_arrival for f in fs])
              if fs else None)
        if budget(spec) is not None:
            # Count misses directly from the per-frame completion
            # latencies — re-deriving the count from the rounded
            # miss_rate float loses frames once n is large enough that
            # rate*n straddles a .5 boundary.
            misses += sum(1 for f in fs
                          if f.t_done - f.t_arrival > budget(spec))
            with_budget += len(fs)
        per_stream[spec.stream_id] = {
            "pipeline": spec.cfg.name,
            "variant": group_of_stream[si].cfg.variant.value,
            "arrival_fps": spec.fps,
            "acquisitions": len(fs),           # served (admitted) frames
            "frames": len(fs) * spec.cfg.n_f,
            "dropped": dropped_per_stream[si],  # out-of-window arrivals
            "deadline_ms": spec.deadline_ms,
            "latency": lat.json_dict() if lat else None,
            "queue_delay": qd.json_dict() if qd else None,
            "deadline_miss_rate": lat.miss_rate if lat else 0.0,
        }

    # Throughput counts what was SERVED: dropped (disconnected) frames
    # never reached the device and must not inflate MB/s or acq/s.
    acqs = len(frames)
    total_frames = sum(per_stream[s.stream_id]["frames"] for s in specs)
    total_bytes = sum(
        per_stream[s.stream_id]["acquisitions"] * s.cfg.input_bytes
        for s in specs)
    all_occ = [n for g in groups for n in g.occupancies]
    stats = {
        "name": (f"multitenant/{len(specs)}streams/{len(groups)}groups"
                 f"/b{policy.max_batch}q{policy.max_queue_delay_ms:g}"
                 f"if{in_flight}/{drain}/{load_profile}"),
        "clients": len(specs),
        "policy": policy.json_dict(),
        "in_flight": in_flight,
        "drain": drain,
        "load_profile": load_profile,
        "trace_sha256": trace_sha256,
        "dropped": sum(dropped_per_stream),
        "dispatch_order": dispatch_order,
        "wall_s": wall,
        "warmup_s": warmup_s,
        "acquisitions": acqs,
        "frames": total_frames,
        "sustained_mbps": total_bytes / (wall * 1e6),
        "fps": total_frames / wall,
        "acq_per_s": acqs / wall,
        # One serving window = one run: a degenerate (zero-width)
        # interval. benchmarks/multitenant.py --repeats replaces this
        # with the bootstrap CI over repeated windows; the schema
        # requires the stamp either way so the gate always has one.
        "acq_per_s_ci": bootstrap_ci([acqs / wall]).json_dict(),
        "deadline_miss_rate": (misses / with_budget if with_budget
                               else 0.0),
        # Host transfer telemetry: all three are host-thread-sequential
        # slices of the wall, so the fraction is well-defined in [0,1].
        # Under the async drain d2h_s is only the residual tail the
        # harvest still had to wait on — the overlap win shows up as
        # this number shrinking, not as transfers disappearing.
        "stage_copy_s": stage_copy_s,
        "h2d_s": h2d_s,
        "d2h_s": d2h_s,
        "transfer_frac": min(1.0, (stage_copy_s + h2d_s + d2h_s)
                             / wall) if wall > 0 else 0.0,
        "latency": latency_stats(
            [f.t_done - f.t_arrival for f in frames]).json_dict(),
        "queue_delay": latency_stats(
            [f.t_dispatch - f.t_arrival for f in frames]).json_dict(),
        "occupancy": occupancy_stats(all_occ,
                                     policy.max_batch).json_dict(),
        "in_flight_occupancy": in_flight_stats(
            depth_samples, in_flight).json_dict(),
        "per_stream": per_stream,
        "groups": {
            g.key: {
                "plan": g.engine.plan.with_serving(
                    warm_start=g.warm_source,
                    in_flight=in_flight).json_dict(),
                "streams": list(g.stream_ids),
                "batches": len(g.occupancies),
                "warmup_s": g.warmup_s,
                "warm_source": g.warm_source,
                # A group whose every stream was fully dropped launches
                # zero batches — no distributions to report.
                "occupancy": (occupancy_stats(
                    g.occupancies, policy.max_batch).json_dict()
                    if g.occupancies else None),
                "in_flight": (in_flight_stats(
                    g.depths, in_flight).json_dict()
                    if g.depths else None),
            } for g in groups},
        "resources": resources.json_dict(),
    }
    if collect_outputs:
        stats["outputs"] = {
            sid: [seqs[k] for k in sorted(seqs)]
            for sid, seqs in outputs.items()}
    return stats
