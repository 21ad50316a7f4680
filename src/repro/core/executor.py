"""Batched + multi-device sharded stage-graph executors.

The paper (and the legacy `UltrasoundPipeline`) times one acquisition per
call. Production traffic wants N acquisitions per dispatch so the fixed
dispatch/launch overhead amortizes and the compiler sees the whole batch
— and past one device, wants that batch *split across every local
device* so throughput scales with hardware instead of clock speed.

Public API
----------
`BatchedExecutor`  — init once, jit once, run (B, n_l, n_c, n_f)
    batches many times on the default device. The batch axis carries the
    logical "batch" sharding name, so under an active mesh binding
    (runtime/sharding.py) it composes with the LM half's meshes.

Both executors expose the serving tier's dispatch granularities:
``__call__`` (synchronous semantics, caller blocks when it reads),
``call_padded`` (fixed-shape ragged dispatch, valid rows sliced off —
the one-batch-at-a-time scheduler entry), ``dispatch_padded`` (the
ASYNC form of call_padded: returns the *padded, unsynchronized* device
array immediately so the host keeps coalescing and launching while the
device executes — the caller slices valid rows after it drains; see
repro.launch.scheduler's in-flight ring), and the zero-copy staged
pair ``place`` + ``dispatch_staged`` (the batch was already padded
into a `repro.core.staging.StagingRing` slot: `place` is the timed H2D
commit, `dispatch_staged` is launch-only — `dispatch_padded` is now
exactly ``dispatch_staged(place(_pad_rows(...)))``). Donation stays
safe across all of them: every dispatch consumes a freshly-placed
device batch, never a caller-retained device array (host ring slots
are reused, their device copies are not). `install_aot` (fed by
repro.core.aot) pins an ahead-of-time-compiled executable for one
padded shape; the padded entry points prefer it over re-entering jit.
`ShardedExecutor`  — the same contract, data-parallel over an explicit
    1-D ``jax.sharding.Mesh`` of local devices ("data" axis): consts are
    replicated, the acquisition batch axis is split via `NamedSharding`,
    and outputs come back batch-sharded. Uneven batches (B % devices
    != 0) are zero-padded to the next multiple and the padding is
    sliced off the result — callers never see it. On hosts with one
    physical device, force a multi-device CPU mesh anywhere with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    JAX initializes; see benchmarks/scaling.py and
    tests/test_sharded_executor.py).

Both executors map the composed stage graph over the leading batch axis:

  * ``cfg.exec_map == "vmap"`` — vectorize: one fused program over the
    batch (throughput-optimal; peak memory scales with batch size),
  * ``cfg.exec_map == "map"``  — sequentialize via ``lax.map`` (constant
    memory; use when the vmapped CNN-variant operator would not fit).

Execution decisions (variant — possibly ``Variant.AUTO`` —, exec_map,
donation) resolve through a `PipelinePlan` (repro.core.plan); pass one
explicitly or let the constructor build it (`policy=` selects fixed /
heuristic / autotune). The `ShardedExecutor` stamps its device topology
into the plan (`PipelinePlan.with_devices`) so every telemetry record
downstream names the mesh it ran on. Constants come from the shared
two-tier cache, so a serve restart or a variant sweep pays the
delay-table precompute once.

Invariants: executors are immutable after construction (one compiled
program each); a sharded and a single-device executor built from the
same config produce allclose images for any batch size (asserted in
tests/test_sharded_executor.py); the RF input buffer is donated only on
accelerator backends (each batch is consumed exactly once in the
streaming loop).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.config import UltrasoundConfig
from repro.core.pipeline import _resolve_plan, init_pipeline
from repro.core.stages import graph_fn
from repro.runtime import sharding


def _mapped_graph_fn(cfg: UltrasoundConfig):
    """The stage graph mapped over the leading batch axis per exec_map."""
    fn = graph_fn(cfg)
    if cfg.exec_map == "vmap":
        return jax.vmap(fn, in_axes=(None, 0))
    # UltrasoundConfig.__post_init__ already validated against EXEC_MAPS
    assert cfg.exec_map == "map", cfg.exec_map

    def mapped(consts, rf_b):
        return jax.lax.map(lambda rf: fn(consts, rf), rf_b)
    return mapped


def _pad_rows(rf_batch, pad_to: int) -> tuple:
    """Zero-pad a ragged batch up to ``pad_to`` rows; returns (batch, b).

    Shared by the executors' ``call_padded`` fixed-shape dispatch: the
    multi-tenant scheduler coalesces heterogeneous arrivals into batches
    of any occupancy 1..pad_to, but every dispatch must hit the SAME
    compiled program — a recompile per occupancy would stall the serving
    loop. Pad rows are zeros; per-example mapping (vmap / lax.map) keeps
    them from influencing the valid rows, and callers slice them off.

    Host (numpy) batches pad on the host: the concatenate then costs a
    memcpy instead of an op-by-op XLA program per distinct occupancy —
    which would be exactly the hidden first-dispatch compile the AOT
    warm-start contract forbids. Device arrays keep the jnp path (their
    pad program caches after one occupancy-shaped compile).
    """
    b = rf_batch.shape[0]
    if b < 1:
        raise ValueError("empty RF batch")
    if b > pad_to:
        raise ValueError(
            f"batch of {b} exceeds pad_to={pad_to} — the scheduler must "
            "never coalesce past its policy's max_batch")
    if b == pad_to:
        return rf_batch, b
    xp = np if isinstance(rf_batch, np.ndarray) else jnp
    fill = xp.zeros((pad_to - b,) + rf_batch.shape[1:], rf_batch.dtype)
    return xp.concatenate([rf_batch, fill]), b


def _resolve_donate(donate: Optional[bool], plan) -> bool:
    """Donation precedence: constructor arg > plan > backend default.

    It is a no-op warning on the CPU stand-in; enable it only where the
    runtime can actually alias the buffer.
    """
    if donate is None:
        donate = plan.donate
    if donate is None:
        donate = jax.default_backend() != "cpu"
    return donate


class BatchedExecutor:
    """Init once, jit once, run (B, n_l, n_c, n_f) batches many times."""

    def __init__(self, cfg: UltrasoundConfig, *,
                 donate: Optional[bool] = None, plan=None,
                 policy: Optional[str] = None):
        self.plan = _resolve_plan(cfg, plan, policy, donate=donate)
        self.cfg = self.plan.concretize(cfg)
        self.consts = jax.tree.map(jnp.asarray, init_pipeline(self.cfg))
        mapped = _mapped_graph_fn(self.cfg)

        def run(consts, rf_b):
            rf_b = sharding.shard_pin(rf_b, d0="batch")
            return mapped(consts, rf_b)

        self.donate = _resolve_donate(donate, self.plan)
        self._fn = jax.jit(run, donate_argnums=(1,) if self.donate else ())
        self._aot: dict = {}              # pad_to -> AOT-compiled executable

    def __call__(self, rf_batch: jnp.ndarray) -> jnp.ndarray:
        """(B, n_l, n_c, n_f) RF batch -> (B, *image_shape)."""
        return self._fn(self.consts, rf_batch)

    def install_aot(self, pad_to: int, compiled) -> None:
        """Pin an AOT-compiled executable for the ``pad_to`` dispatch
        shape (built by `repro.core.aot.aot_warm` — lower+compile,
        never a live first-dispatch compilation)."""
        self._aot[pad_to] = compiled

    def place(self, rf_batch) -> jnp.ndarray:
        """H2D: commit an already-padded host batch to the device.

        The staging-ring entry (repro.core.staging): the buffer is a
        ring slot the caller keeps reusing, so this ALWAYS produces a
        fresh device array — the host slot is free to be rewritten
        once the in-flight bound says its dispatch settled, and the
        device array is safe to donate into the compiled program.
        """
        return jnp.asarray(rf_batch)

    def dispatch_staged(self, dev_batch, pad_to: int) -> jnp.ndarray:
        """Async dispatch of an already-placed ``(pad_to, ...)`` batch.

        The zero-copy serving entry: the batch was padded by a staging
        ring and moved by `place`, so this is launch-only — through the
        AOT executable when one is installed. With donation enabled the
        compiled program consumes ``dev_batch``; callers must not
        reuse the device array (the host ring slot stays theirs).
        """
        fn = self._aot.get(pad_to, self._fn)
        return fn(self.consts, dev_batch)

    def dispatch_padded(self, rf_batch, pad_to: int) -> jnp.ndarray:
        """Async fixed-shape dispatch: the PADDED, UNSYNCED output.

        The in-flight serving entry: pads to ``pad_to`` rows, launches
        (through the AOT executable when one is installed), and returns
        the device array without blocking or slicing — the caller
        tracks how many rows are valid and slices after it drains
        (`jax.block_until_ready` / ``.is_ready()``). Donation-safe:
        the launched buffer is the freshly-padded batch, never an array
        the caller still holds.
        """
        rf_batch, _ = _pad_rows(rf_batch, pad_to)
        return self.dispatch_staged(self.place(rf_batch), pad_to)

    def call_padded(self, rf_batch: jnp.ndarray,
                    pad_to: int) -> jnp.ndarray:
        """Fixed-shape dispatch of a ragged batch (B <= pad_to rows).

        Heterogeneous-arrival entry point for the dynamic-batching
        scheduler (repro.launch.scheduler): zero-pads the batch to
        ``pad_to`` rows so every occupancy 1..pad_to reuses one compiled
        program, then slices the valid rows off the result. Pad rows
        cost compute, never a recompile.
        """
        b = rf_batch.shape[0]
        out = self.dispatch_padded(rf_batch, pad_to)
        return out[:b] if b != pad_to else out

    @property
    def jitted(self):
        """The compiled (consts, rf_batch) -> images callable."""
        return self._fn

    @property
    def input_bytes_per_acq(self) -> int:
        """B_in of one acquisition (paper eq. 2 normalization)."""
        return self.cfg.input_bytes

    @property
    def name(self) -> str:
        return (f"{self.cfg.name}:{self.cfg.variant.value}"
                f":{self.cfg.exec_map}")


class ShardedExecutor:
    """Data-parallel `BatchedExecutor` over a 1-D mesh of local devices.

    The acquisition batch axis is split across the "data" mesh axis with
    `NamedSharding`; constants are replicated. One jitted SPMD program
    serves every call; a `shard_map` runs the stage graph on each
    device's batch shard with no cross-device communication (the
    pipeline is embarrassingly parallel over acquisitions).

    ``devices=None`` takes every local device. Uneven batches are
    zero-padded up to a device multiple and the pad rows sliced off the
    returned images, so any B >= 1 is accepted — at the cost of one
    wasted device-row of compute for remainders (callers streaming for
    throughput should keep B a multiple of ``n_devices``).
    """

    def __init__(self, cfg: UltrasoundConfig, *,
                 devices: Optional[Sequence] = None,
                 donate: Optional[bool] = None, plan=None,
                 policy: Optional[str] = None):
        devs = tuple(devices) if devices is not None \
            else tuple(jax.local_devices())
        if not devs:
            raise ValueError("ShardedExecutor needs at least one device")
        self.devices = devs
        self.n_devices = len(devs)
        base = _resolve_plan(cfg, plan, policy, donate=donate)
        self.plan = base.with_devices(self.n_devices,
                                      (("data", self.n_devices),))
        self.cfg = self.plan.concretize(cfg)

        self.mesh = Mesh(np.asarray(devs), ("data",))
        self._consts_sharding = NamedSharding(self.mesh, P())
        self._batch_sharding = NamedSharding(self.mesh, P("data"))
        self.consts = jax.device_put(
            jax.tree.map(jnp.asarray, init_pipeline(self.cfg)),
            self._consts_sharding)
        # Each device maps the graph over its own batch shard
        # (shard_map), for both exec_maps: the partitioner cannot split
        # a Pallas kernel (a custom call) or a sequential lax.map scan —
        # left to it, the batch would be gathered and run whole on every
        # device. No collectives: acquisitions are independent, so
        # there is no varying-axis bookkeeping to check (check_vma=False
        # also lets Pallas outputs, which carry none, through).
        mapped = jax.shard_map(_mapped_graph_fn(self.cfg), mesh=self.mesh,
                               in_specs=(P(), P("data")),
                               out_specs=P("data"), check_vma=False)

        def run(consts, rf_b):
            return mapped(consts, rf_b)

        self.donate = _resolve_donate(donate, self.plan)
        self._fn = jax.jit(
            run,
            in_shardings=(self._consts_sharding, self._batch_sharding),
            out_shardings=self._batch_sharding,
            donate_argnums=(1,) if self.donate else ())
        self._aot: dict = {}              # pad_to -> AOT-compiled executable

    def _pad(self, rf_batch: jnp.ndarray) -> tuple:
        b = rf_batch.shape[0]
        if b < 1:
            raise ValueError("empty RF batch")
        pad = -b % self.n_devices
        if pad:
            fill = jnp.zeros((pad,) + rf_batch.shape[1:], rf_batch.dtype)
            rf_batch = jnp.concatenate([rf_batch, fill])
        return rf_batch, b, pad

    def __call__(self, rf_batch: jnp.ndarray) -> jnp.ndarray:
        """(B, n_l, n_c, n_f) RF batch -> (B, *image_shape), any B >= 1."""
        rf_batch, b, pad = self._pad(rf_batch)
        out = self._fn(self.consts, rf_batch)
        return out[:b] if pad else out

    def dispatch(self, rf_batch: jnp.ndarray) -> jnp.ndarray:
        """Like ``__call__`` but keeps the (padded) batch-sharded result.

        The streaming loop uses this to track per-device shards of the
        in-flight output; B must already be a device multiple so no
        host-side slicing re-synchronizes the stream.
        """
        b = rf_batch.shape[0]
        if b < 1:
            raise ValueError("empty RF batch")
        if b % self.n_devices:
            raise ValueError(
                f"dispatch() needs batch % n_devices == 0 "
                f"(got B={b}, n_devices={self.n_devices}); use __call__ "
                "for remainder-padded one-shot execution")
        return self._fn(self.consts, rf_batch)

    def install_aot(self, pad_to: int, compiled) -> None:
        """Pin an AOT-compiled SPMD executable for the ``pad_to`` shape
        (built by `repro.core.aot.aot_warm`)."""
        self._aot[pad_to] = compiled

    def place(self, rf_batch) -> jnp.ndarray:
        """H2D: commit an already-padded host batch to the mesh.

        Sharded counterpart of `BatchedExecutor.place`: the batch is
        committed to the batch sharding explicitly so the AOT
        executable — which, unlike jit, does not re-resolve placements
        — always sees its compiled-for layout. Always a fresh device
        array, so the staging-ring slot stays the caller's and the
        device copy is safe to donate.
        """
        return jax.device_put(jnp.asarray(rf_batch),
                              self._batch_sharding)

    def dispatch_staged(self, dev_batch, pad_to: int) -> jnp.ndarray:
        """Async dispatch of an already-placed ``(pad_to, ...)`` batch
        (`place` committed it to the mesh; ``pad_to`` must be a device
        multiple — one SPMD shape per mesh)."""
        if pad_to % self.n_devices:
            raise ValueError(
                f"dispatch_staged needs pad_to % n_devices == 0 "
                f"(got pad_to={pad_to}, n_devices={self.n_devices})")
        fn = self._aot.get(pad_to, self._fn)
        return fn(self.consts, dev_batch)

    def dispatch_padded(self, rf_batch, pad_to: int) -> jnp.ndarray:
        """Async fixed-shape dispatch: the PADDED, UNSYNCED device array.

        Sharded counterpart of `BatchedExecutor.dispatch_padded`:
        ``pad_to`` must be a device multiple (one SPMD shape per mesh).
        """
        if pad_to % self.n_devices:
            raise ValueError(
                f"dispatch_padded needs pad_to % n_devices == 0 "
                f"(got pad_to={pad_to}, n_devices={self.n_devices})")
        rf_batch, _ = _pad_rows(rf_batch, pad_to)
        return self.dispatch_staged(self.place(rf_batch), pad_to)

    def call_padded(self, rf_batch: jnp.ndarray,
                    pad_to: int) -> jnp.ndarray:
        """Fixed-shape dispatch of a ragged batch (B <= pad_to rows).

        The sharded counterpart of `BatchedExecutor.call_padded`:
        ``pad_to`` must be a device multiple so the one compiled SPMD
        shape splits evenly across the mesh (the scheduler enforces
        ``max_batch % n_devices == 0`` at construction).
        """
        if pad_to % self.n_devices:
            raise ValueError(
                f"call_padded needs pad_to % n_devices == 0 "
                f"(got pad_to={pad_to}, n_devices={self.n_devices})")
        b = rf_batch.shape[0]
        out = self.dispatch_padded(rf_batch, pad_to)
        return out[:b] if b != pad_to else out

    @property
    def jitted(self):
        """The compiled SPMD (consts, rf_batch) -> images callable."""
        return self._fn

    @property
    def input_bytes_per_acq(self) -> int:
        """B_in of one acquisition (paper eq. 2 normalization)."""
        return self.cfg.input_bytes

    @property
    def name(self) -> str:
        return (f"{self.cfg.name}:{self.cfg.variant.value}"
                f":{self.cfg.exec_map}:d{self.n_devices}")
