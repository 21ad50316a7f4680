"""Batched + multi-device sharded stage-graph executors.

The paper (and the legacy `UltrasoundPipeline`) times one acquisition per
call. Production traffic wants N acquisitions per dispatch so the fixed
dispatch/launch overhead amortizes and the compiler sees the whole batch
— and past one device, wants that batch *split across every local
device* so throughput scales with hardware instead of clock speed.

Public API
----------
`make_executor`  — the serving tier's one executor choice: two or more
    ``devices`` give a `ShardedExecutor` over them (``max_batch`` must
    divide by their count, `mesh_size`), anything else a
    `BatchedExecutor` on the default device. The scheduler and the warm
    pool both build through it, so their pool keys cannot disagree.
`BatchedExecutor`  — init once, jit once, run (B, n_l, n_c, n_f)
    batches many times on the default device. The batch axis carries the
    logical "batch" sharding name, so under an active mesh binding
    (runtime/sharding.py) it composes with the LM half's meshes.

A batch reaches the device one way: ``place`` + ``dispatch_staged``.
The batch was already padded to ``pad_to`` rows in a
`repro.core.staging.StagingRing` slot; `place` is the timed H2D commit
and `dispatch_staged` is launch-only, returning the padded, unsynced
device array (the caller slices valid rows after it drains).
Donation stays safe: every dispatch consumes a freshly-placed device
batch, never a caller-retained device array (host ring slots are
reused, their device copies are not). `install_aot` (fed by
repro.core.aot) pins an ahead-of-time-compiled executable for one
padded shape; `dispatch_staged` prefers it over re-entering jit.
``__call__`` is the one-shot oracle the tests compare against: any
B >= 1, valid rows only.
`ShardedExecutor`  — the same contract, data-parallel over an explicit
    1-D ``jax.sharding.Mesh`` of local devices ("data" axis): consts are
    replicated, the acquisition batch axis is split via `NamedSharding`,
    and outputs come back batch-sharded. ``__call__`` zero-pads uneven
    batches (B % devices != 0) to the next multiple and slices the
    padding off the result. On hosts with one physical device, force a
    multi-device CPU mesh anywhere with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    JAX initializes; see tests/test_sharded_executor.py).

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

    n_devices = 1

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

    ``devices=None`` takes every local device. ``__call__`` zero-pads
    uneven batches up to a device multiple and slices the pad rows off
    the returned images, so any B >= 1 is accepted; `dispatch_staged`
    takes only a ``pad_to`` that divides by ``n_devices``.
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
        """Zero rows up to a device multiple, for `__call__`."""
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


def mesh_size(devices: Optional[Sequence], max_batch: int) -> int:
    """Devices a serving batch of ``max_batch`` rows is split across.

    Two or more ``devices``: all of them, and ``max_batch`` must divide
    by their count (one SPMD shape per mesh). Anything else: 1, the
    default device. Part of every warm-pool key.
    """
    n = len(devices) if devices is not None and len(devices) > 1 else 1
    if max_batch % n:
        raise ValueError(
            f"max_batch={max_batch} must be a multiple of "
            f"n_devices={n} for sharded dispatch")
    return n


def make_executor(cfg: UltrasoundConfig, *, devices: Optional[Sequence],
                  max_batch: int, plan=None,
                  donate: Optional[bool] = None):
    """The serving executor of ``cfg`` for batches of ``max_batch`` rows:
    a `ShardedExecutor` over ``devices`` when `mesh_size` is above 1,
    else a `BatchedExecutor` on the default device."""
    if mesh_size(devices, max_batch) > 1:
        return ShardedExecutor(cfg, devices=devices, plan=plan,
                               donate=donate)
    return BatchedExecutor(cfg, plan=plan, donate=donate)
