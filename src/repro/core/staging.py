"""Preallocated host staging rings for the serving dispatch path.

Before this module, every coalesced dispatch paid TWO host copies on
the admit thread before the device ever saw a byte:
``np.stack([f.rf for f in batch])`` materialized a fresh batch array,
then a zero-padding concatenate copied it into a second fresh array of
the padded shape. Both allocations and both memcpys sat
on the serving critical path, every batch, forever.

`StagingRing` fuses them into zero extra copies: a ring of
preallocated ``(pad_to, *frame_shape)`` host buffers, pre-zeroed once
at construction. Coalescing writes each admitted frame's RF directly
into the next ring slot (one row-copy per frame — the minimum any
host->device path pays), and the pad region needs re-zeroing only when
a previous occupant left stale rows beyond the new occupancy. The slot
is handed to the executor's ``place``/``dispatch_staged`` pair as-is —
no stack, no concatenate, no allocation.

Ring sizing (the aliasing contract, tested in tests/test_staging.py):
a slot may be rewritten only after the dispatch that read it no longer
needs the host buffer. The scheduler launches a group's batch m+1 only
while strictly fewer than ``in_flight`` batches are pending globally,
and a group's batches retire FIFO — so when slot ``i`` comes around
again after ``slots`` stagings, the batch that last used it is at
least ``slots`` launches back and (with ``slots >= depth + 1``) is
provably no longer pending: its transfer and compute both finished.
An undersized ring (``slots < depth + 1``) could hand the device a
buffer the admit thread is concurrently overwriting, so construction
refuses it outright.

Timing: `stage` accumulates its own wall time (``stage_copy_s``) so
the scheduler can stamp the staging cost into the transfer telemetry
instead of losing it inside the dispatch latency.
"""

from __future__ import annotations

import time
from typing import Sequence, Tuple

import numpy as np

__all__ = ["StagingRing"]


class StagingRing:
    """Ring of preallocated padded host batch buffers for one group.

    ``depth`` is the scheduler's ``in_flight`` bound; ``slots`` defaults
    to ``depth + 1`` (the minimum safe size — see the module docstring)
    and may only be grown, never shrunk, past it.
    """

    def __init__(self, pad_to: int, frame_shape: Sequence[int], dtype, *,
                 depth: int, slots: int = None):
        if pad_to < 1:
            raise ValueError(f"pad_to must be >= 1 (got {pad_to})")
        if depth < 1:
            raise ValueError(f"depth must be >= 1 (got {depth})")
        if slots is None:
            slots = depth + 1
        if slots < depth + 1:
            raise ValueError(
                f"staging ring of {slots} slots cannot back in_flight="
                f"{depth} pending dispatches — a slot could be rewritten "
                f"while the device still reads it; need >= {depth + 1}")
        self.pad_to = pad_to
        self.depth = depth
        self.slots = slots
        self.frame_shape = tuple(frame_shape)
        self.dtype = np.dtype(dtype)
        # Pre-zeroed ONCE: a full batch never re-zeros, a partial batch
        # re-zeros only rows a previous occupant dirtied past its own
        # occupancy.
        self._bufs = [np.zeros((pad_to,) + self.frame_shape, self.dtype)
                      for _ in range(slots)]
        self._fill = [0] * slots       # dirtied rows per slot
        self._next = 0
        self.stage_copy_s = 0.0        # accumulated host-copy wall time
        self.batches_staged = 0

    def stage(self, frames_rf: Sequence[np.ndarray]
              ) -> Tuple[np.ndarray, int]:
        """Write a coalesced batch into the next slot; (buffer, b).

        Returns the full ``(pad_to, *frame_shape)`` padded buffer —
        rows past ``b`` are guaranteed zero — ready for
        ``executor.place`` / ``dispatch_staged``. The returned buffer is
        OWNED by the ring: it is valid until ``slots`` further `stage`
        calls, which is exactly what the scheduler's in-flight bound
        guarantees (see class docstring).
        """
        b = len(frames_rf)
        if b < 1:
            raise ValueError("empty RF batch")
        if b > self.pad_to:
            raise ValueError(
                f"batch of {b} exceeds pad_to={self.pad_to} — the "
                "scheduler must never coalesce past its policy's "
                "max_batch")
        t0 = time.perf_counter()
        i = self._next
        self._next = (i + 1) % self.slots
        buf = self._bufs[i]
        for r, rf in enumerate(frames_rf):
            buf[r] = rf
        if self._fill[i] > b:          # stale rows from a fuller occupant
            buf[b:self._fill[i]] = 0
        self._fill[i] = b
        self.stage_copy_s += time.perf_counter() - t0
        self.batches_staged += 1
        return buf, b
