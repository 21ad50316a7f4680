"""Convenience shims shared by every Pallas kernel wrapper.

`auto_interpret` is the shared interpret-mode policy (compiled where
Mosaic runs, interpret mode for the CPU tests), `next_multiple` the
shared tile-padding helper and `VMEM_LIMIT_BYTES` the scoped-VMEM
budget every kernel declares — one definition each, so the kernels'
contract cannot fork per package.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas.tpu as pltpu


CompilerParams = pltpu.CompilerParams

# Scoped VMEM each compiled kernel may use. A TPU v5e core has 128 MiB of
# VMEM and XLA's default scoped limit is 16 MiB; kernels declare this
# budget explicitly, and the fused megakernel's capability predicate
# refuses geometries whose resident blocks exceed it.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def auto_interpret(interpret: Optional[bool]) -> bool:
    """Resolve the ``interpret=None`` default of every kernel wrapper.

    None means "compiled where the Mosaic TPU backend exists, interpret
    mode everywhere else". Interpret mode exists for the CPU tests; it
    is never a measurement path.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def next_multiple(x: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``x`` (tile-padding contract)."""
    return ((x + m - 1) // m) * m


def block_sample_axis(iq: jnp.ndarray, bs: int) -> jnp.ndarray:
    """(n_s, n_c, n_f, 2) -> (n_sb, bs, n_c, n_f, 2) sample-axis blocking.

    Zero-pads the sample axis to a multiple of ``bs`` and reshapes it into
    blocks — the shared contract between the BSR delay-table builder (which
    indexes sample *blocks*) and the kernel wrappers that consume blocked
    IQ. Zero padding is exact: padded samples are only ever multiplied by
    structurally-zero BSR blocks.
    """
    n_s = iq.shape[0]
    pad = next_multiple(n_s, bs) - n_s
    if pad:
        iq = jnp.pad(iq, ((0, pad),) + ((0, 0),) * (iq.ndim - 1))
    return iq.reshape((-1, bs) + iq.shape[1:])
