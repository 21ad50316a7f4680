"""Public wrapper for the BSR beamform kernel (complex, multi-channel DAS V3).

`bsr_beamform` is the complex multi-channel beamform used by repro.core's
sparse variant: channels, stored blocks and the complex product all run
inside one pallas_call.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.bsr_spmm import kernel as _k
from repro.kernels.pallas_compat import auto_interpret


def bsr_beamform(cols, blocks, iq_b, *, precision="f32", interpret=None):
    """Complex multi-channel beamform via block-sparse matmuls.

    Args:
      cols:   (n_c, n_pb, K) int32.
      blocks: (n_c, n_pb, K, bp, 2 * bs) f32, [re | im] on the minor axis.
      iq_b:   (n_sb, bs, n_c, n_f, 2) f32 blocked IQ.
      precision: SpMM-operand dtype, "f32" | "bf16" | "f16"
        (accumulation is always f32).
    Returns:
      (n_pb * bp, n_f, 2) f32 beamformed output, summed over channels.
    """
    iq = jnp.moveaxis(iq_b.astype(jnp.float32), 2, 0)    # (c,sb,bs,f,2)
    re, im = iq[..., 0], iq[..., 1]
    x = jnp.concatenate([jnp.concatenate([re, im], -1),
                         jnp.concatenate([-im, re], -1)], 2)  # (c,sb,2bs,2f)
    y = _k.bsr_beamform_pallas(
        cols, blocks.astype(jnp.float32), x, precision=precision,
        interpret=auto_interpret(interpret))             # (pb, bp, 2f)
    n_pb, bp, n_f2 = y.shape
    n_f = n_f2 // 2
    return jnp.stack([y[..., :n_f], y[..., n_f:]], -1).reshape(
        n_pb * bp, n_f, 2)
