"""Public wrapper for the fused DAS beamform kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.das_beamform import kernel as _k
from repro.kernels.pallas_compat import auto_interpret, next_multiple


def planar_tables(idx, frac, apod, rot, *, bp: int, cc: int):
    """(n_pix, n_c) tables -> the kernel's planar, padded layout.

    Returns ``(idx (n_c', 1, n_pix'), tab (n_c', 4, n_pix'), bp, cc)``
    with tab rows frac, apod, rot_re, rot_im. The pixel axis is padded
    to a ``bp`` multiple and the channel axis to a ``cc`` multiple, both
    with zero apodization, so padding contributes exact zeros. ``bp`` is
    clamped to the (128-rounded) pixel count and ``cc`` to the channel
    count. Shared with the fused megakernel's wrapper.
    """
    n_pix, n_c = idx.shape
    bp = min(bp, next_multiple(n_pix, 128))
    cc = min(cc, n_c)
    pad_p = next_multiple(n_pix, bp) - n_pix
    pad_c = next_multiple(n_c, cc) - n_c
    tab = jnp.stack([frac, apod, rot[..., 0], rot[..., 1]])   # (4, P, C)
    tab = jnp.pad(tab, ((0, 0), (0, pad_p), (0, pad_c)))      # apod 0 pads
    idx = jnp.pad(idx, ((0, pad_p), (0, pad_c)))
    return idx.T[:, None, :], tab.transpose(2, 0, 1), bp, cc


def planar_iq(iq, n_c: int):
    """(n_s, n_c, n_f, 2) IQ -> (2, n_c', n_f, n_s') f32 planar IQ, with
    the sample axis zero-padded to a lane multiple (128) and the channel
    axis to ``n_c`` rows (zero rows meet zero apodization)."""
    n_s, c = iq.shape[0], iq.shape[1]
    iq = jnp.pad(iq.astype(jnp.float32),
                 ((0, next_multiple(n_s, 128) - n_s), (0, n_c - c),
                  (0, 0), (0, 0)))
    return iq.transpose(3, 1, 2, 0)


def das_beamform(idx, frac, apod, rot, iq, *, bp: int = _k.DEFAULT_BP,
                 precision: str = "f32", interpret=None):
    """Fused delay-and-sum beamform.

    Args:
      idx:  (n_pix, n_c) int32 floor sample indices (clamped to n_s - 2).
      frac: (n_pix, n_c) f32 interpolation fractions.
      apod: (n_pix, n_c) f32 apodization (0 disables a (pixel, channel)).
      rot:  (n_pix, n_c, 2) f32 unit phasors.
      iq:   (n_s, n_c, n_f, 2) f32.
      bp:   pixel-tile lanes.
      precision: matmul-operand dtype, "f32" | "bf16" | "f16"
        (accumulation is always f32).
    Returns:
      (n_pix, n_f, 2) f32 beamformed IQ.
    """
    n_pix = idx.shape[0]
    idx_t, tab, bp, _ = planar_tables(idx, frac, apod, rot, bp=bp,
                                      cc=_k.CHANNEL_CHUNK)
    out = _k.das_beamform_pallas(
        idx_t, tab, planar_iq(iq, idx_t.shape[0]), bp=bp,
        precision=precision, interpret=auto_interpret(interpret))
    return out[:, :, :n_pix].transpose(2, 1, 0)
