"""Public wrappers for the fused demod→beamform→head megakernel.

`fused_rf_to_envelope` / `fused_rf_to_power` own the layout, padding,
dtype, and interpret policy; the head's global epilogue (normalize +
compress + smooth) stays OUTSIDE — the fused lowering in
repro.core.lowering runs it via the reference head's own compress
functions on the sliced (pad-free) kernel output, so the global max
never sees pad rows.

Padding contract (same as das_beamform, whose `planar_tables` builds the
tables): the pixel axis is padded to a ``bp`` multiple with zero
apodization, so pad pixels beamform to exactly zero (envelope 0 / power
0) and are sliced off before returning.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.das_beamform.ops import planar_tables
from repro.kernels.fused_pipeline import kernel as _k
from repro.kernels.pallas_compat import auto_interpret

DEFAULT_BP = _k.DEFAULT_BP


def _fused(carrier, lpf, idx, frac, apod, rot, rf, wall, *, head, decim,
           bp, precision, interpret):
    n_pix, n_c = idx.shape
    n_l, _, n_f = rf.shape
    idx_t, tab, bp, _ = planar_tables(idx, frac, apod, rot,
                                      bp=bp or DEFAULT_BP, cc=n_c)
    rf_t = rf.astype(jnp.float32).transpose(1, 2, 0).reshape(-1, n_l)
    out = _k.fused_pipeline_pallas(
        carrier.T, jnp.reshape(lpf, (1, -1)), idx_t, tab, rf_t,
        None if wall is None else jnp.reshape(wall, (1, -1)),
        head=head, n_f=n_f, decim=decim, bp=bp, precision=precision,
        interpret=auto_interpret(interpret))
    return out[:, :n_pix]


def fused_rf_to_envelope(carrier, lpf, idx, frac, apod, rot, rf, *,
                         decim: int, bp=None, precision: str = "f32",
                         interpret=None):
    """Fused RF -> B-mode envelope (demod + DAS beamform + |z|).

    Args:
      carrier: (n_l, 2) f32 demod carrier (cos / -sin).
      lpf:  (taps,) f32 decimating FIR.
      idx:  (n_pix, n_c) int32 floor sample indices.
      frac / apod: (n_pix, n_c) f32.
      rot:  (n_pix, n_c, 2) f32 unit phasors.
      rf:   (n_l, n_c, n_f) RF (any real dtype; cast to f32).
      bp:   pixel-tile lanes (None -> DEFAULT_BP), clamped + padded.
      precision: "f32" | "bf16" | "f16" matmul-operand precision
        (f32 accumulate); see repro.core.config.PRECISION_TOLERANCES.
    Returns:
      (n_pix, n_f) f32 envelope — feed repro.core.bmode.compress_envelope.
    """
    return _fused(carrier, lpf, idx, frac, apod, rot, rf, None,
                  head="bmode", decim=decim, bp=bp, precision=precision,
                  interpret=interpret).T


def fused_rf_to_power(carrier, lpf, idx, frac, apod, rot, wall, rf, *,
                      decim: int, bp=None, precision: str = "f32",
                      interpret=None):
    """Fused RF -> power-doppler R0 (demod + DAS + wall filter + power).

    Same table arguments as `fused_rf_to_envelope`, plus ``wall``: the
    (kw,) f32 wall-filter taps. Returns (n_pix,) f32 R0 — feed
    repro.core.doppler.power_compress.
    """
    return _fused(carrier, lpf, idx, frac, apod, rot, rf, wall,
                  head="power_doppler", decim=decim, bp=bp,
                  precision=precision, interpret=interpret)[0]
