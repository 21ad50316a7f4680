"""Doppler heads: Color (lag-1 autocorrelation) and Power Doppler.

Color Doppler (Kasai autocorrelator):
  RF -> IQ -> beamformed IQ ensemble -> wall filter (FIR along frames) ->
  R1 = sum_f z[f+1] conj(z[f]) -> v = atan2(Im R1, Re R1) -> spatial smooth.

Power Doppler:
  same front end -> R0 = sum_f |z[f]|^2 -> 10 log10 -> dynamic range scale.

Every stage is pointwise arithmetic, a fixed FIR conv, or a reduction.
The atan2/log10 use the CNN-expressible approximations when
cfg.cnn_transcendentals is set (paper §II-C, §VII).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from repro.core import cnn_ops
from repro.core.config import UltrasoundConfig


def wall_filter_taps(cfg: UltrasoundConfig) -> np.ndarray:
    """Binomial high-pass FIR: (n-1)-fold convolution of [1, -1].

    A standard static clutter filter: removes the DC/slow (tissue) component
    of the slow-time signal before velocity estimation.
    """
    taps = np.array([1.0], dtype=np.float64)
    for _ in range(max(cfg.wall_filter_taps - 1, 1)):
        taps = np.convolve(taps, [1.0, -1.0])
    # Normalize to unit l2 gain at Nyquist.
    taps /= np.sqrt((taps ** 2).sum())
    return taps.astype(np.float32)


def smoothing_kernel(cfg: UltrasoundConfig) -> np.ndarray:
    k = cfg.smooth_kernel
    return np.full((k, k), 1.0 / (k * k), dtype=np.float32)


def apply_wall_filter(consts, bf: jnp.ndarray) -> jnp.ndarray:
    """(n_pix, n_f, 2) -> (n_pix, n_f', 2) FIR high-pass along frames.

    Explicitly ordered shift-and-add rather than lax.conv, for the same
    reason as demod.rf_to_iq: XLA:CPU conv codegen is context-dependent
    (1-ulp drift inside loop bodies / pallas grids), and the wall filter
    is the pipeline's most cancellation-amplified stage — the high-pass
    residual is orders of magnitude below the partial sums, so a 1-ulp
    upstream difference is visible in the final image. Pinning the tap
    order keeps it bit-identical in every execution context.
    """
    taps = consts["wall_taps"]                        # (k,)
    k = taps.shape[0]
    n_fp = bf.shape[1] - k + 1                        # VALID along frames
    acc = jnp.zeros(bf.shape[:1] + (n_fp, 2), jnp.float32)
    for t in range(k):  # static unroll; ascending tap order is the contract
        acc = acc + taps[t] * bf[:, t:t + n_fp, :]
    return acc


def _smooth(cfg: UltrasoundConfig, consts, img: jnp.ndarray) -> jnp.ndarray:
    """(nz, nx) -> (nz, nx) box smoothing, SAME padding (a real 2-D conv)."""
    k = consts["smooth"]                              # (k, k)
    x = img[None, None, :, :]
    out = lax.conv_general_dilated(
        x, k[None, None, :, :], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)   # f32 on the TPU too
    return out[0, 0]


def color_doppler_image(cfg: UltrasoundConfig, consts,
                        bf: jnp.ndarray) -> jnp.ndarray:
    """(n_pix, n_f, 2) -> (nz, nx) velocity map, normalized to [-1, 1]."""
    z = apply_wall_filter(consts, bf)                 # (n_pix, n_f', 2)
    z0, z1 = z[:, :-1], z[:, 1:]
    # R1 = sum_f z1 * conj(z0): pointwise products + frame reduction.
    re = (z1[..., 0] * z0[..., 0] + z1[..., 1] * z0[..., 1]).sum(axis=1)
    im = (z1[..., 1] * z0[..., 0] - z1[..., 0] * z0[..., 1]).sum(axis=1)
    if cfg.cnn_transcendentals:
        phase = cnn_ops.atan2_approx(im, re)
    else:
        phase = jnp.arctan2(im, re)
    v = phase / np.pi                                 # Nyquist-normalized
    return _smooth(cfg, consts, v.reshape(cfg.nz, cfg.nx))


def power_from_ensemble(consts, bf: jnp.ndarray) -> jnp.ndarray:
    """(n_pix, n_f, 2) -> (n_pix,) wall-filtered power R0.

    The tile-local half of the power-doppler head (per-pixel FIR along
    frames + per-pixel frame reduction) — the part the fused megakernel
    computes on tile-resident beamformed IQ.
    """
    z = apply_wall_filter(consts, bf)
    return cnn_ops.cabs2(z).sum(axis=1)               # (n_pix,)


def power_compress(cfg: UltrasoundConfig, consts,
                   r0: jnp.ndarray) -> jnp.ndarray:
    """(n_pix,) R0 -> (nz, nx) power map in [0, 1].

    The global half: normalize_by_max over all pixels plus the SAME-conv
    spatial smooth — the fused lowering's fusion boundary, shared
    verbatim with the monolithic reference (see bmode.compress_envelope
    for the contract rationale).
    """
    r0 = cnn_ops.normalize_by_max(r0)
    if cfg.cnn_transcendentals:
        db = 10.0 * cnn_ops.log10_approx(r0)
    else:
        db = 10.0 * jnp.log10(jnp.maximum(r0, 1e-30))
    dr = cfg.dynamic_range_db
    img = (cnn_ops.clip(db, -dr, 0.0) + dr) / dr
    return _smooth(cfg, consts, img.reshape(cfg.nz, cfg.nx))


def power_doppler_image(cfg: UltrasoundConfig, consts,
                        bf: jnp.ndarray) -> jnp.ndarray:
    """(n_pix, n_f, 2) -> (nz, nx) power map in [0, 1]."""
    return power_compress(cfg, consts, power_from_ensemble(consts, bf))
