"""Delay-and-sum geometry tables (precomputed at init, excluded from timing).

Plane-wave (0 deg) transmit, dynamic-aperture receive:

  tau(p, c) = ( z_p + sqrt(z_p^2 + (x_p - x_c)^2) ) / c_sound

The IQ-domain DAS interpolates the decimated IQ signal at s = tau * fs_iq and
applies the phase rotation exp(+j 2 pi f0 tau) to compensate demodulation.

All tables are numpy float32/int32; they are constants of the pipeline.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from repro.core.config import UltrasoundConfig
from repro.core import geometry


@dataclasses.dataclass(frozen=True)
class DelayTables:
    """Per (pixel, channel) gather/interp/apodization/rotation constants.

    idx   : (n_pix, n_c) int32 — floor sample index into IQ axis (clamped)
    frac  : (n_pix, n_c) f32   — linear interpolation fraction in [0, 1)
    valid : (n_pix, n_c) f32   — 1.0 where the delay lands inside the trace
    apod  : (n_pix, n_c) f32   — dynamic-aperture Hann apodization (masked)
    rot   : (n_pix, n_c, 2) f32 — unit phasor exp(+j 2 pi f0 tau) as (re, im)
    """

    idx: np.ndarray
    frac: np.ndarray
    valid: np.ndarray
    apod: np.ndarray
    rot: np.ndarray


def compute_delay_tables(cfg: UltrasoundConfig) -> DelayTables:
    zp, xp = geometry.flat_grid(cfg)                       # (n_pix,)
    xc = geometry.element_positions(cfg)                   # (n_c,)

    # Two-way time of flight [s]: plane-wave transmit + receive path.
    dz = zp[:, None]                                       # (n_pix, 1)
    dx = xp[:, None] - xc[None, :]                         # (n_pix, n_c)
    tau = (dz + np.sqrt(dz * dz + dx * dx)) / cfg.c_sound  # (n_pix, n_c)

    # Fractional sample position in the decimated IQ trace.
    s = tau * cfg.fs_iq
    idx = np.floor(s).astype(np.int64)
    frac = (s - idx).astype(np.float32)
    valid = ((idx >= 0) & (idx < cfg.n_s - 1)).astype(np.float32)
    idx = np.clip(idx, 0, cfg.n_s - 2).astype(np.int32)

    # Dynamic receive aperture: accept elements with |dx| <= z / (2 F#),
    # tapered with a Hann window across the active aperture.
    half_aperture = dz / (2.0 * cfg.f_number)              # (n_pix, 1)
    rel = np.clip(np.abs(dx) / np.maximum(half_aperture, 1e-9), 0.0, 1.0)
    apod = (0.5 + 0.5 * np.cos(np.pi * rel)).astype(np.float32)
    apod *= (np.abs(dx) <= half_aperture).astype(np.float32)
    apod *= valid
    # Normalize so each pixel's weights sum to ~1 (keeps dynamic range flat).
    norm = apod.sum(axis=1, keepdims=True)
    apod = (apod / np.maximum(norm, 1e-9)).astype(np.float32)

    phase = 2.0 * np.pi * cfg.f0 * tau
    rot = np.stack([np.cos(phase), np.sin(phase)], axis=-1).astype(np.float32)

    return DelayTables(
        idx=idx,
        frac=frac,
        valid=valid,
        apod=apod,
        rot=rot,
    )


# ---------------------------------------------------------------------------
# Dense one-hot interpolation operator (V2 — Full CNN)
# ---------------------------------------------------------------------------


def interp_matrix(cfg: UltrasoundConfig, tables: DelayTables) -> np.ndarray:
    """Complex DAS operator as a dense (n_c, n_pix, n_s, 2) tensor.

    Row (c, p) has two nonzeros (linear interpolation) scaled by apodization
    and rotated by the steering phasor:

        M[c, p, s] = apod * rot * ((1-frac) [s == idx] + frac [s == idx+1])

    Applying it is a per-channel (n_pix x n_s) @ (n_s x n_f) complex matmul
    — i.e. a 1x1 convolution with n_s input channels and n_pix output
    channels, the canonical CNN re-expression of a gather (TINA-style).
    """
    n_pix, n_c, n_s = cfg.n_pix, cfg.n_c, cfg.n_s
    M = np.zeros((n_c, n_pix, n_s, 2), dtype=np.float32)
    rows = np.arange(n_pix)
    for c in range(n_c):
        w = tables.apod[:, c]
        re = tables.rot[:, c, 0] * w
        im = tables.rot[:, c, 1] * w
        i0 = tables.idx[:, c]
        f = tables.frac[:, c]
        # scatter-add the two interpolation taps (init-time numpy, untimed)
        np.add.at(M[c, :, :, 0], (rows, i0), re * (1.0 - f))
        np.add.at(M[c, :, :, 1], (rows, i0), im * (1.0 - f))
        np.add.at(M[c, :, :, 0], (rows, i0 + 1), re * f)
        np.add.at(M[c, :, :, 1], (rows, i0 + 1), im * f)
    return M


# ---------------------------------------------------------------------------
# Banded block-sparse operator (V3 — structured sparse, TPU-adapted)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BsrOperator:
    """Block-sparse row (BSR) form of the DAS operator, per channel.

    The delay profile s(p) is piecewise-smooth in the pixel index, so the
    (n_pix x n_s) operator is *banded*: each pixel-block of bp rows touches a
    bounded window of sample columns. We store, for every (channel,
    pixel-block), K sample-block indices plus the dense (bp x bs) blocks —
    a static-shape structure whose only irregularity is a *block-level*
    gather (the TPU adaptation of the paper's V3: gather granularity is
    raised to MXU-aligned tiles, matmuls stay dense).

    blocks  : (n_c, n_pb, K, bp, 2 * bs) f32 — [re | im] side by side on
              the minor axis (a 128-lane row at bs=64, so the TPU layout
              stores the operator unpadded)
    col_idx : (n_c, n_pb, K) int32 — sample-block column for each stored block
    """

    blocks: np.ndarray
    col_idx: np.ndarray
    bp: int
    bs: int
    nnz_ratio: float  # stored / dense block count (reported in benchmarks)


def bsr_operator(cfg: UltrasoundConfig, tables: DelayTables) -> BsrOperator:
    bp, bs = cfg.sparse_block_p, cfg.sparse_block_s
    n_pix, n_c, n_s = cfg.n_pix, cfg.n_c, cfg.n_s
    n_pb = (n_pix + bp - 1) // bp
    n_sb = (n_s + bs - 1) // bs
    pad_p, pad_s = n_pb * bp, n_sb * bs

    dense = interp_matrix(cfg, tables)  # (n_c, n_pix, n_s, 2)
    dense_p = np.zeros((n_c, pad_p, pad_s, 2), dtype=np.float32)
    dense_p[:, :n_pix, :n_s] = dense
    # (n_c, n_pb, bp, n_sb, bs, 2) block view
    blk = dense_p.reshape(n_c, n_pb, bp, n_sb, bs, 2)
    occupied = np.abs(blk).sum(axis=(2, 4, 5)) > 0  # (n_c, n_pb, n_sb)

    K = max(int(occupied.sum(axis=2).max()), 1)
    blocks = np.zeros((n_c, n_pb, K, bp, 2 * bs), dtype=np.float32)
    col_idx = np.zeros((n_c, n_pb, K), dtype=np.int32)
    for c in range(n_c):
        for i in range(n_pb):
            cols = np.nonzero(occupied[c, i])[0]
            for k, sb in enumerate(cols):
                b = blk[c, i, :, sb]                      # (bp, bs, 2)
                blocks[c, i, k] = np.concatenate([b[..., 0], b[..., 1]], 1)
                col_idx[c, i, k] = sb
            # unused K-slots keep col 0 with all-zero data (contribute 0)

    nnz_ratio = float(occupied.sum()) / float(n_c * n_pb * n_sb)
    return BsrOperator(blocks=blocks, col_idx=col_idx, bp=bp, bs=bs,
                       nnz_ratio=nnz_ratio)
