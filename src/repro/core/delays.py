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


def interp_taps(tables: DelayTables) -> Tuple[np.ndarray, np.ndarray]:
    """The two nonzeros of each row (p, c) of the complex DAS operator.

        M[c, p, s] = apod * rot * ((1-frac) [s == idx] + frac [s == idx+1])

    Returns ``idx`` (n_pix, n_c) and ``w`` (2, 2, n_pix, n_c) f32, where
    ``w[k, r]`` is component r (0 re, 1 im) of the weight at sample
    ``idx + k``. Both operator forms (dense CNN, banded BSR) place these.
    """
    re = tables.rot[..., 0] * tables.apod
    im = tables.rot[..., 1] * tables.apod
    lo, hi = 1.0 - tables.frac, tables.frac
    w = np.stack([np.stack([re * lo, im * lo]), np.stack([re * hi, im * hi])])
    return tables.idx, w


def interp_operator(cfg: UltrasoundConfig,
                    tables: DelayTables) -> Tuple[np.ndarray, np.ndarray]:
    """Complex DAS operator as its real and imaginary halves, each a dense
    (n_c, n_s8, n_pix) f32 tensor; n_s8 is n_s rounded up to a multiple
    of 8 and the padded sample rows are zero.

    Applying it is a per-channel (n_pix x n_s) @ (n_s x n_f) complex matmul
    — i.e. a 1x1 convolution with n_s input channels and n_pix output
    channels, the canonical CNN re-expression of a gather (TINA-style).
    The halves are stored apart, pixels minor and samples second-minor,
    because that is the order the TPU's contractions read: an interleaved
    (..., 2) operator is re-written in every call to split it, and a
    second-minor axis that is not a multiple of 8 is laid out elsewhere.
    """
    n_pix, n_c = cfg.n_pix, cfg.n_c
    n_s8 = -(-cfg.n_s // 8) * 8
    halves = np.zeros((2, n_c, n_s8, n_pix), dtype=np.float32)
    idx, w = interp_taps(tables)
    chans, rows = np.arange(n_c)[None, :], np.arange(n_pix)[:, None]
    for k in (0, 1):
        for r in (0, 1):
            # each (c, s, p) is hit at most once; added into zeros, a -0.0
            # weight is stored as +0.0 (init-time numpy, untimed)
            np.add.at(halves[r], (chans, idx + k, rows), w[k, r])
    return halves[0], halves[1]


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

    dense_p = np.zeros((n_c, pad_p, pad_s, 2), dtype=np.float32)
    idx, w = interp_taps(tables)
    chans, rows = np.arange(n_c)[None, :], np.arange(n_pix)[:, None]
    for k in (0, 1):
        for r in (0, 1):
            np.add.at(dense_p, (chans, rows, idx + k, r), w[k, r])
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
