"""Delay-and-sum beamforming — the paper's three implementation variants.

All variants compute the same math (validated to allclose in
tests/test_beamform_variants.py):

    y[p, f] = sum_c apod[p,c] * rot[p,c] * lerp(IQ[:, c, f], s[p,c])

V1 DYNAMIC — per-channel gather (take) + pointwise lerp. The irregular
    memory access pattern the paper shows is fast on GPU, slow on TPU.
V2 CNN     — the gather folded into a precomputed one-hot interpolation
    operator; the whole beamform is a per-channel dense complex matmul
    (a 1x1 conv), which maps onto the MXU.
V3 SPARSE  — the same operator in banded block-sparse (BSR) form; dense
    MXU tiles over the nonzero band, irregularity confined to a
    *block-level* gather (TPU adaptation of the paper's sparse variant;
    the paper could not run V3 on TPU at all).

Input : IQ (n_s, n_c, n_f, 2)
Output: beamformed (n_pix, n_f, 2)
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cnn_ops
from repro.core.config import UltrasoundConfig, Variant

# The XLA formulations are the f32 tier. The TPU's default f32 matmul
# rounds its operands to bf16, so the contractions ask for full f32
# precision explicitly (a no-op on the CPU).
F32 = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# V1 — dynamic indexing
# ---------------------------------------------------------------------------


def beamform_dynamic(cfg: UltrasoundConfig, consts: Dict[str, jnp.ndarray],
                     iq: jnp.ndarray) -> jnp.ndarray:
    idx, frac = consts["idx"], consts["frac"]            # (n_pix, n_c)
    apod, rot = consts["apod"], consts["rot"]            # (..., 2)

    iq_c = iq.transpose(1, 0, 2, 3)                      # (n_c, n_s, n_f, 2)

    def one_channel(iq_1, idx_1, frac_1, apod_1, rot_1):
        s0 = jnp.take(iq_1, idx_1, axis=0)               # (n_pix, n_f, 2)
        s1 = jnp.take(iq_1, idx_1 + 1, axis=0)
        f = frac_1[:, None, None]
        v = s0 * (1.0 - f) + s1 * f
        v = cnn_ops.cmul(v, rot_1[:, None, :])
        return v * apod_1[:, None, None]

    per_c = jax.vmap(one_channel, in_axes=(0, 1, 1, 1, 1))(
        iq_c, idx, frac, apod, rot)                      # (n_c, n_pix, n_f, 2)
    return per_c.sum(axis=0)


# ---------------------------------------------------------------------------
# V2 — full CNN (one-hot interpolation matmul)
# ---------------------------------------------------------------------------


def beamform_cnn(cfg: UltrasoundConfig, consts: Dict[str, jnp.ndarray],
                 iq: jnp.ndarray) -> jnp.ndarray:
    M = consts["interp_matrix"]                          # (n_c, n_pix, n_s, 2)
    # Two real einsums realize the complex matmul; each is a stack of
    # per-channel (n_pix x n_s) @ (n_s x n_f) matmuls == 1x1 convolutions.
    a = jnp.einsum("cps,scfr->pfr", M[..., 0], iq,
                   precision=F32)                        # M_re * (IQre, IQim)
    b = jnp.einsum("cps,scfr->pfr", M[..., 1], iq,
                   precision=F32)                        # M_im * (IQre, IQim)
    return cnn_ops.cpack(a[..., 0] - b[..., 1], a[..., 1] + b[..., 0])


# ---------------------------------------------------------------------------
# V3 — structured block-sparse
# ---------------------------------------------------------------------------


def beamform_sparse(cfg: UltrasoundConfig, consts: Dict[str, jnp.ndarray],
                    iq: jnp.ndarray) -> jnp.ndarray:
    blocks = consts["bsr_blocks"]                        # (n_c,n_pb,K,bp,2bs)
    col_idx = consts["bsr_col_idx"]                      # (n_c, n_pb, K)
    n_c, n_pb, K, bp, bs2 = blocks.shape
    bs = bs2 // 2
    n_s, _, n_f, _ = iq.shape
    n_sb = -(-n_s // bs)

    pad = n_sb * bs - n_s
    iq_p = jnp.pad(iq, ((0, pad), (0, 0), (0, 0), (0, 0)))
    iq_b = iq_p.reshape(n_sb, bs, n_c, n_f, 2)           # blocked IQ

    def one_channel(blocks_1, cols_1, iq_1):
        # iq_1: (n_sb, bs, n_f, 2); cols_1: (n_pb, K)
        g = jnp.take(iq_1, cols_1, axis=0)               # (n_pb, K, bs, n_f, 2)
        a = jnp.einsum("ikps,iksfr->ipfr", blocks_1[..., :bs], g,
                       precision=F32)
        b = jnp.einsum("ikps,iksfr->ipfr", blocks_1[..., bs:], g,
                       precision=F32)
        return cnn_ops.cpack(a[..., 0] - b[..., 1], a[..., 1] + b[..., 0])

    per_c = jax.vmap(one_channel, in_axes=(0, 0, 2))(
        blocks, col_idx, iq_b)                           # (n_c, n_pb, bp, n_f, 2)
    y = per_c.sum(axis=0).reshape(n_pb * bp, n_f, 2)
    return y[: cfg.n_pix]


# ---------------------------------------------------------------------------


# The XLA formulations per variant — each is also registered as the
# "xla" lowering of the beamform stage op (repro.core.lowering); the
# Pallas lowerings of DYNAMIC (kernels/das_beamform) and SPARSE
# (kernels/bsr_spmm) live in the registry, selected per plan.
BEAMFORMERS = {
    Variant.DYNAMIC: beamform_dynamic,
    Variant.CNN: beamform_cnn,
    Variant.SPARSE: beamform_sparse,
}


def beamform(cfg: UltrasoundConfig, consts: Dict[str, jnp.ndarray],
             iq: jnp.ndarray) -> jnp.ndarray:
    """Pure-XLA beamform dispatch (the monolithic oracle's reference
    path — lowering-aware execution goes through the stage graph)."""
    return BEAMFORMERS[cfg.variant](cfg, consts, iq)
