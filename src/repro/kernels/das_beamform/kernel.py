"""Fused DAS beamform Pallas kernel (TPU target).

The paper's V2 "full CNN" variant materializes the one-hot interpolation
operator in HBM — (n_c, n_pix, n_s) floats, 2.7 GB at the paper's geometry
(their Table I peak-memory column). This kernel is the TPU-native fusion of
V1 and V2: the one-hot interpolation weights are *built on the fly in VMEM*
from the compact delay tables and immediately consumed by an MXU matmul,
so the gather becomes matrix work without the O(n_pix * n_s) HBM
footprint.

Layout (planar re/im, pixels on the lane axis — no size-2 minor axis):
  idx   (n_c, 1, n_pix)        int32 floor sample index
  tab   (n_c, 4, n_pix)        f32 rows: frac, apod, rot_re, rot_im
  iq    (2, n_c, n_f, n_s)     f32 planar IQ (n_s zero-padded to 128)
  out   (2, n_f, n_pix)        f32 planar beamformed IQ

Tiling:
  grid = (n_pix // bp, n_c // cc)   pixel tiles "parallel", channel
                                    chunks "arbitrary" (sequential);
                                    cc = min(CHANNEL_CHUNK, n_c)
  VMEM per step: one (cc, ., bp) slab of each table, one (2, cc, n_f, n_s)
  slab of IQ, the resident (2, n_f, bp) output tile, and one (n_s, bp)
  weight tile built per channel.

Per channel the body computes ``iq_c (n_f, n_s) @ w (n_s, bp)``; the
output tile accumulates channels in ascending order across the
sequential channel axis — the same order as the reference's channel sum.
All accumulation is f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
import jax.experimental.pallas as pl

from repro.kernels.pallas_compat import CompilerParams, VMEM_LIMIT_BYTES

DEFAULT_BP = 128     # pixel-tile lanes (multiple of 128 on the TPU)
CHANNEL_CHUNK = 8    # channels per grid step

# Matmul-operand compute dtypes for the mixed-precision contract: only the
# one-hot weight tile and the IQ operand are cast; accumulation stays f32
# (preferred_element_type) and everything pointwise stays f32. "f32" is the
# identity cast and runs the dot at full f32 precision.
COMPUTE_DTYPES = {
    "f32": jnp.float32,
    "bf16": jnp.bfloat16,
    "f16": jnp.float16,
}


def dot_precision(precision: str):
    """The dot precision that keeps the f32 tier an f32 contraction (the
    TPU's default f32 matmul rounds its operands to bf16)."""
    return lax.Precision.HIGHEST if precision == "f32" else None


def beamform_channel(idx, tab, iq_re, iq_im, *, precision: str):
    """One channel's beamformed contribution to a pixel tile.

    Args:
      idx: (1, bp) int32 floor sample index per pixel.
      tab: (4, bp) f32 rows frac, apod, rot_re, rot_im.
      iq_re, iq_im: (n_f, n_s) f32 planar IQ of the channel.
    Returns:
      (re, im), each (n_f, bp) f32.

    The one-hot weights are built in VMEM and consumed by the MXU.
    Apodization and rotation are applied AFTER the dot, in the same f32
    expression order as the XLA dynamic beamformer (lerp -> cmul(rot) ->
    *apod); the one-hot contraction's zero terms add exactly. Shared by
    the fused megakernel (repro.kernels.fused_pipeline).
    """
    cdt = COMPUTE_DTYPES[precision]
    n_s, bp = iq_re.shape[1], idx.shape[1]
    frac, apod = tab[0:1], tab[1:2]
    rot_re, rot_im = tab[2:3], tab[3:4]
    iota = lax.broadcasted_iota(jnp.int32, (n_s, bp), 0)
    w = (jnp.where(iota == idx, 1.0 - frac, 0.0) +
         jnp.where(iota == idx + 1, frac, 0.0)).astype(cdt)   # (n_s, bp)
    prec = dot_precision(precision)
    v_re = jnp.dot(iq_re.astype(cdt), w, precision=prec,
                   preferred_element_type=jnp.float32)       # (n_f, bp)
    v_im = jnp.dot(iq_im.astype(cdt), w, precision=prec,
                   preferred_element_type=jnp.float32)
    return ((v_re * rot_re - v_im * rot_im) * apod,
            (v_re * rot_im + v_im * rot_re) * apod)


def _kernel(idx_ref, tab_ref, iq_ref, out_ref, *, precision):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc_re, acc_im = out_ref[0], out_ref[1]
    for c in range(idx_ref.shape[0]):     # static unroll over the chunk
        re, im = beamform_channel(idx_ref[c], tab_ref[c], iq_ref[0, c],
                                  iq_ref[1, c], precision=precision)
        acc_re = acc_re + re
        acc_im = acc_im + im
    out_ref[0] = acc_re
    out_ref[1] = acc_im


@functools.partial(jax.jit,
                   static_argnames=("bp", "precision", "interpret"))
def das_beamform_pallas(idx, tab, iq, *, bp: int = DEFAULT_BP,
                        precision: str = "f32", interpret: bool = True):
    """Planar tables + IQ -> (2, n_f, n_pix) planar beamformed IQ.

    idx (n_c, 1, n_pix), tab (n_c, 4, n_pix), iq (2, n_c, n_f, n_s); n_pix
    must be a multiple of bp and n_c of ``min(CHANNEL_CHUNK, n_c)`` (ops.py
    pads both). `precision`
    selects the matmul-operand dtype (f32 | bf16 | f16); accumulation is
    always f32.
    """
    n_c, _, n_pix = idx.shape
    _, _, n_f, n_s = iq.shape
    cc = min(CHANNEL_CHUNK, n_c)
    assert n_pix % bp == 0 and n_c % cc == 0, (n_pix, bp, n_c, cc)
    return pl.pallas_call(
        functools.partial(_kernel, precision=precision),
        grid=(n_pix // bp, n_c // cc),
        in_specs=[
            pl.BlockSpec((cc, 1, bp), lambda i, j: (j, 0, i)),
            pl.BlockSpec((cc, 4, bp), lambda i, j: (j, 0, i)),
            pl.BlockSpec((2, cc, n_f, n_s), lambda i, j: (0, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, n_f, bp), lambda i, j: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((2, n_f, n_pix), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(idx, tab, iq)
