"""Fused demod→beamform→head Pallas megakernel (TPU target).

The per-stage lowering registry (repro.core.lowering) still pays one
kernel launch and a full HBM round trip of the activation at every stage
boundary: RF → IQ (n_s·n_c·n_f·2 floats) → beamformed IQ → head. This
kernel executes the whole RF-to-{envelope, wall-filtered power} chain in
ONE pallas_call, keeping every intermediate tile-resident:

  grid = (n_pix // bp,)   one pixel tile per step, "arbitrary"
                          (sequential: step 0 fills the IQ scratch)
  step 0:   demod the FULL RF block into a planar (2, n_c*n_f, n_s) VMEM
            scratch (the IQ cube is shared by every pixel tile, so it is
            computed once and persists across the sequential grid — this
            is the HBM traffic the fusion removes);
  step i:   for each channel, the das_beamform channel body
            (`repro.kernels.das_beamform.kernel.beamform_channel`: one-hot
            interpolation weights built in VMEM, contracted on the MXU,
            rotated and apodized) accumulates into the (n_f, bp) tile, then
            the head's tile-local half runs: |z| envelope (bmode) or
            wall-filter + R0 frame power (power_doppler).

Everything is planar, pixels on the lane axis: tables (n_c, ., n_pix) as
in das_beamform, RF as (n_c*n_f, n_l), outputs (n_f, n_pix) / (1, n_pix).
The RF block and the IQ scratch are whole-acquisition VMEM residents, so
the kernel fits VMEM only at reduced geometries; `fused_vmem_bytes` is
the estimate the capability predicate (repro.core.lowering) holds to
`VMEM_LIMIT_BYTES` on the TPU.

The head's *global* half (normalize_by_max over all pixels, dB
compression, power-doppler's 2-D smooth) is NOT in the kernel — a
single-pass tiled kernel cannot see the global max. The fused lowering
runs it as a pointwise XLA epilogue reusing the reference head's own
``compress`` functions verbatim (repro.core.bmode / doppler), so the
boundary adds no numeric drift. See docs/kernels.md.

Determinism contract
--------------------
``precision="f32"`` + interpret mode executes the *reference modules'
own expressions* inside the kernel body: ``demod.rf_to_iq`` and
``doppler.apply_wall_filter`` are imported and called on the VMEM
blocks, and the beamform uses the das_beamform channel body (zero
one-hot terms add exactly in f32; channels accumulate in ascending
order, the reference sum's order) — so the fused f32 path is bit-exact
against the monolithic oracle by construction, asserted in
tests/test_fused_pipeline.py. The compiled path (TPU) re-expresses the
demod FIR as a banded weight matrix built in VMEM and fed to the MXU
(Mosaic lowers matmuls, not conv_general); it has not run on the chip.

``precision="bf16"/"f16"`` casts the MATMUL OPERANDS (banded demod FIR,
one-hot DAS weights, and their IQ counterparts) to the reduced dtype
with f32 accumulation (preferred_element_type); all pointwise math
stays f32. The image-level error bounds live in
``repro.core.config.PRECISION_TOLERANCES``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

# The interpret/f32 path reuses the reference stage expressions verbatim
# (the bit-exactness contract above). Safe import direction: repro.core
# never imports repro.kernels at module scope.
from repro.core import cnn_ops, demod, doppler
from repro.kernels.das_beamform.kernel import (COMPUTE_DTYPES,
                                               beamform_channel,
                                               dot_precision)
from repro.kernels.pallas_compat import (CompilerParams, VMEM_LIMIT_BYTES,
                                         next_multiple)

DEFAULT_BP = 128  # pixel-tile lanes (multiple of 128 on the TPU)


def fused_vmem_bytes(n_l: int, n_c: int, n_f: int, decim: int) -> int:
    """VMEM the kernel keeps resident at this geometry (f32 bytes, lane-
    padded): the double-buffered (n_c*n_f, n_l) RF block, its two mixed
    copies, the banded FIR and the planar IQ scratch. Pixel-tile blocks
    are small next to these and are left out."""
    rows = next_multiple(n_c * n_f, 8)
    n_l_p = next_multiple(n_l, 128)
    n_s_p = next_multiple(-(-n_l // decim), 128)
    rf = rows * n_l_p * 4
    return 4 * rf + n_l_p * n_s_p * 4 + 2 * rows * n_s_p * 4


def _banded_fir_t(lpf, n_l: int, n_s: int, n_s_pad: int, decim: int,
                  pad_lo: int, n_taps: int):
    """The decimating SAME-padded FIR as a dense banded (n_l, n_s_pad)
    matrix.

    W[l, s] = lpf[k] where l = s*decim + k - pad_lo and s < n_s.
    Out-of-range taps simply never match — the implicit zero padding of
    the conv — and the pad columns stay zero. Built in VMEM per kernel
    invocation (the one-hot trick of das_beamform applied to the demod
    FIR).
    """
    row = lax.broadcasted_iota(jnp.int32, (n_l, n_s_pad), 0)
    col = lax.broadcasted_iota(jnp.int32, (n_l, n_s_pad), 1)
    w = jnp.zeros((n_l, n_s_pad), dtype=jnp.float32)
    for k in range(n_taps):       # static tap loop
        w = w + jnp.where((row == col * decim + (k - pad_lo)) & (col < n_s),
                          lpf[k], 0.0)
    return w


def _demod_matmul(carrier, lpf, rf, *, decim, n_s, n_s_pad, pad_lo,
                  n_taps, precision):
    """Compiled-path demod: mix, then the banded FIR as one MXU matmul.

    rf (n_c*n_f, n_l), carrier (2, n_l) -> planar IQ, 2x
    (n_c*n_f, n_s_pad).
    """
    cdt = COMPUTE_DTYPES[precision]
    w = _banded_fir_t(lpf, rf.shape[1], n_s, n_s_pad, decim, pad_lo,
                      n_taps).astype(cdt)
    out = []
    for r in range(2):
        mixed = (rf * carrier[r:r + 1]).astype(cdt)
        out.append(jnp.dot(mixed, w, precision=dot_precision(precision),
                           preferred_element_type=jnp.float32))
    return out


def _wall_power_tile(wall, bf_re, bf_im, *, exact):
    """Tile-local power-doppler front on planar (n_f, bp) beamformed IQ:
    FIR along frames -> (1, bp) R0 power."""
    if exact:
        # Reference expression, verbatim (bit-exact in interpret mode).
        z = doppler.apply_wall_filter(
            {"wall_taps": wall}, jnp.stack([bf_re.T, bf_im.T], axis=-1))
        return cnn_ops.cabs2(z).sum(axis=1)[None, :]
    kw = wall.shape[0]
    n_fp = bf_re.shape[0] - kw + 1
    acc_re = jnp.zeros((n_fp, bf_re.shape[1]), dtype=jnp.float32)
    acc_im = acc_re
    for t in range(kw):                                  # static tap loop
        acc_re = acc_re + wall[t] * bf_re[t:t + n_fp]
        acc_im = acc_im + wall[t] * bf_im[t:t + n_fp]
    return (acc_re * acc_re + acc_im * acc_im).sum(axis=0, keepdims=True)


def _make_kernel(head: str, *, n_c, n_f, n_l, decim, n_s, pad_lo, n_taps,
                 precision, exact):

    def kernel(carrier_ref, lpf_ref, idx_ref, tab_ref, *rest):
        if head == "power_doppler":
            wall_ref, rf_ref, out_ref, iq_ref = rest
        else:
            rf_ref, out_ref, iq_ref = rest

        @pl.when(pl.program_id(0) == 0)
        def _demod_once():
            # The IQ cube is pixel-independent: computed on the first
            # grid step only, persisted in scratch across the sequential
            # steps — the HBM round trip the fusion eliminates.
            if exact:
                rf = rf_ref[...].reshape(n_c, n_f, n_l).transpose(2, 0, 1)
                iq = demod.rf_to_iq(
                    {"carrier": carrier_ref[...].T, "lpf": lpf_ref[0]},
                    rf, decim)                           # (n_s, c, f, 2)
                iq = iq.transpose(3, 1, 2, 0).reshape(2, n_c * n_f, -1)
                iq_ref[...] = jnp.zeros_like(iq_ref)
                iq_ref[:, :, :iq.shape[2]] = iq
            else:
                re, im = _demod_matmul(
                    carrier_ref[...], lpf_ref[0], rf_ref[...],
                    decim=decim, n_s=n_s, n_s_pad=iq_ref.shape[2],
                    pad_lo=pad_lo, n_taps=n_taps, precision=precision)
                iq_ref[0] = re
                iq_ref[1] = im

        bf_re = jnp.zeros((n_f, idx_ref.shape[2]), jnp.float32)
        bf_im = bf_re
        for c in range(n_c):              # ascending: the reference order
            re, im = beamform_channel(
                idx_ref[c], tab_ref[c], iq_ref[0, c * n_f:(c + 1) * n_f],
                iq_ref[1, c * n_f:(c + 1) * n_f], precision=precision)
            bf_re = bf_re + re
            bf_im = bf_im + im

        if head == "bmode":
            out_ref[...] = cnn_ops.magnitude(bf_re, bf_im)   # (n_f, bp)
        else:
            out_ref[...] = _wall_power_tile(
                wall_ref[0], bf_re, bf_im, exact=exact)      # (1, bp)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("head", "n_f", "decim", "bp", "precision",
                     "interpret"))
def fused_pipeline_pallas(carrier, lpf, idx, tab, rf, wall=None, *,
                          head: str, n_f: int, decim: int,
                          bp: int = DEFAULT_BP, precision: str = "f32",
                          interpret: bool = True):
    """(n_c*n_f, n_l) RF -> (n_f, n_pix) envelope [bmode] or (1, n_pix)
    wall-filtered power R0 [power_doppler].

    Tables come planar and padded from das_beamform.ops.planar_tables
    (n_pix a multiple of bp); carrier arrives (2, n_l), lpf (1, k) and
    wall (1, kw) so every VMEM block is >= 2-D.
    """
    n_c, _, n_pix = idx.shape
    n_l = rf.shape[1]
    n_s = n_l // decim
    assert n_pix % bp == 0, (n_pix, bp)
    n_taps = lpf.shape[-1]
    pad_lo = demod._same_pad(n_l, n_taps, decim)[0]
    # Reference-expression path: only meaningful where the interpreter
    # executes real XLA convs; the compiled path feeds the MXU matmul
    # re-expressions. Reduced precision always takes the matmul path —
    # the operand casts ARE the precision contract.
    exact = precision == "f32" and interpret

    kernel = _make_kernel(head, n_c=n_c, n_f=n_f, n_l=n_l, decim=decim,
                          n_s=n_s, pad_lo=pad_lo, n_taps=n_taps,
                          precision=precision, exact=exact)

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    in_specs = [whole(carrier), whole(lpf),
                pl.BlockSpec((n_c, 1, bp), lambda i: (0, 0, i)),
                pl.BlockSpec((n_c, 4, bp), lambda i: (0, 0, i))]
    args = [carrier, lpf, idx, tab]
    if head == "power_doppler":
        in_specs.append(whole(wall))
        args.append(wall)
        rows = 1
    elif head == "bmode":
        rows = n_f
    else:
        raise ValueError(f"unsupported fused head: {head!r}")
    in_specs.append(whole(rf))
    args.append(rf)

    return pl.pallas_call(
        kernel,
        grid=(n_pix // bp,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, bp), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, n_pix), jnp.float32),
        scratch_shapes=[pltpu.VMEM(
            (2, rf.shape[0], next_multiple(n_s, 128)), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*args)
