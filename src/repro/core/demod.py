"""RF -> IQ quadrature demodulation, expressed as CNN primitives.

Stages (all static, deterministic):
  1. pointwise mix with the precomputed carrier (cos / -sin at f0),
  2. FIR low-pass + decimation as an explicitly ordered shift-and-add
     (a strided 1-D conv with the tap accumulation order pinned), read
     in polyphase form.

The carrier vectors and FIR taps are init-time constants (paper §II-C).
Complex IQ is carried as a trailing (re, im) axis — no complex dtypes.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import jax.numpy as jnp

from repro.core.config import UltrasoundConfig


def design_lowpass(cfg: UltrasoundConfig) -> np.ndarray:
    """Hamming-windowed sinc FIR, cutoff = lpf_cutoff * f0 (one-sided)."""
    n = cfg.lpf_taps
    assert n % 2 == 1, "FIR length must be odd for linear phase"
    fc = cfg.lpf_cutoff * cfg.f0 / cfg.fs  # normalized cutoff (cycles/sample)
    m = np.arange(n) - (n - 1) / 2.0
    h = 2 * fc * np.sinc(2 * fc * m)
    h *= np.hamming(n)
    h /= h.sum()
    return h.astype(np.float32)


def demod_consts(cfg: UltrasoundConfig) -> Dict[str, np.ndarray]:
    t = np.arange(cfg.n_l, dtype=np.float64) / cfg.fs
    ph = 2.0 * np.pi * cfg.f0 * t
    # Factor 2 restores the analytic-signal amplitude after low-pass.
    carrier = np.stack([2.0 * np.cos(ph), -2.0 * np.sin(ph)], axis=-1)
    return {
        "carrier": carrier.astype(np.float32),      # (n_l, 2)
        "lpf": design_lowpass(cfg),                 # (taps,)
    }


def rf_to_iq(consts: Dict[str, jnp.ndarray], rf: jnp.ndarray,
             decim: int) -> jnp.ndarray:
    """(n_l, n_c, n_f) RF -> (n_s, n_c, n_f, 2) IQ.

    The mix is pointwise; the low-pass + decimation is a strided FIR over
    the axial axis with 'SAME' padding (output length ceil(n_l / decim)),
    written as an explicitly ordered shift-and-add over the taps rather
    than lax.conv: XLA:CPU emits differently-rounded (1-ulp) conv code for
    this strided shape inside loop bodies (fori_loop / pallas grids), so a
    conv-based reference could never be matched bitwise by a fused kernel.
    Pinning the tap accumulation order makes the demod bit-identical in
    every execution context at identical cost (k FMAs per output sample).

    The FIR is read in polyphase form, so no tap is a strided slice: the
    padded signal is reshaped to (n_q, decim, ...), and tap
    t = decim * a + r reads the unit-stride window p[a:a + n_s, r], the
    same samples a stride-decim slice from t would gather. On the TPU a
    strided slice of the axial axis is a gather across lanes that XLA
    neither fuses nor costs; unit-stride windows fuse with the
    accumulation into one pass. The RF and the carrier are zero-padded
    before the mix (0 * 0 is the +0.0 a pad of the mixed signal holds),
    so the pad fuses with the int16 conversion instead of copying the
    mixed signal once more.
    """
    n_l, n_c, n_f = rf.shape
    lpf = consts["lpf"]                                        # (k,)
    k = lpf.shape[0]
    n_s = -(-n_l // decim)
    n_q = n_s + (k - 1) // decim       # phases cover every tap's window
    pad_lo, _ = _same_pad(n_l, k, decim)
    axial = (pad_lo, n_q * decim - n_l - pad_lo)
    x = jnp.pad(rf.astype(jnp.float32), (axial, (0, 0), (0, 0)))
    carrier = jnp.pad(consts["carrier"], (axial, (0, 0)))
    mixed = x[..., None] * carrier[:, None, None, :]  # (n_q*decim,c,f,2)

    p = mixed.reshape(n_q, decim, n_c, n_f, 2)
    acc = jnp.zeros((n_s, n_c, n_f, 2), jnp.float32)
    for t in range(k):  # static unroll; ascending tap order is the contract
        a, r = divmod(t, decim)
        acc = acc + lpf[t] * p[a:a + n_s, r]
    return acc


def _same_pad(length: int, k: int, stride: int):
    """TF-style SAME padding for output length ceil(length / stride)."""
    out = -(-length // stride)
    total = max((out - 1) * stride + k - length, 0)
    lo = total // 2
    return (lo, total - lo)
