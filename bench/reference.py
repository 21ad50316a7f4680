"""Plain reference of the RF-to-image pipelines the benchmark serves.

Written from the pipeline's definition, in straightforward `jax.numpy`
and float32, and independent of the program under test: it builds its
own delay tables and filters from the configuration's numbers and
imports nothing of `repro`.

    RF (n_l, n_c, n_f * n_angles) int16, event e = f * n_angles + a
      -> demod: carrier mix, then the 'SAME'-padded, decimated FIR low-pass
         written as one (n_s, n_l) banded matrix
      -> DAS: for each pixel and channel, the two IQ samples around the
         two-way delay of the event's steering angle (bench/steering.py),
         linear interpolation, the phase rotation exp(+j 2 pi f0 tau), and
         the apodized sum over channels
      -> compounding: the mean over the n_angles events of each frame,
         in re and im (nothing to do for one 0-degree wave)
      -> head: B-mode (envelope, 20 log10, dynamic range), color Doppler
         (wall filter, lag-one autocorrelation, atan2, 3x3 box smooth) or
         power Doppler (wall filter, R0, 10 log10, 3x3 box smooth)

Where the configuration states `cnn_transcendentals`, the logarithm and
atan2 are the bounded-error approximations it names (16 square roots for
ln, a 9th-order polynomial for atan); those formulas are copied here so
that the comparison is with the stated computation, not with a different
one.

The matrix contractions (the FIR and the channel sum) run in float32
(``Precision.HIGHEST``, six bf16 passes on the TPU's MXU). The control
runs them one step lower, as the TPU's ``Precision.HIGH`` does: each
operand split into a bf16 high and low part and the three larger
products summed (``"bf16_3x"``), written out with integer masks so that
it means the same on every backend and no compiler can fold it away.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from bench import steering

# Fields of a configuration's "pipeline" block that the reference reads.
GEOMETRY_KEYS = ("n_l", "n_c", "n_f", "fs", "f0", "c_sound", "pitch",
                 "decim", "lpf_taps", "lpf_cutoff", "nz", "nx", "z_min",
                 "z_max", "f_number", "dynamic_range_db", "wall_filter_taps",
                 "smooth_kernel", "cnn_transcendentals", "n_angles",
                 "angle_max_deg")

EPS = 1e-30
PRECISIONS = ("highest", "bf16_3x")


# ---------------------------------------------------------------------------
# Constants (numpy, float64 arithmetic, stored as float32)
# ---------------------------------------------------------------------------


def element_positions(g: Dict) -> np.ndarray:
    return (np.arange(g["n_c"], dtype=np.float64)
            - (g["n_c"] - 1) / 2.0) * g["pitch"]


def pixel_positions(g: Dict):
    """(z, x) of every pixel, row-major over (nz, nx)."""
    half_ap = (g["n_c"] - 1) / 2.0 * g["pitch"]
    z = np.linspace(g["z_min"], g["z_max"], g["nz"])
    x = np.linspace(-half_ap, half_ap, g["nx"])
    zz, xx = np.meshgrid(z, x, indexing="ij")
    return zz.reshape(-1), xx.reshape(-1)


def receive_aperture(g: Dict):
    """(z, dx, weight) per (pixel, channel): the pixel's depth, its
    lateral offset from the element, and the dynamic Hann aperture of
    half-width z / (2 f_number), before the delay's range is applied."""
    zp, xp = pixel_positions(g)
    xc = element_positions(g)
    z = zp[:, None]
    dx = xp[:, None] - xc[None, :]
    half = z / (2.0 * g["f_number"])
    rel = np.clip(np.abs(dx) / np.maximum(half, 1e-9), 0.0, 1.0)
    return z, dx, (0.5 + 0.5 * np.cos(np.pi * rel)) * (np.abs(dx) <= half)


def delay_tables(g: Dict) -> Dict[str, np.ndarray]:
    """One 0-degree wave. Per (pixel, channel): sample index,
    interpolation fraction, apodization and the rotation phasor of the
    two-way delay (the transmit path is the depth)."""
    z, dx, weight = receive_aperture(g)
    tau = (z + np.sqrt(z * z + dx * dx)) / g["c_sound"]
    n_s = g["n_l"] // g["decim"]
    s = tau * g["fs"] / g["decim"]
    idx = np.floor(s)
    frac = s - idx
    valid = (idx >= 0) & (idx < n_s - 1)
    idx = np.clip(idx, 0, n_s - 2).astype(np.int32)
    apod = weight * valid
    apod = apod / np.maximum(apod.sum(axis=1, keepdims=True), 1e-9)
    phase = 2.0 * np.pi * g["f0"] * tau
    return {"idx": idx, "frac": frac.astype(np.float32),
            "apod": apod.astype(np.float32),
            "rot_re": np.cos(phase).astype(np.float32),
            "rot_im": np.sin(phase).astype(np.float32)}


def steered_tables(g: Dict) -> Dict[str, np.ndarray]:
    """Steered waves, factored so that memory does not grow with the
    angles: the receive part per (pixel, channel) and the transmit part
    per (angle, pixel), each delay in float64 as whole IQ samples, the
    fraction left over, and its rotation phasor. `angle_tables` adds
    them up for one angle on the device."""
    z, dx, weight = receive_aperture(g)
    zp, xp = pixel_positions(g)
    theta = steering.angles(g)[:, None]
    per_sample = g["fs"] / g["decim"] / g["c_sound"]
    per_radian = 2.0 * np.pi * g["f0"] / g["c_sound"]
    out = {"weight": weight.astype(np.float32)}
    for part, path in (("rx", np.sqrt(z * z + dx * dx)),
                       ("tx", steering.transmit_path(
                           zp[None], xp[None], np.cos(theta),
                           np.sin(theta)))):
        s = path * per_sample
        idx = np.floor(s)
        out.update({f"idx_{part}": idx.astype(np.int32),
                    f"frac_{part}": (s - idx).astype(np.float32),
                    f"rot_re_{part}": np.cos(path * per_radian).astype(
                        np.float32),
                    f"rot_im_{part}": np.sin(path * per_radian).astype(
                        np.float32)})
    return out


def angle_tables(g: Dict, c: Dict, a):
    """The tables of `delay_tables` for steering angle ``a``, from
    `steered_tables`' parts."""
    n_s = g["n_l"] // g["decim"]
    u = c["frac_rx"] + c["frac_tx"][a][:, None]
    carry = jnp.floor(u)
    idx = c["idx_rx"] + c["idx_tx"][a][:, None] + carry.astype(jnp.int32)
    valid = (idx >= 0) & (idx < n_s - 1)
    apod = c["weight"] * valid
    apod = apod / jnp.maximum(apod.sum(axis=1, keepdims=True), 1e-9)
    t_re, t_im = c["rot_re_tx"][a][:, None], c["rot_im_tx"][a][:, None]
    return {"idx": jnp.clip(idx, 0, n_s - 2), "frac": u - carry,
            "apod": apod,
            "rot_re": c["rot_re_rx"] * t_re - c["rot_im_rx"] * t_im,
            "rot_im": c["rot_re_rx"] * t_im + c["rot_im_rx"] * t_re}


def lowpass(g: Dict) -> np.ndarray:
    """Hamming-windowed sinc, cutoff lpf_cutoff * f0, unit DC gain."""
    n = g["lpf_taps"]
    fc = g["lpf_cutoff"] * g["f0"] / g["fs"]
    m = np.arange(n) - (n - 1) / 2.0
    h = 2 * fc * np.sinc(2 * fc * m) * np.hamming(n)
    return (h / h.sum()).astype(np.float32)


def demod_matrix(g: Dict) -> np.ndarray:
    """(n_s, n_l) matrix of the 'SAME'-padded FIR followed by decimation:
    output s sums lpf[t] * x[s * decim + t - pad_lo]."""
    n_l, decim, h = g["n_l"], g["decim"], lowpass(g)
    k = h.shape[0]
    n_s = -(-n_l // decim)
    pad_lo = max((n_s - 1) * decim + k - n_l, 0) // 2
    mat = np.zeros((n_s, n_l), np.float32)
    for s in range(n_s):
        for t in range(k):
            l = s * decim + t - pad_lo
            if 0 <= l < n_l:
                mat[s, l] += h[t]
    return mat


def carrier(g: Dict) -> np.ndarray:
    """(n_l, 2): 2 cos and -2 sin of the carrier (analytic amplitude)."""
    ph = 2.0 * np.pi * g["f0"] * np.arange(g["n_l"]) / g["fs"]
    return np.stack([2.0 * np.cos(ph), -2.0 * np.sin(ph)], -1).astype(
        np.float32)


def wall_taps(g: Dict) -> np.ndarray:
    """Binomial high-pass along frames, unit l2 norm."""
    taps = np.array([1.0])
    for _ in range(max(g["wall_filter_taps"] - 1, 1)):
        taps = np.convolve(taps, [1.0, -1.0])
    return (taps / np.sqrt((taps ** 2).sum())).astype(np.float32)


# ---------------------------------------------------------------------------
# Approximations the configuration names (cnn_transcendentals)
# ---------------------------------------------------------------------------


def ln_approx(x):
    y = jnp.maximum(x, EPS)
    for _ in range(16):
        y = jnp.sqrt(y)
    return 65536.0 * (y - 1.0) * 2.0 / (1.0 + y)


def log10(x, approx: bool):
    if approx:
        return ln_approx(x) / float(np.log(10.0))
    return jnp.log10(jnp.maximum(x, EPS))


def atan2(y, x, approx: bool):
    if not approx:
        return jnp.arctan2(y, x)
    ax, ay = jnp.abs(x), jnp.abs(y)
    z = jnp.minimum(ax, ay) / (jnp.maximum(ax, ay) + EPS)
    z2 = z * z
    base = z * (0.9998660 + z2 * (-0.3302995 + z2 * (
        0.1801410 + z2 * (-0.0851330 + z2 * 0.0208351))))

    def pick(mask, a, b):
        m = mask.astype(jnp.float32)
        return m * a + (1.0 - m) * b
    ang = pick(ay >= ax, np.pi / 2 - base, base)
    ang = pick((x <= 0) & (ax >= EPS), np.pi - ang, ang)
    return pick((y <= 0) & (ay >= EPS), -ang, ang)


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------


def box_smooth(img, k: int):
    """k x k mean filter with zero padding ('SAME')."""
    p = k // 2
    x = jnp.pad(img, ((p, k - 1 - p), (p, k - 1 - p)))
    nz, nx = img.shape
    acc = jnp.zeros_like(img)
    for a in range(k):
        for b in range(k):
            acc = acc + x[a:a + nz, b:b + nx]
    return acc / float(k * k)


def contract(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` in float32, or in three bf16 passes."""
    dot = functools.partial(jnp.einsum, spec,
                            precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return dot(a, b)

    def bf16(x):
        # Round to bf16's 8-bit mantissa (to nearest, ties to even) with
        # integer ops: a float round trip through bf16 is an
        # excess-precision convert pair that the compiler may drop, and
        # the low parts with it.
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    def split(x):
        hi = bf16(x)
        return hi, bf16(x - hi)
    (ah, al), (bh, bl) = split(a), split(b)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def demodulate(c: Dict, rf, precision: str):
    """(n_l, n_c, n_f) RF -> (n_s, n_c, n_f, 2) IQ."""
    x = rf.astype(jnp.float32)
    mixed = x[..., None] * c["carrier"][:, None, None, :]   # (n_l,c,f,2)
    return contract("sl,lcfr->scfr", c["demod"], mixed, precision)


def apodized_sum(c: Dict, v_re, v_im, precision: str):
    """IQ at each (pixel, channel)'s delay -> rotated by its phasor,
    apodized and summed over channels: (n_pix, n_f) as (re, im)."""
    re = v_re * c["rot_re"][..., None] - v_im * c["rot_im"][..., None]
    im = v_re * c["rot_im"][..., None] + v_im * c["rot_re"][..., None]
    return (contract("pcf,pc->pf", re, c["apod"], precision),
            contract("pcf,pc->pf", im, c["apod"], precision))


def beamform(g: Dict, c: Dict, rf, precision: str):
    """One 0-degree wave: (n_l, n_c, n_f) RF -> (n_pix, n_f) complex
    image as (re, im), with `delay_tables`' tables. Its (I, Q) pair
    gathers stay as they are, although `beamform_steered`'s rows are
    faster: another form compiles to another program on the TPU, whose
    last bits differ, and the images of configurations without the
    steering keys are pinned bit for bit."""
    iq = demodulate(c, rf, precision)
    chan = jnp.arange(g["n_c"])[None, :]
    s0 = iq[c["idx"], chan]                                  # (p,c,f,2)
    s1 = iq[c["idx"] + 1, chan]
    f = c["frac"][..., None, None]
    v = s0 * (1.0 - f) + s1 * f
    return apodized_sum(c, v[..., 0], v[..., 1], precision)


def beamform_steered(g: Dict, c: Dict, rf, precision: str):
    """One steering angle's events: (n_l, n_c, n_f) RF -> (n_pix, n_f)
    complex image as (re, im), with `angle_tables`' tables."""
    iq = demodulate(c, rf, precision)
    # Each (pixel, channel) gathers one row: I and Q at its two samples.
    # A gather on the TPU costs about the same per index whatever the
    # row holds, so one index per pair is about twice as fast as the two
    # that `beamform` takes (on a TPU v5e at PICMUS size, 256 x 256
    # pixels and 128 channels: 79 against 150 ms per angle).
    rows = jnp.concatenate([iq[:-1], iq[1:]], axis=-1)       # (s-1,c,f,4)
    rows = rows.reshape((-1,) + rows.shape[2:])
    at = rows[c["idx"] * g["n_c"] + jnp.arange(g["n_c"])[None, :]]
    f = c["frac"][..., None]
    return apodized_sum(c, at[..., 0] * (1.0 - f) + at[..., 2] * f,
                        at[..., 1] * (1.0 - f) + at[..., 3] * f, precision)


def compound(g: Dict, c: Dict, rf, precision: str):
    """(n_l, n_c, n_f * n_angles) RF -> (n_pix, n_f) coherently
    compounded image as (re, im): each angle's events beamformed with
    its own tables, one angle at a time, and the mean taken."""
    n_a = g["n_angles"]
    x = rf.reshape(g["n_l"], g["n_c"], g["n_f"], n_a)

    def add(acc, a):
        re, im = beamform_steered(g, dict(c, **angle_tables(g, c, a)),
                                  x[..., a], precision)
        return (acc[0] + re, acc[1] + im), None
    zero = jnp.zeros((g["nz"] * g["nx"], g["n_f"]), jnp.float32)
    (re, im), _ = jax.lax.scan(add, (zero, zero), jnp.arange(n_a))
    return re / n_a, im / n_a


def wall_filter(c: Dict, y):
    taps = c["wall"]
    k = taps.shape[0]
    n = y.shape[1] - k + 1
    return sum(taps[t] * y[:, t:t + n] for t in range(k))


def image(g: Dict, modality: str, precision: str, c: Dict, rf):
    """One acquisition's image, shaped as the program serves it."""
    approx = bool(g["cnn_transcendentals"])
    re, im = (beamform(g, c, rf, precision) if g["n_angles"] == 1
              else compound(g, c, rf, precision))
    dr = g["dynamic_range_db"]
    nz, nx = g["nz"], g["nx"]
    if modality == "bmode":
        env = jnp.sqrt(re * re + im * im)
        env = env / (env.max(axis=0, keepdims=True) + EPS)
        db = 20.0 * log10(env, approx)
        return ((jnp.clip(db, -dr, 0.0) + dr) / dr).reshape(nz, nx, -1)
    zr, zi = wall_filter(c, re), wall_filter(c, im)
    if modality == "doppler":
        r1_re = (zr[:, 1:] * zr[:, :-1] + zi[:, 1:] * zi[:, :-1]).sum(1)
        r1_im = (zi[:, 1:] * zr[:, :-1] - zr[:, 1:] * zi[:, :-1]).sum(1)
        v = atan2(r1_im, r1_re, approx) / np.pi
        return box_smooth(v.reshape(nz, nx), g["smooth_kernel"])
    if modality == "power_doppler":
        r0 = (zr * zr + zi * zi).sum(1)
        r0 = r0 / (r0.max() + EPS)
        db = 10.0 * log10(r0, approx)
        img = (jnp.clip(db, -dr, 0.0) + dr) / dr
        return box_smooth(img.reshape(nz, nx), g["smooth_kernel"])
    raise ValueError(f"no reference for modality {modality!r}")


def constants(g: Dict) -> Dict[str, np.ndarray]:
    consts = (delay_tables(g) if g["n_angles"] == 1
              else steered_tables(g))
    consts.update(carrier=carrier(g), demod=demod_matrix(g),
                  wall=wall_taps(g))
    return consts


class Reference:
    """The reference images of one configuration's geometry, one
    acquisition per call, at the contraction precision named at
    construction ("highest" for the benchmark, "bf16_3x" for the
    control)."""

    def __init__(self, geometry: Dict, precision: str = "highest"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.g = steering.geometry(GEOMETRY_KEYS, geometry)
        self.precision = precision
        self.consts = jax.tree.map(jnp.asarray, constants(self.g))
        self._fns = {}

    def __call__(self, modality: str, rf) -> np.ndarray:
        if modality not in self._fns:
            self._fns[modality] = jax.jit(functools.partial(
                image, self.g, modality, self.precision))
        return np.asarray(self._fns[modality](self.consts, rf))
