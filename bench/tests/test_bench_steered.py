"""Steered plane waves in the benchmark's RF, reference and work count, on
the CPU at a small size (bench/tests/fixtures/small-pw.json: 16 elements,
5 angles over +-16 degrees, 2 frames)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import reference, rf, steering, work

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "small-pw.json")
# Seed 11 puts the one scatterer of a point-target pool at z = 8.54 mm,
# x = 0.50 mm: inside the grid, away from its edges.
POINT_SEED = 11


@pytest.fixture(scope="module")
def conf():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def g(conf):
    return steering.geometry(reference.GEOMETRY_KEYS, conf["pipeline"])


def one_scatterer(conf, **kw):
    return dict(conf["rf"], n_scatter=1, noise=0.0, **kw)


def envelope(trace):
    """|analytic signal| of a real trace (FFT Hilbert transform)."""
    n = trace.shape[0]
    h = np.zeros(n)
    h[0] = 1.0
    h[1:(n + 1) // 2] = 2.0
    if n % 2 == 0:
        h[n // 2] = 1.0
    return np.abs(np.fft.ifft(np.fft.fft(trace) * h))


def test_angles_and_event_layout(g, conf):
    np.testing.assert_allclose(np.rad2deg(steering.angles(g)),
                               [-16.0, -8.0, 0.0, 8.0, 16.0])
    assert steering.angles(dict(g, n_angles=1)).tolist() == [0.0]
    x = rf.make_pool(conf["pipeline"], conf["rf"], [3])
    assert x.shape == (1, g["n_l"], g["n_c"], g["n_f"] * g["n_angles"])
    assert x.dtype == np.int16


@pytest.mark.parametrize("seed", [0, POINT_SEED])
def test_each_events_echo_arrives_at_the_steered_time_of_flight(g, conf,
                                                               seed):
    # The scatterer moves 2 wavelengths (16 samples of two-way delay) per
    # frame, so an event that saw another frame's position would miss.
    p = one_scatterer(conf, flow_fraction=1.0, flow_speed=2.0)
    x = rf.make_pool(conf["pipeline"], p, [seed])[0].astype(np.float64)
    zs, xs, _, moving, _ = rf.scatterers(
        g, p, jax.random.wrap_key_data(rf.key_data(seed)))
    assert bool(moving[0])
    centre = g["n_c"] // 2
    x_c = (centre - (g["n_c"] - 1) / 2.0) * g["pitch"]
    lam = g["c_sound"] / g["f0"]
    theta = steering.angles(g)
    for e in range(g["n_f"] * g["n_angles"]):
        f, a = divmod(e, g["n_angles"])
        z = float(zs[0]) + 2.0 * lam * f
        xp = float(xs[0])
        tau = (z * np.cos(theta[a]) + xp * np.sin(theta[a])
               + np.hypot(z, xp - x_c)) / g["c_sound"]
        peak = np.argmax(envelope(x[:, centre, e]))
        assert abs(peak - tau * g["fs"]) <= 1.0, (e, peak, tau * g["fs"])


def das_float64(g, x):
    """Plain DAS with compounding in float64, written from the definition:
    demodulate, then for every angle, pixel and channel interpolate the IQ
    at the two-way delay, rotate, weight, sum; average over angles."""
    n_l, n_c, n_f, n_a = g["n_l"], g["n_c"], g["n_f"], g["n_angles"]
    decim, fs, f0, c = g["decim"], g["fs"], g["f0"], g["c_sound"]
    h = reference.lowpass(g).astype(np.float64)
    k = h.shape[0]
    n_s = n_l // decim
    pad_lo = max((n_s - 1) * decim + k - n_l, 0) // 2
    t = np.arange(n_l) / fs
    bb = x.astype(np.float64) * 2.0 * np.exp(-2j * np.pi * f0 * t)[:, None,
                                                                    None]
    bb = np.concatenate([np.zeros((pad_lo,) + bb.shape[1:]), bb,
                         np.zeros((k,) + bb.shape[1:])])
    iq = sum(h[j] * bb[j:j + n_s * decim:decim] for j in range(k))
    iq = iq.reshape(n_s, n_c, n_f, n_a)
    xc = (np.arange(n_c) - (n_c - 1) / 2.0) * g["pitch"]
    half_ap = (n_c - 1) / 2.0 * g["pitch"]
    zs = np.linspace(g["z_min"], g["z_max"], g["nz"])
    xs = np.linspace(-half_ap, half_ap, g["nx"])
    out = np.zeros((g["nz"], g["nx"], n_f), np.complex128)
    for a, th in enumerate(steering.angles(g)):
        for iz, z in enumerate(zs):
            for ix, xp in enumerate(xs):
                acc = np.zeros(n_f, np.complex128)
                wsum = 0.0
                for ch in range(n_c):
                    tau = (z * np.cos(th) + xp * np.sin(th)
                           + np.hypot(z, xp - xc[ch])) / c
                    s = tau * fs / decim
                    i = int(np.floor(s))
                    dx = abs(xp - xc[ch])
                    half = z / (2.0 * g["f_number"])
                    if not (0 <= i < n_s - 1) or dx > half:
                        continue
                    w = 0.5 + 0.5 * np.cos(np.pi * min(dx / half, 1.0))
                    fr = s - i
                    v = (iq[i, ch, :, a] * (1.0 - fr)
                         + iq[i + 1, ch, :, a] * fr)
                    acc += w * v * np.exp(2j * np.pi * f0 * tau)
                    wsum += w
                if wsum > 0:
                    out[iz, ix] += acc / wsum
    return out / n_a


def iq_image(pipe, x):
    """The reference's (compounded) complex image, (nz, nx, n_f)."""
    g = steering.geometry(reference.GEOMETRY_KEYS, pipe)
    consts = jax.tree.map(jnp.asarray, reference.constants(g))
    bf = reference.beamform if g["n_angles"] == 1 else reference.compound
    re, im = jax.jit(lambda c, r: bf(g, c, r, "highest"))(consts, x)
    return (np.asarray(re, np.float64) + 1j * np.asarray(im, np.float64)
            ).reshape(g["nz"], g["nx"], g["n_f"])


def test_compounded_reference_matches_a_float64_das(g, conf):
    x = rf.make_pool(conf["pipeline"], conf["rf"], [2 ** 40 + 7])[0]
    got, want = iq_image(conf["pipeline"], x), das_float64(g, x)
    # float32 against float64: the FIR, the tables rounded to float32
    # (fraction and phasor), the sums over 16 channels and 5 angles. This
    # seed reads 1.0e-7 of the image's peak; 1e-6 leaves ten times that,
    # and a table off by one sample, a wrong sign in either delay or a
    # sum in place of the mean is off by 1e-1 or more.
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-6, err


def side_lobes(env, iz, ix, r=3):
    """Mean envelope outside r pixels of the peak at (iz, ix)."""
    mask = np.ones(env.shape, bool)
    mask[max(iz - r, 0):iz + r + 1, max(ix - r, 0):ix + r + 1] = False
    return env[mask].mean()


def test_point_target_peaks_at_the_scatterer_and_compounding_lowers_side_lobes(
        g, conf):
    p = one_scatterer(conf)
    zs, xs, *_ = rf.scatterers(
        g, p, jax.random.wrap_key_data(rf.key_data(POINT_SEED)))
    half_ap = (g["n_c"] - 1) / 2.0 * g["pitch"]
    dz = (g["z_max"] - g["z_min"]) / (g["nz"] - 1)
    dx = 2 * half_ap / (g["nx"] - 1)
    lobes = []
    for n_a in (1, 3, 5):
        pipe = dict(conf["pipeline"], n_angles=n_a)
        env = np.abs(iq_image(pipe, rf.make_pool(pipe, p, [POINT_SEED])[0]))
        env = env[..., 0] / env[..., 0].max()
        iz, ix = np.unravel_index(np.argmax(env), env.shape)
        assert abs(g["z_min"] + iz * dz - float(zs[0])) <= dz, (n_a, iz)
        assert abs(-half_ap + ix * dx - float(xs[0])) <= dx, (n_a, ix)
        lobes.append(side_lobes(env, iz, ix))
    assert lobes[0] > lobes[1] > lobes[2], lobes


@pytest.mark.parametrize("modality", ["bmode", "doppler", "power_doppler"])
def test_work_grows_linearly_in_the_angles_for_each_term(g, modality):
    g = dict(g, n_f=8, wall_filter_taps=4)
    t = {n: work.terms(dict(g, n_angles=n), modality) for n in (1, 2, 3, 5)}
    assert t[1]["compound"] == 0
    pix = g["nz"] * g["nx"]
    for n in (2, 3, 5):
        assert t[n]["demod"] == n * t[1]["demod"]
        assert t[n]["beamform"] == n * t[1]["beamform"]
        assert t[n]["compound"] == 2 * pix * g["n_f"] * (n - 1) + (
            2 * pix * g["n_f"])
        assert t[n]["head"] == t[1]["head"]
        assert work.flops_per_acquisition(dict(g, n_angles=n), modality) == (
            sum(t[n].values()))
