"""The benchmark's own RF: plane-wave point scatterers, made on the device.

The same physics as the program's synthetic RF, computed here so that
the inputs are the benchmark's and not the program's, and on the device
so that set-up does not spend seconds per acquisition on the host:

  * ``n_scatter`` point scatterers, uniform in depth over the image and
    laterally over the aperture, amplitudes uniform in [0.3, 1.0];
  * ``n_angles`` plane waves per frame, steered as bench/steering.py
    states (one 0-degree wave by default); each element receives a
    2-cycle Gaussian-enveloped pulse at f0 after the two-way time of
    flight of each transmit event;
  * the first ``flow_fraction`` of the scatterers move axially by
    ``flow_speed`` wavelengths per frame (blood), the rest are still
    (tissue), so that the Doppler heads see flow and clutter; every
    event of a frame sees the scatterers where that frame has them;
  * a noise floor of ``noise`` (relative to unit amplitude), then
    quantisation to int16 at 30000 / max|rf|, as an ADC would.

Every acquisition is a function of its own 64-bit seed alone: the same
seed gives the same RF, on any batch of seeds.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from bench import steering

GEOMETRY_KEYS = ("n_l", "n_c", "n_f", "fs", "f0", "c_sound", "pitch",
                 "z_min", "z_max", "n_angles", "angle_max_deg")
PHYSICS_KEYS = ("n_scatter", "flow_fraction", "flow_speed", "noise")


def key_data(seed: int) -> np.ndarray:
    """A threefry key from a non-negative seed of up to 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned integer")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def scatterers(g: Dict, p: Dict, key):
    """(z, x, amplitude, moving) of the scatterers at frame 0, and the
    key of the noise."""
    k_z, k_x, k_a, k_n = jax.random.split(key, 4)
    ns = p["n_scatter"]
    half_ap = (g["n_c"] - 1) / 2.0 * g["pitch"]
    zs = jax.random.uniform(k_z, (ns,), jnp.float32, g["z_min"], g["z_max"])
    xs = jax.random.uniform(k_x, (ns,), jnp.float32, -half_ap, half_ap)
    amp = jax.random.uniform(k_a, (ns,), jnp.float32, 0.3, 1.0)
    moving = jnp.arange(ns) < int(p["flow_fraction"] * ns)
    return zs, xs, amp, moving, k_n


def _one(g: Dict, p: Dict, kd):
    zs, xs, amp, moving, k_n = scatterers(g, p, jax.random.wrap_key_data(kd))
    xc = jnp.asarray((np.arange(g["n_c"]) - (g["n_c"] - 1) / 2.0)
                     * g["pitch"], jnp.float32)
    t = jnp.arange(g["n_l"], dtype=jnp.float32) / g["fs"]
    lam = g["c_sound"] / g["f0"]
    sigma = 1.0 / g["f0"]

    def event(f, cos_t=None, sin_t=None):
        z = zs + jnp.where(moving, p["flow_speed"] * lam * f, 0.0)
        d_rx = jnp.sqrt(z[None, :] ** 2 + (xs[None, :] - xc[:, None]) ** 2)
        d_tx = z if cos_t is None else steering.transmit_path(
            z, xs, cos_t, sin_t)
        tof = (d_tx[None, :] + d_rx) / g["c_sound"]            # (n_c, ns)
        arg = t[:, None, None] - tof[None]                     # (n_l,n_c,ns)
        pulse = (jnp.exp(-0.5 * (arg / sigma) ** 2)
                 * jnp.cos(2 * np.pi * g["f0"] * arg))
        return (pulse * amp).sum(-1)                           # (n_l, n_c)

    n_a = g["n_angles"]
    if n_a == 1:
        # One 0-degree wave per frame: the transmit path is the depth and
        # the map runs over frames, the very program that a configuration
        # without the steering keys has always compiled, so that its RF
        # stays bit for bit on every backend
        # (bench/tests/fixtures/zero-degree-outputs.json).
        rf = jax.lax.map(event, jnp.arange(g["n_f"], dtype=jnp.float32))
    else:
        theta = steering.angles(g)
        a = np.arange(g["n_f"] * n_a) % n_a          # event e = f * n_a + a
        rf = jax.lax.map(lambda x: event(*x), (
            jnp.asarray(np.arange(g["n_f"] * n_a) // n_a, jnp.float32),
            jnp.asarray(np.cos(theta)[a], jnp.float32),
            jnp.asarray(np.sin(theta)[a], jnp.float32)))
    rf = rf.transpose(1, 2, 0)                                 # (n_l,n_c,E)
    rf = rf + p["noise"] * jax.random.normal(k_n, rf.shape, jnp.float32)
    scale = 30000.0 / jnp.maximum(jnp.abs(rf).max(), 1e-9)
    return (rf * scale).astype(jnp.int16)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pool(g_items, p_items, kd):
    g, p = dict(g_items), dict(p_items)
    return jax.lax.map(functools.partial(_one, g, p), kd)


def make_pool(geometry: Dict, physics: Dict,
              seeds: Sequence[int]) -> np.ndarray:
    """(len(seeds), n_l, n_c, n_f * n_angles) int16 RF, one acquisition
    per seed, made in one jitted call on the default device."""
    g = tuple(steering.geometry(GEOMETRY_KEYS, geometry).items())
    p = tuple((k, physics[k]) for k in PHYSICS_KEYS)
    kd = np.stack([key_data(s) for s in seeds])
    return np.asarray(_pool(g, p, kd))
