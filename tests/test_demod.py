"""RF->IQ demodulation: a pure tone at f0 demodulates to a constant
envelope; decimation produces exactly n_l // decim samples; the
polyphase FIR equals a strided one bit for bit."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import paper_config, tiny_config
from repro.core.demod import _same_pad, demod_consts, rf_to_iq


def test_tone_envelope_constant():
    cfg = tiny_config()
    t = np.arange(cfg.n_l) / cfg.fs
    rf = np.cos(2 * np.pi * cfg.f0 * t + 0.3)[:, None, None]
    rf = np.broadcast_to(rf, cfg.rf_shape).astype(np.float32)
    consts = jax.tree.map(jnp.asarray, demod_consts(cfg))
    iq = np.asarray(rf_to_iq(consts, jnp.asarray(rf), cfg.decim))
    assert iq.shape == (cfg.n_s, cfg.n_c, cfg.n_f, 2)
    env = np.sqrt(iq[..., 0] ** 2 + iq[..., 1] ** 2)
    # ignore filter edges
    mid = env[cfg.lpf_taps // cfg.decim + 2: -cfg.lpf_taps // cfg.decim - 2]
    assert np.all(np.abs(mid - 1.0) < 0.05), (mid.min(), mid.max())


def test_phase_tracks_offset():
    cfg = tiny_config()
    t = np.arange(cfg.n_l) / cfg.fs
    phi = 0.7
    rf = np.cos(2 * np.pi * cfg.f0 * t + phi)[:, None, None]
    rf = np.broadcast_to(rf, cfg.rf_shape).astype(np.float32)
    consts = jax.tree.map(jnp.asarray, demod_consts(cfg))
    iq = np.asarray(rf_to_iq(consts, jnp.asarray(rf), cfg.decim))
    mid = slice(10, cfg.n_s - 10)
    phase = np.arctan2(iq[mid, 0, 0, 1], iq[mid, 0, 0, 0])
    assert np.all(np.abs(phase - phi) < 0.05)


def test_int16_input_supported():
    cfg = tiny_config()
    rf = (np.random.default_rng(0).integers(
        -30000, 30000, cfg.rf_shape)).astype(np.int16)
    consts = jax.tree.map(jnp.asarray, demod_consts(cfg))
    iq = rf_to_iq(consts, jnp.asarray(rf), cfg.decim)
    assert iq.dtype == jnp.float32
    assert bool(jnp.isfinite(iq).all())


def _strided_fir_iq(consts, rf, decim):
    """The FIR as a stride-``decim`` slice per tap, ascending tap order."""
    n_l, n_c, n_f = rf.shape
    x = rf.astype(jnp.float32)
    mixed = x[..., None] * consts["carrier"][:, None, None, :]
    k = consts["lpf"].shape[0]
    m = jnp.pad(mixed, (_same_pad(n_l, k, decim), (0, 0), (0, 0), (0, 0)))
    n_s = -(-n_l // decim)
    acc = jnp.zeros((n_s, n_c, n_f, 2), jnp.float32)
    for t in range(k):
        acc = acc + consts["lpf"][t] * lax.slice_in_dim(
            m, t, t + (n_s - 1) * decim + 1, stride=decim, axis=0)
    return acc


@pytest.mark.parametrize("dtype", [np.float32, np.int16],
                         ids=["float32", "int16"])
@pytest.mark.parametrize("cfg", [
    tiny_config(),
    tiny_config(n_l=509),                   # decim does not divide n_l
    tiny_config(decim=2),
    tiny_config(n_l=511, decim=2),
    paper_config().with_(n_c=4, n_f=2),     # the paper's 1336 samples, 31 taps
], ids=["tiny", "ragged", "decim2", "decim2-ragged", "paper-axial"])
def test_polyphase_fir_bit_identical_to_strided(cfg, dtype):
    """The polyphase read sums the same products in the same order as a
    stride-``decim`` slice per tap, so the IQ is equal bit for bit."""
    rf = np.random.default_rng(cfg.n_l).integers(
        -30000, 30000, (2,) + cfg.rf_shape).astype(dtype)
    consts = jax.tree.map(jnp.asarray, demod_consts(cfg))

    def batched(fn):
        return jax.jit(jax.vmap(lambda c, x: fn(c, x, cfg.decim),
                                in_axes=(None, 0)))

    got = np.asarray(batched(rf_to_iq)(consts, jnp.asarray(rf)))
    want = np.asarray(batched(_strided_fir_iq)(consts, jnp.asarray(rf)))
    assert got.shape == (2, -(-cfg.n_l // cfg.decim), cfg.n_c, cfg.n_f, 2)
    assert np.array_equal(got, want)
