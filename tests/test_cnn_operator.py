"""The CNN operator's stored form: re and im halves, (n_c, n_s8, n_pix).

Both operator forms are checked against a plain numpy re-derivation of
the interleaved (n_c, n_pix, n_s, 2) operator they were built from
before: the dense halves must hold its values, transposed, with the
padded sample rows exactly zero, and the banded BSR blocks must be bit
for bit what blocking that operator gives.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import tiny_config
from repro.core.beamform import beamform_cnn, beamform_dynamic
from repro.core.delays import (bsr_operator, compute_delay_tables,
                               interp_operator)

# n_s = n_l // decim: a multiple of 8, then two that are not (the
# paper's 334 among them).
CONFIGS = {"ns128": dict(), "ns125": dict(n_l=500),
           "ns334": dict(n_l=1336)}


def _interleaved(cfg, t):
    """The operator as one (n_c, n_pix, n_s, 2) tensor, tap by tap."""
    M = np.zeros((cfg.n_c, cfg.n_pix, cfg.n_s, 2), dtype=np.float32)
    rows = np.arange(cfg.n_pix)
    for c in range(cfg.n_c):
        w = t.apod[:, c]
        re, im = t.rot[:, c, 0] * w, t.rot[:, c, 1] * w
        i0, f = t.idx[:, c], t.frac[:, c]
        np.add.at(M[c, :, :, 0], (rows, i0), re * (1.0 - f))
        np.add.at(M[c, :, :, 1], (rows, i0), im * (1.0 - f))
        np.add.at(M[c, :, :, 0], (rows, i0 + 1), re * f)
        np.add.at(M[c, :, :, 1], (rows, i0 + 1), im * f)
    return M


@pytest.fixture(params=list(CONFIGS), scope="module")
def case(request):
    cfg = tiny_config(**CONFIGS[request.param])
    t = compute_delay_tables(cfg)
    return cfg, t, _interleaved(cfg, t)


def test_cnn_halves_are_the_interleaved_operator_transposed(case):
    cfg, t, M = case
    re, im = interp_operator(cfg, t)
    n_s8 = -(-cfg.n_s // 8) * 8
    for half in (re, im):
        assert half.shape == (cfg.n_c, n_s8, cfg.n_pix)
        assert half.dtype == np.float32 and half.flags.c_contiguous
        assert not half[:, cfg.n_s:].any()
        assert not np.signbit(half[:, cfg.n_s:]).any()
    np.testing.assert_array_equal(re[:, :cfg.n_s], M[..., 0].transpose(0, 2, 1))
    np.testing.assert_array_equal(im[:, :cfg.n_s], M[..., 1].transpose(0, 2, 1))
    # two taps per (pixel, channel) row at most, nothing else
    assert (np.count_nonzero(re, axis=1) <= 2).all()


def test_bsr_blocks_bit_identical_to_interleaved_blocking(case):
    cfg, t, M = case
    bp, bs = cfg.sparse_block_p, cfg.sparse_block_s
    n_pb, n_sb = -(-cfg.n_pix // bp), -(-cfg.n_s // bs)
    dense_p = np.zeros((cfg.n_c, n_pb * bp, n_sb * bs, 2), np.float32)
    dense_p[:, :cfg.n_pix, :cfg.n_s] = M
    blk = dense_p.reshape(cfg.n_c, n_pb, bp, n_sb, bs, 2)
    occupied = np.abs(blk).sum(axis=(2, 4, 5)) > 0
    K = max(int(occupied.sum(axis=2).max()), 1)
    blocks = np.zeros((cfg.n_c, n_pb, K, bp, 2 * bs), np.float32)
    col_idx = np.zeros((cfg.n_c, n_pb, K), np.int32)
    for c in range(cfg.n_c):
        for i in range(n_pb):
            for k, sb in enumerate(np.nonzero(occupied[c, i])[0]):
                b = blk[c, i, :, sb]
                blocks[c, i, k] = np.concatenate([b[..., 0], b[..., 1]], 1)
                col_idx[c, i, k] = sb

    op = bsr_operator(cfg, t)
    assert op.blocks.dtype == blocks.dtype and op.blocks.shape == blocks.shape
    assert op.blocks.tobytes() == blocks.tobytes()
    assert op.col_idx.dtype == col_idx.dtype
    assert op.col_idx.tobytes() == col_idx.tobytes()


def test_cnn_beamform_matches_dynamic(case):
    cfg, t, _ = case
    re, im = interp_operator(cfg, t)
    rng = np.random.default_rng(5)
    iq = jnp.asarray(rng.standard_normal(
        (cfg.n_s, cfg.n_c, 3, 2)).astype(np.float32))
    cnn = beamform_cnn(cfg, {"interp_re": re, "interp_im": im}, iq)
    dyn = beamform_dynamic(cfg, {"idx": t.idx, "frac": t.frac,
                                 "apod": t.apod, "rot": t.rot}, iq)
    assert cnn.shape == (cfg.n_pix, 3, 2)
    np.testing.assert_allclose(np.asarray(cnn), np.asarray(dyn),
                               rtol=1e-5, atol=1e-5)
