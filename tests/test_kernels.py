"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode.

Each kernel's tolerance reflects f32 accumulation-order differences only.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import tiny_config
from repro.core.delays import bsr_operator, compute_delay_tables
from repro.kernels.das_beamform import das_beamform
from repro.kernels.das_beamform.ref import das_beamform_ref
from repro.kernels.bsr_spmm import bsr_beamform
from repro.kernels.bsr_spmm.ref import bsr_beamform_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref


# ---------------------------------------------------------------------------
# das_beamform
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_pix,n_c,n_s,n_f,bp", [
    (64, 4, 32, 2, 32),
    (96, 8, 64, 4, 32),     # n_pix % bp == 0, multiple blocks
    (100, 3, 40, 1, 32),    # ragged n_pix -> wrapper pads
])
def test_das_beamform_sweep(rng, n_pix, n_c, n_s, n_f, bp):
    idx = rng.integers(0, n_s - 1, (n_pix, n_c)).astype(np.int32)
    frac = rng.uniform(0, 1, (n_pix, n_c)).astype(np.float32)
    apod = rng.uniform(0, 1, (n_pix, n_c)).astype(np.float32)
    ph = rng.uniform(-np.pi, np.pi, (n_pix, n_c))
    rot = np.stack([np.cos(ph), np.sin(ph)], -1).astype(np.float32)
    iq = rng.standard_normal((n_s, n_c, n_f, 2)).astype(np.float32)

    args = tuple(jnp.asarray(a) for a in (idx, frac, apod, rot, iq))
    out = das_beamform(*args, bp=bp)
    ref = das_beamform_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_das_beamform_matches_pipeline_tables(rng):
    """Kernel on real geometry tables == the core dynamic beamformer."""
    cfg = tiny_config()
    t = compute_delay_tables(cfg)
    iq = rng.standard_normal((cfg.n_s, cfg.n_c, 2, 2)).astype(np.float32)
    out = das_beamform(jnp.asarray(t.idx), jnp.asarray(t.frac),
                       jnp.asarray(t.apod), jnp.asarray(t.rot),
                       jnp.asarray(iq), bp=64)
    ref = das_beamform_ref(jnp.asarray(t.idx), jnp.asarray(t.frac),
                           jnp.asarray(t.apod), jnp.asarray(t.rot),
                           jnp.asarray(iq))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# bsr_beamform
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_pb,K,bp,bs,n_sb,nf", [
    (4, 2, 16, 16, 6, 3),
    (8, 1, 8, 32, 4, 8),
    (3, 3, 32, 8, 9, 1),
])
def test_bsr_spmm_sweep(rng, n_pb, K, bp, bs, n_sb, nf):
    n_c = 3     # channels accumulate in the grid alongside stored blocks
    cols = rng.integers(0, n_sb, (n_c, n_pb, K)).astype(np.int32)
    blocks = rng.standard_normal((n_c, n_pb, K, bp, 2 * bs)).astype(
        np.float32)
    iq_b = rng.standard_normal((n_sb, bs, n_c, nf, 2)).astype(np.float32)
    args = tuple(jnp.asarray(a) for a in (cols, blocks, iq_b))
    np.testing.assert_allclose(np.asarray(bsr_beamform(*args)),
                               np.asarray(bsr_beamform_ref(*args)),
                               rtol=2e-4, atol=2e-4)


def test_bsr_beamform_matches_core_sparse(rng):
    """Pallas BSR beamform == repro.core sparse variant on real tables."""
    from repro.core.beamform import beamform_sparse
    cfg = tiny_config()
    t = compute_delay_tables(cfg)
    op = bsr_operator(cfg, t)
    n_f = 2
    iq = rng.standard_normal((cfg.n_s, cfg.n_c, n_f, 2)).astype(np.float32)

    n_sb = -(-cfg.n_s // op.bs)
    pad = n_sb * op.bs - cfg.n_s
    iq_b = np.pad(iq, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        n_sb, op.bs, cfg.n_c, n_f, 2)
    out = bsr_beamform(jnp.asarray(op.col_idx), jnp.asarray(op.blocks),
                       jnp.asarray(iq_b))[: cfg.n_pix]
    consts = {"bsr_blocks": jnp.asarray(op.blocks),
              "bsr_col_idx": jnp.asarray(op.col_idx)}
    ref = beamform_sparse(cfg, consts, jnp.asarray(iq))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,l,hq,hkv,d,causal", [
    (1, 64, 2, 2, 16, True),
    (2, 96, 4, 2, 32, True),     # GQA + ragged padding
    (1, 128, 4, 1, 64, True),    # MQA
    (2, 64, 2, 2, 16, False),
])
def test_flash_attention_sweep(rng, b, l, hq, hkv, d, causal):
    q = rng.standard_normal((b, l, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, l, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, l, hkv, d)).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, bq=32, bk=32)
    rep = hq // hkv
    kr, vr = np.repeat(k, rep, 2), np.repeat(v, rep, 2)
    ref = jax.vmap(lambda a, b_, c: attention_ref(
        a.transpose(1, 0, 2), b_.transpose(1, 0, 2), c.transpose(1, 0, 2),
        causal=causal).transpose(1, 0, 2))(
            jnp.asarray(q), jnp.asarray(kr), jnp.asarray(vr))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16_close(rng):
    b, l, h, d = 1, 64, 2, 32
    q = (rng.standard_normal((b, l, h, d))).astype(np.float32)
    k = (rng.standard_normal((b, l, h, d))).astype(np.float32)
    v = (rng.standard_normal((b, l, h, d))).astype(np.float32)
    out16 = flash_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), causal=True, bq=32, bk=32)
    out32 = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=True, bq=32, bk=32)
    assert out16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out16, dtype=np.float32),
                               np.asarray(out32), rtol=0.1, atol=0.05)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bsz,L,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 100, 3, 16, 8, 32),    # ragged -> wrapper pads
    (1, 32, 1, 64, 16, 32),
])
def test_ssd_scan_sweep(rng, bsz, L, H, P, N, chunk):
    log_a = -np.abs(rng.standard_normal((bsz, L, H))).astype(
        np.float32) * 0.2
    x = rng.standard_normal((bsz, L, H, P)).astype(np.float32)
    b = (rng.standard_normal((bsz, L, H, N)) * 0.3).astype(np.float32)
    c = (rng.standard_normal((bsz, L, H, N)) * 0.3).astype(np.float32)
    y = ssd_scan(jnp.asarray(log_a), jnp.asarray(x), jnp.asarray(b),
                 jnp.asarray(c), chunk=chunk)
    for bi in range(bsz):
        for h in range(H):
            ref = ssd_scan_ref(
                jnp.asarray(log_a[bi, :, h:h + 1]), jnp.asarray(x[bi, :, h]),
                jnp.asarray(b[bi, :, h]), jnp.asarray(c[bi, :, h]))
            np.testing.assert_allclose(
                np.asarray(y[bi, :, h]), np.asarray(ref),
                rtol=2e-4, atol=2e-4)


def test_ssd_kernel_matches_model_path(rng):
    """Pallas SSD == the model's chunked XLA implementation."""
    from repro.models.ssm import _ssd_chunked
    bsz, L, H, P, N = 2, 64, 2, 16, 8
    log_a = -np.abs(rng.standard_normal((bsz, L, H))).astype(
        np.float32) * 0.1
    x = rng.standard_normal((bsz, L, H, P)).astype(np.float32)
    bmat = (rng.standard_normal((bsz, L, N)) * 0.3).astype(np.float32)
    cmat = (rng.standard_normal((bsz, L, N)) * 0.3).astype(np.float32)

    y_model, _ = _ssd_chunked(jnp.asarray(log_a), jnp.asarray(x),
                              jnp.asarray(bmat), jnp.asarray(cmat), 16)
    bh = np.broadcast_to(bmat[:, :, None, :], (bsz, L, H, N))
    ch = np.broadcast_to(cmat[:, :, None, :], (bsz, L, H, N))
    y_kern = ssd_scan(jnp.asarray(log_a), jnp.asarray(x), jnp.asarray(bh),
                      jnp.asarray(ch), chunk=16)
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_model),
                               rtol=2e-4, atol=2e-4)
