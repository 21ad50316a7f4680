"""Prefill/decode vs teacher-forced forward: the serving path must produce
the same logits as the training path, token by token.

This is the strongest correctness test in the suite: it exercises KV-cache
updates (both paper variants), rope offsets, sliding windows + dual rope
bases (gemma3), MLA's absorbed-weight decode vs expanded prefill
(deepseek), SSD chunked-vs-recurrent equivalence (mamba2), and the shared
attention block caches (zamba2).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke
from repro.core.config import Variant
from repro.models import get_model
from repro.models.common import logits_from_hidden

ARCHS = ["qwen3_8b", "gemma3_1b", "deepseek_v2_236b", "mamba2_130m",
         "zamba2_1p2b", "granite_moe_3b_a800m", "qwen2_vl_2b"]


def _forward_logits(model, cfg, params, tokens):
    batch = {"tokens": tokens, "labels": tokens}
    h, _ = model.forward(params, batch)
    return np.asarray(logits_from_hidden(params["embed"], cfg, h))


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("kv_variant", [Variant.DYNAMIC, Variant.CNN])
def test_prefill_then_decode_matches_forward(arch, kv_variant, key, rng):
    cfg = get_smoke(arch).with_(kv_variant=kv_variant,
                                capacity_factor=8.0)
    model = get_model(cfg)
    params = model.init_params(key)

    B, S, extra = 2, 16, 4
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S + extra)).astype(np.int32))

    full = _forward_logits(model, cfg, params, tokens)   # (B, S+extra, V)

    # prefill on the first S tokens
    prompt = {"tokens": tokens[:, :S], "labels": tokens[:, :S]}
    logits_p, cache = jax.jit(model.prefill)(params, prompt)
    np.testing.assert_allclose(
        np.asarray(logits_p)[:, 0], full[:, S - 1], rtol=2e-3, atol=2e-3,
        err_msg="prefill last-position logits != forward")

    # grow cache and decode the remaining tokens one by one
    from repro.train.serving import _grow_cache
    cache = _grow_cache(model, cache, S + extra + 1)
    lengths = jnp.full((B,), S, jnp.int32)
    decode = jax.jit(model.decode_step)
    for t in range(extra):
        logits_d, cache = decode(params, tokens[:, S + t: S + t + 1],
                                 cache, lengths)
        np.testing.assert_allclose(
            np.asarray(logits_d)[:, 0], full[:, S + t],
            rtol=2e-3, atol=2e-3,
            err_msg=f"{arch}/{kv_variant} decode step {t} diverged")
        lengths = lengths + 1


def test_encdec_decode_matches_teacher_forced(key, rng):
    """seamless: greedy decode logits == teacher-forced decoder logits,
    given the same encoder output and token prefix."""
    import jax.numpy as jnp
    cfg = get_smoke("seamless_m4t_large_v2")
    model = get_model(cfg)
    params = model.init_params(key)

    B, S_enc, S_dec = 2, 12, 5
    enc = jnp.asarray(
        (0.02 * rng.standard_normal((B, S_enc, cfg.d_model))).astype(
            np.float32))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S_dec)).astype(
        np.int32))
    toks = toks.at[:, 0].set(0)  # BOS (prefill consumes token 0)

    # teacher-forced: full decoder pass
    batch = {"enc_embeds": enc, "tokens": toks, "labels": toks}
    h, _ = model.forward(params, batch)
    full = np.asarray(logits_from_hidden(params["embed"], cfg, h))

    # serving: prefill (encode + BOS) then decode steps. Unjitted:
    # dec_len is a python int in the batch dict (jit would trace it).
    logits_p, cache = model.prefill(
        params, {"enc_embeds": enc, "dec_len": 16})
    np.testing.assert_allclose(np.asarray(logits_p)[:, 0], full[:, 0],
                               rtol=2e-3, atol=2e-3)
    lengths = jnp.ones((B,), jnp.int32)
    decode = jax.jit(model.decode_step)
    for t in range(1, S_dec):
        logits_d, cache = decode(params, toks[:, t:t + 1], cache, lengths)
        np.testing.assert_allclose(
            np.asarray(logits_d)[:, 0], full[:, t], rtol=2e-3, atol=2e-3,
            err_msg=f"seamless decode step {t}")
        lengths = lengths + 1


def test_cache_update_variants_identical(rng):
    from repro.models.attention import cache_update
    b, s, h, d = 3, 12, 2, 4
    cache = rng.standard_normal((b, s, h, d)).astype(np.float32)
    new = rng.standard_normal((b, 1, h, d)).astype(np.float32)
    lengths = jnp.asarray(rng.integers(0, s, (b,)).astype(np.int32))
    a = cache_update(jnp.asarray(cache), jnp.asarray(new), lengths,
                     Variant.DYNAMIC)
    c = cache_update(jnp.asarray(cache), jnp.asarray(new), lengths,
                     Variant.CNN)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-7)
