"""Language-model serving: slot-based batching over the LM zoo.

B fixed slots, each request prefills into its slot, then all slots
decode in lockstep (static shapes — one compiled program for the whole
serving session, the paper's §II-E execution model). Works on CPU with
smoke configs; the production mesh shards slots over data and
heads/experts over model exactly like the dry-run decode cells.

  PYTHONPATH=src python -m repro.train.serving --arch qwen3-8b --smoke \
      --requests 16 --batch 4 --prompt-len 32 --max-new 32
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke
from repro.data.batches import synth_train_batch
from repro.models import get_model
from repro.train import steps as steps_lib


def serve_session(cfg, *, requests: int, batch: int, prompt_len: int,
                  max_new: int, seed: int = 0):
    """Process `requests` prompts in slot batches of `batch`.

    Returns (generated tokens array, stats dict).
    """
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    prefill_step = jax.jit(steps_lib.make_prefill_step(model))
    serve_step = jax.jit(steps_lib.make_serve_step(model))

    outs = []
    n_steps = 0
    # perf_counter, like every other timing window in this module: an
    # NTP step mid-session would skew (or negate) a time.time() wall.
    t0 = time.perf_counter()
    max_len = prompt_len + max_new + 1
    for r0 in range(0, requests, batch):
        bsz = min(batch, requests - r0)
        prompt = synth_train_batch(cfg, bsz, prompt_len, seed=seed + r0)
        tok_next, cache = prefill_step(params, prompt)
        tok = tok_next[:, None]
        if cfg.family == "audio":
            # enc-dec prefill returns a decode-ready cache (BOS consumed)
            lengths = jnp.ones((bsz,), jnp.int32)
        else:
            # decoder-only: extend the prefilled cache to serving length
            cache = _grow_cache(model, cache, max_len)
            lengths = jnp.full((bsz,), prompt_len, jnp.int32)

        gen = [np.asarray(tok)]
        for _ in range(max_new):
            tok, cache, lengths = serve_step(params, tok, cache, lengths)
            gen.append(np.asarray(tok))
            n_steps += 1
        outs.append(np.concatenate(gen, axis=1))

    wall = time.perf_counter() - t0
    toks = sum(o.size for o in outs)
    stats = {"wall_s": wall, "tokens": toks,
             "tok_per_s": toks / max(wall, 1e-9),
             "decode_steps": n_steps}
    return np.concatenate(outs, axis=0)[:requests], stats


def _grow_cache(model, cache, max_len: int):
    """Pad every leaf's sequence axis to max_len.

    The sequence axis is identified structurally via cache_specs
    (seq_sharded=True labels it "seq"); leaves without one (SSM/conv
    states) pass through untouched.
    """
    specs = model.cache_specs(seq_sharded=True)

    def grow(ax, a):
        if "seq" not in ax:
            return a
        i = ax.index("seq")
        if a.shape[i] >= max_len:
            return a
        pad = [(0, 0)] * a.ndim
        pad[i] = (0, max_len - a.shape[i])
        return jnp.pad(a, pad)

    return jax.tree.map(
        grow, specs, cache,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, (str, tuple)) for a in x))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    out, stats = serve_session(
        cfg, requests=args.requests, batch=args.batch,
        prompt_len=args.prompt_len, max_new=args.max_new)
    print(f"served {args.requests} requests: {stats['tokens']} tokens in "
          f"{stats['wall_s']:.2f}s = {stats['tok_per_s']:,.0f} tok/s")


if __name__ == "__main__":
    main()
