"""Pure-jnp oracle for the BSR beamform kernel."""

from __future__ import annotations

import jax.numpy as jnp


def bsr_beamform_ref(cols, blocks, iq_b):
    """y = sum_c sum_k blocks[c, :, k] @ iq_b[cols[c, :, k], :, c] (complex).

    cols (n_c, n_pb, K) i32; blocks (n_c, n_pb, K, bp, 2 * bs) [re | im];
    iq_b (n_sb, bs, n_c, nf, 2). Returns (n_pb * bp, nf, 2).
    """
    bs = blocks.shape[-1] // 2
    x = jnp.moveaxis(iq_b, 2, 0)                        # (c, sb, bs, nf, 2)
    g = jnp.take_along_axis(
        x[:, None], cols[:, :, :, None, None, None], axis=2)  # c,pb,K,bs,nf,2
    b_re, b_im = blocks[..., :bs], blocks[..., bs:]
    re = (jnp.einsum("cikps,ciksf->ipf", b_re, g[..., 0])
          - jnp.einsum("cikps,ciksf->ipf", b_im, g[..., 1]))
    im = (jnp.einsum("cikps,ciksf->ipf", b_re, g[..., 1])
          + jnp.einsum("cikps,ciksf->ipf", b_im, g[..., 0]))
    n_pb, bp = re.shape[:2]
    return jnp.stack([re, im], -1).reshape(n_pb * bp, -1, 2)
