"""Block-sparse (BSR) beamform Pallas kernel — the paper's V3 on TPU.

The paper could not run its sparse-matrix variant on TPU ("structured sparse
operators are not fully supported by the current TPU execution backend").
This kernel is the TPU-native adaptation: sparsity is expressed at MXU-tile
granularity (BSR blocks), block column indices are *scalar-prefetched* so
the Pallas pipeline can schedule the HBM->VMEM DMA of the right IQ block
before each grid step, and each step is one real
(bp x 2bs) @ (2bs x 2nf) MXU product accumulated into the output tile:

  y[i] = sum_c sum_k blocks[c, i, k] @ x[c, cols[c, i, k]]

Complex arithmetic is folded into the operand layout, with no size-2
minor axis anywhere:
  cols    (n_c * n_pb * K,)           int32, scalar-prefetched
  blocks  (n_c, n_pb, K, bp, 2bs)     f32  [B_re | B_im]
  x       (n_c, n_sb, 2bs, 2nf)       f32  [[x_re, x_im], [-x_im, x_re]]
  y       (n_pb, bp, 2nf)             f32  [y_re | y_im]

Grid: (n_pb, n_c, K) — pixel blocks "parallel"; channels and stored
blocks "arbitrary" (the output tile accumulates across both). The
channel axis lives in the grid, not in a vmap: a vmap over a
scalar-prefetch operand lowers to an explicit loop, which the executor's
batch vmap then turns into one copy of the operator per batch row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.das_beamform.kernel import COMPUTE_DTYPES, dot_precision
from repro.kernels.pallas_compat import CompilerParams, VMEM_LIMIT_BYTES


def _kernel(cols_ref, block_ref, x_ref, y_ref, *, precision):
    cdt = COMPUTE_DTYPES[precision]

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[0] += jnp.dot(block_ref[0, 0, 0].astype(cdt),
                        x_ref[0, 0].astype(cdt),
                        precision=dot_precision(precision),
                        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("precision", "interpret"))
def bsr_beamform_pallas(cols, blocks, x, *, precision: str = "f32",
                        interpret: bool = True):
    """y[i] = sum_c sum_k blocks[c, i, k] @ x[c, cols[c, i, k]].

    Args:
      cols:   (n_c, n_pb, K) int32 block-column indices.
      blocks: (n_c, n_pb, K, bp, 2bs) f32 [re | im] blocks.
      x:      (n_c, n_sb, 2bs, 2nf) f32 complex-expanded blocked IQ.
      precision: matmul-operand dtype, "f32" | "bf16" | "f16"
        (accumulation is always f32).
    Returns:
      (n_pb, bp, 2nf) f32 [y_re | y_im].
    """
    n_c, n_pb, K, bp, bs2 = blocks.shape
    nf2 = x.shape[-1]

    def x_map(i, c, k, cols):
        return (c, cols[(c * n_pb + i) * K + k], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pb, n_c, K),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bp, bs2),
                         lambda i, c, k, cols: (c, i, k, 0, 0)),
            pl.BlockSpec((1, 1, bs2, nf2), x_map),
        ],
        out_specs=pl.BlockSpec((1, bp, nf2),
                               lambda i, c, k, cols: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, precision=precision),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pb, bp, nf2), jnp.float32),
        interpret=interpret,
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(cols.reshape(-1), blocks, x)
