"""Pallas TPU kernels: page gather/scatter between frame pools and
contiguous transfer buffers.

This is the DMA *block-assembly* stage of the thesis' engine on TPU: the
R5 segments a transfer into blocks whose pages are scattered across the
physical pool; ``page_gather`` packs the pages named by a (scalar-prefetch)
page list into a contiguous staging buffer for the interconnect, and
``page_scatter`` is the receive-side inverse (packets land contiguously,
pages fan out to their frames).  One grid step = one page = one VMEM-sized
DMA, the translation again living in the index_map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(idx_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def page_gather(pool, indices, *, interpret: bool = False):
    """pool: (P, rows, lanes); indices: (n,) int32 -> (n, rows, lanes).

    A block is one whole page, so its last two dims are the pool's own and
    the TPU's (8, 128) tiling rule holds for any page shape.
    indices < 0 are "unmapped" (thesis: a fault the runtime must resolve
    first); they are clamped to frame 0 — callers mask, the kernel never
    traps, faults are a control-plane event.
    """
    P, R, E = pool.shape
    n = indices.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(
            (1, R, E), lambda i, idx: (jnp.maximum(idx[i], 0), 0, 0))],
        out_specs=pl.BlockSpec((1, R, E), lambda i, idx: (i, 0, 0)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, R, E), pool.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), pool)


def _scatter_kernel(idx_ref, blk_ref, pool_ref, out_ref):
    out_ref[...] = blk_ref[...]


def page_scatter(pool, indices, block, *, interpret: bool = False):
    """Scatter ``block`` (n, rows, lanes) into ``pool`` (P, rows, lanes).

    The pool is aliased to the output (in-place on TPU): pages not named by
    ``indices`` keep their contents.  Unmapped (-1) entries clamp to frame
    0 — callers must resolve residency first, as the serving engine does.
    """
    P, R, E = pool.shape
    n = indices.shape[0]

    def pool_map(i, idx):
        return (jnp.maximum(idx[i], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, R, E), lambda i, idx: (i, 0, 0)),  # block
                  pl.BlockSpec((1, R, E), pool_map)],           # pool (alias)
        out_specs=pl.BlockSpec((1, R, E), pool_map),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, R, E), pool.dtype),
        interpret=interpret,
        input_output_aliases={2: 0},
    )(indices.astype(jnp.int32), block, pool)
