"""jit'd wrappers for page gather/scatter over pools of any page shape."""

from __future__ import annotations

import functools
import math

import jax

from repro.kernels.page_pack.page_pack import page_gather, page_scatter

LANES = 128


def _as_pages(x):
    """View (n, ...) as (n, rows, lanes): lane-dense rows where the page
    size allows, else one row holding the whole page."""
    elems = math.prod(x.shape[1:])
    lanes = LANES if elems % LANES == 0 else elems
    return x.reshape(x.shape[0], elems // lanes, lanes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pages(pool, indices, *, interpret: bool = False):
    out = page_gather(_as_pages(pool), indices, interpret=interpret)
    return out.reshape((indices.shape[0],) + pool.shape[1:])


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_pages(pool, indices, block, *, interpret: bool = False):
    out = page_scatter(_as_pages(pool), indices, _as_pages(block),
                       interpret=interpret)
    return out.reshape(pool.shape)
