"""Alg.-2 expected-objective kernel.

For every candidate allocation c against the conditional histogram p(b):

    J(c) = amort(c) + sum_b p(b) [ co_min*min(c,b) + co_over*(c-b)+
                                   + co_under*(b-c)+ ]

with candidates outside the observed bin range [lo, hi] masked to +inf
(they are dominated; see core.predictor). This is the per-interval hot
loop of the Spork simulator: the sweep engine calls it once per
(scheduling interval x app x sweep point).

Tiling: grid (cand_blocks, bin_blocks); candidates parallel, bins
accumulated. The (c, b) interaction tile is generated from index
arithmetic; the only HBM traffic is the two O(N) vectors. The inner
contraction `per @ p` runs on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import use_interpret

BLOCK = 128
_INF = 3.0e38


def _kernel(params_ref, hist_ref, amort_ref, out_ref, *, block: int):
    c_blk = pl.program_id(0)
    b_blk = pl.program_id(1)
    nb = pl.num_programs(1)
    co_min = params_ref[0, 0]
    co_over = params_ref[0, 1]
    co_under = params_ref[0, 2]
    total = params_ref[0, 3]
    lo = params_ref[0, 4]
    hi = params_ref[0, 5]

    p = hist_ref[0, :] / jnp.maximum(total, 1.0)        # (block,) bin probs
    cc = (c_blk * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 0)).astype(jnp.float32)
    bb = (b_blk * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 1)).astype(jnp.float32)
    relu = lambda x: jnp.maximum(x, 0.0)
    per = (co_min * jnp.minimum(cc, bb) + co_over * relu(cc - bb)
           + co_under * relu(bb - cc))                  # (c, b)
    partial = jax.lax.dot_general(
        per, p[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)[:, 0]       # (block,)

    @pl.when(b_blk == 0)
    def _init():
        out_ref[0, :] = partial

    @pl.when(b_blk > 0)
    def _accum():
        out_ref[0, :] = out_ref[0, :] + partial

    @pl.when(b_blk == nb - 1)
    def _finalize():
        cand = (c_blk * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1)).astype(jnp.float32)[0, :]
        j = out_ref[0, :] + amort_ref[0, :]
        mask = (cand >= lo) & (cand <= hi)
        out_ref[0, :] = jnp.where(mask, j, _INF)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spork_predict_pallas(hist: jnp.ndarray, amort: jnp.ndarray,
                         params: jnp.ndarray, interpret: bool | None = None):
    """hist, amort: (N,) float32; params: (6,) [co_min, co_over, co_under,
    total, lo, hi]. Returns J: (N,) float32 (masked entries ~ +inf).
    ``interpret=None`` follows the platform (`repro.kernels.backend`)."""
    if interpret is None:
        interpret = use_interpret()
    n = hist.shape[0]
    n_pad = ((n + BLOCK - 1) // BLOCK) * BLOCK
    pad = n_pad - n
    histp = jnp.pad(hist.astype(jnp.float32), (0, pad))[None, :]
    amortp = jnp.pad(amort.astype(jnp.float32), (0, pad))[None, :]
    prm = params.astype(jnp.float32).reshape(1, 6)
    grid = (n_pad // BLOCK, n_pad // BLOCK)

    out = pl.pallas_call(
        functools.partial(_kernel, block=BLOCK),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 6), lambda c, b: (0, 0)),
            pl.BlockSpec((1, BLOCK), lambda c, b: (0, b)),   # hist bins
            pl.BlockSpec((1, BLOCK), lambda c, b: (0, c)),   # amort(c)
        ],
        out_specs=pl.BlockSpec((1, BLOCK), lambda c, b: (0, c)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
    )(prm, histp, amortp)
    return out[0, :n]
