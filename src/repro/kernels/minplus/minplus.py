"""Min-plus (tropical) DP transition kernel.

Computes, for every destination level j:

    out(j)    = min_i [ F(i) + T(i, j) ]
    arg(j)    = argmin_i [...]                       (first minimizer)
    T(i, j)   = af*(j-i)+ + df*(i-j)+ + ac*(ycc(j)-ycp(i))+ + dc*(ycp(i)-ycc(j))+

The transition matrix T is *generated in-registers* from index arithmetic
and two O(N) vectors — O(N^2) MXU/VPU work on O(N) HBM traffic, which is
the whole point of the kernel: the pure-jnp path materializes the (N, N)
matrix in memory every scan step.

Tiling: grid (j_blocks, i_blocks); j is parallel across the grid, i is the
innermost (arbitrary) dimension accumulated into the output block with the
standard revisit pattern. Blocks are (1, BLOCK) row vectors so the lane
dimension is 128-aligned for the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import use_interpret

BLOCK = 128
# Large *positive* sentinel: padded source levels must never win the min.
_PAD_HI = 3.0e38


def _kernel(params_ref, f_ref, ycp_ref, ycc_ref, out_ref, arg_ref, *,
            n_valid: int, block: int):
    i_blk = pl.program_id(1)
    af = params_ref[0, 0]
    df = params_ref[0, 1]
    ac = params_ref[0, 2]
    dc = params_ref[0, 3]

    f = f_ref[0, :]                       # (block,) source levels i
    ycp = ycp_ref[0, :]                   # (block,)
    ycc = ycc_ref[0, :]                   # (block,) destination levels j

    ii = (i_blk * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 0)).astype(jnp.float32)
    jj = (pl.program_id(0) * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 1)).astype(jnp.float32)
    relu = lambda x: jnp.maximum(x, 0.0)
    trans = (af * relu(jj - ii) + df * relu(ii - jj)
             + ac * relu(ycc[None, :] - ycp[:, None])
             + dc * relu(ycp[:, None] - ycc[None, :]))
    vals = f[:, None] + trans
    # mask padded source levels
    vals = jnp.where(ii < n_valid, vals, _PAD_HI)

    local_min = jnp.min(vals, axis=0)
    # first minimizer, spelled out: Mosaic's argmin does not promise the
    # lowest index on ties (levels are exact small integers in f32)
    local_arg = jnp.min(jnp.where(vals == local_min[None, :], ii, _PAD_HI),
                        axis=0).astype(jnp.int32)

    @pl.when(i_blk == 0)
    def _init():
        out_ref[0, :] = local_min
        arg_ref[0, :] = local_arg

    @pl.when(i_blk > 0)
    def _accum():
        cur = out_ref[0, :]
        better = local_min < cur              # strict: keep first minimizer
        out_ref[0, :] = jnp.where(better, local_min, cur)
        arg_ref[0, :] = jnp.where(better, local_arg, arg_ref[0, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def minplus_pallas(F: jnp.ndarray, yc_prev: jnp.ndarray, yc_cur: jnp.ndarray,
                   params: jnp.ndarray, interpret: bool | None = None):
    """F, yc_prev, yc_cur: (N,) float32; params: (4,) [af, df, ac, dc].
    ``interpret=None`` follows the platform (`repro.kernels.backend`)."""
    if interpret is None:
        interpret = use_interpret()
    n = F.shape[0]
    n_pad = ((n + BLOCK - 1) // BLOCK) * BLOCK
    pad = n_pad - n
    Fp = jnp.pad(F.astype(jnp.float32), (0, pad),
                 constant_values=_PAD_HI)[None, :]
    ycp = jnp.pad(yc_prev.astype(jnp.float32), (0, pad))[None, :]
    ycc = jnp.pad(yc_cur.astype(jnp.float32), (0, pad))[None, :]
    prm = params.astype(jnp.float32).reshape(1, 4)
    grid = (n_pad // BLOCK, n_pad // BLOCK)

    out, arg = pl.pallas_call(
        functools.partial(_kernel, n_valid=n, block=BLOCK),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 4), lambda j, i: (0, 0)),          # params
            pl.BlockSpec((1, BLOCK), lambda j, i: (0, i)),      # F (source)
            pl.BlockSpec((1, BLOCK), lambda j, i: (0, i)),      # yc_prev
            pl.BlockSpec((1, BLOCK), lambda j, i: (0, j)),      # yc_cur
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK), lambda j, i: (0, j)),
            pl.BlockSpec((1, BLOCK), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        ],
        interpret=interpret,
    )(prm, Fp, ycp, ycc)
    return out[0, :n], arg[0, :n]
