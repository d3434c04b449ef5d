"""GQA flash-decode attention kernel (one new token vs. a long KV cache).

TPU adaptation of flash-decoding: instead of GPU-style warp splits, the
cache sequence axis is tiled into VMEM-resident blocks and reduced with an
online softmax; the grouped queries of one KV head are packed into the
sublane dimension so the (G, D) x (D, S_blk) score matmul runs on the MXU.

Grid: (batch, kv_heads, seq_blocks); batch/head parallel, sequence
innermost (arbitrary) carrying the running max / normalizer / accumulator
in VMEM scratch. Per-sequence lengths mask the tail block and support
ragged batches.

Memory: decode attention is bandwidth-bound (every KV byte is touched once
per token). The roofline win vs the jnp path is avoiding the materialized
(B, Hq, S) score tensor: HBM traffic drops from ~2*S*Hkv*D + S*Hq floats
to the KV read ~2*S*Hkv*D — a (1 + G/(2D))x reduction, and VMEM tiling
keeps the working set on-chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import use_interpret

_NEG = -1.0e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_s: int, scale: float):
    s_blk = pl.program_id(2)
    ns = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]                    # scalar (SMEM)

    @pl.when(s_blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    lead = (0,) * (k_ref.ndim - 2)                        # block's unit dims
    q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, D)
    k = k_ref[lead].astype(jnp.float32)                   # (block_s, D)
    v = v_ref[lead].astype(jnp.float32)

    scores = jax.lax.dot_general(                          # (G, block_s)
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    pos = (s_blk * block_s
           + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1))
    scores = jnp.where(pos < length, scores, _NEG)

    m_prev = m_ref[:, 0:1]                                 # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                        # (G, 1)
    p = jnp.exp(scores - m_new)                            # (G, block_s)
    p = jnp.where(pos < length, p, 0.0)
    l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(                              # (G, D)
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s_blk == ns - 1)
    def _done():
        denom = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            lengths: jnp.ndarray, block_s: int = 256,
                            interpret: bool | None = None) -> jnp.ndarray:
    """q: (B, Hq, D); k/v: (B, S, Hkv, D); lengths: (B,). See ref.py.
    ``interpret=None`` follows the platform (`repro.kernels.backend`)."""
    if interpret is None:
        interpret = use_interpret()
    b, hq, d = q.shape
    _, s, hkv, _ = k.shape
    assert hq % hkv == 0, "grouped-query heads must divide evenly"
    g = hq // hkv
    block_s = min(block_s, max(s, 1))
    s_pad = ((s + block_s - 1) // block_s) * block_s
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    # Mosaic tiles a block's last two dims, so each must be a whole
    # array dim or aligned. With D a multiple of 128 the cache is viewed
    # as (B, S, Hkv*D) -- a free reshape -- and head h is the block of
    # columns [h*D, (h+1)*D). Otherwise the cache is copied head-major
    # (B, Hkv, S, D) on every call.
    qg = q.reshape(b, hkv, g, d)
    if d % 128 == 0:
        kt = k.reshape(b, s_pad, hkv * d)
        vt = v.reshape(b, s_pad, hkv * d)
        kv_spec = pl.BlockSpec((1, block_s, d),
                               lambda bi, hi, si, ln: (bi, si, hi))
    else:
        kt = k.transpose(0, 2, 1, 3)                       # (B, Hkv, S, D)
        vt = v.transpose(0, 2, 1, 3)
        kv_spec = pl.BlockSpec((1, 1, block_s, d),
                               lambda bi, hi, si, ln: (bi, hi, si, 0))
    lens = lengths.astype(jnp.int32).reshape(b)
    grid = (b, hkv, s_pad // block_s)

    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                         # lengths -> SMEM
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d),
                             lambda bi, hi, si, ln: (bi, hi, 0, 0)),   # q
                kv_spec,                                             # k
                kv_spec,                                             # v
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda bi, hi, si, ln: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),     # acc
                pltpu.VMEM((g, 128), jnp.float32),   # running max (bcast)
                pltpu.VMEM((g, 128), jnp.float32),   # running normalizer
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(lens, qg, kt, vt)
    return out.reshape(b, hq, d)
