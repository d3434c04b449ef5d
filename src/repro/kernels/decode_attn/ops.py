"""Jit'd wrapper for GQA flash-decode (compiled on TPU, interpreted elsewhere).

`decode_attention(q, k, v, lengths)` matches ref.decode_attention_ref.
The serving engine calls this for decode steps when the KV cache is long
enough that the kernel's bandwidth savings matter; otherwise the jnp path
is used (one fused XLA op is faster for tiny caches).
"""

from __future__ import annotations

import jax.numpy as jnp

from .decode_attn import decode_attention_pallas
from .ref import decode_attention_ref

# below this cache length the jnp path wins (no VMEM pipeline setup)
MIN_KERNEL_SEQ = 512


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, block_s: int = 256,
                     force_kernel: bool = False) -> jnp.ndarray:
    if not force_kernel and k.shape[1] < MIN_KERNEL_SEQ:
        return decode_attention_ref(q, k, v, lengths)
    return decode_attention_pallas(q, k, v, lengths, block_s=block_s)
