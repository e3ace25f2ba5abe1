"""jit'd wrapper for the flash-attention kernel: layout plumbing
([B,S,H,D] model layout <-> [B,H,S,D] kernel layout), GQA expansion and
kernel/oracle dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.flash_attn import flash_attention_pallas
from repro.kernels.flash_attn.ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("window", "use_kernel",
                                             "interpret", "q_block",
                                             "k_block"))
def flash_attention(q, k, v, *, window: int = 0, use_kernel: bool = False,
                    interpret=None, q_block: int = 128,
                    k_block: int = 128):
    """q: [B, S, Hq, D]; k,v: [B, S, Hkv, D] (GQA-expanded internally).
    Causal (+ optional sliding window).  Returns [B, S, Hq, D]."""
    hq, hkv = q.shape[2], k.shape[2]
    if hq != hkv:
        g = hq // hkv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if use_kernel:
        out = flash_attention_pallas(qt, kt, vt, window=window,
                                     q_block=q_block, k_block=k_block,
                                     interpret=interpret)
    else:
        out = flash_attention_ref(qt, kt, vt, causal=True, window=window)
    return jnp.moveaxis(out, 1, 2)
