"""Pallas TPU kernel: causal flash attention with BLOCK-LEVEL causal skip.

This is the documented fix (EXPERIMENTS.md §Roofline) for the jnp chunked
attention's mask waste: the jnp path computes the full [q_chunk, S] score
rectangle and masks; this kernel's grid is (B*H, nq, nk) with
``pl.when(ki <= last_needed(qi))`` so strictly-above-diagonal key blocks
are never computed — ~2x fewer score FLOPs at long context, and the
online-softmax state lives in VMEM scratch across the innermost k loop.

Sliding-window (local) attention uses the same skip on BOTH sides of the
band, so a gemma3/llama4 local layer only touches window/k_block blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, q_block: int, k_block: int, window: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    q_start = qi * q_block
    k_start = ki * k_block
    # block-level causal band: this k block is needed iff it intersects
    # [q_start - window + 1, q_start + q_block - 1]
    needed = k_start <= q_start + q_block - 1
    if window:
        needed = jnp.logical_and(
            needed, k_start + k_block - 1 > q_start - window)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # [qc, D]
        k = k_ref[0].astype(jnp.float32)              # [kc, D]
        v = v_ref[0].astype(jnp.float32)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jnp.dot(q, k.T) * scale                   # [qc, kc]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = kpos <= qpos
        if window:
            keep = jnp.logical_and(keep, kpos > qpos - window)
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...]                           # [qc]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           q_block: int = 128, k_block: int = 128,
                           interpret=None):
    """q,k,v: [B, H, S, D] -> [B, H, S, D].  causal must be True (the
    decoder case); window>0 adds sliding-window banding.  interpret=None
    compiles on a TPU backend and interprets on any other."""
    interpret = resolve_interpret(interpret)
    assert causal, "kernel is causal-only (decoder attention)"
    b, h, s, d = q.shape
    q_block = min(q_block, s)
    k_block = min(k_block, s)
    assert s % q_block == 0 and s % k_block == 0
    nq, nk = s // q_block, s // k_block
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)

    grid = (b * h, nq, nk)
    kern = functools.partial(_kernel, q_block=q_block, k_block=k_block,
                             window=window, nk=nk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, k_block, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, k_block, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, d), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)
