"""Decode attention (Pallas): one query token per sequence against a
ring-buffered KV cache, GQA native.

Grid: (B, H).  Per step the kernel streams the ring cache in bk-key blocks
(fori_loop), masking by the absolute position each ring slot holds
(slot i holds pos-1 - ((pos-1 - i) mod S); negative = never written).
VMEM: q row [1, hd] + k/v blocks [bk, hd] + f32 accumulators.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# f32 operands contract at f32: on a TPU the default is one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def _dec_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, scale, window,
                bk, S):
    hd = q_ref.shape[-1]
    # this row's tokens written (current abs pos = pos-1); ``pos`` rides
    # scalar prefetch (SMEM), since a rank-1 block of 1 is refused by Mosaic
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0, 0].astype(jnp.float32) * scale  # [1, hd]
    q_pos = pos - 1

    n_kb = S // bk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        slot = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        last = pos - 1
        k_pos = last - jnp.mod(last - slot, S)  # ring absolute positions
        ok = (k_pos >= 0) & (k_pos <= q_pos)
        if window is not None:
            ok &= q_pos - k_pos < window
        s = jnp.dot(q, k.T, precision=HIGHEST)  # [1, bk]
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        m_new = jnp.maximum(m_new, -0.5 * jnp.float32(1e30))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v, precision=HIGHEST)
        return m_new, l_new, acc

    m0 = jnp.full((1, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, 1), jnp.float32)
    a0 = jnp.zeros((1, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, pos, *, window=None,
                            scale=None, bk=128, interpret=True):
    """q: [B,H,1,hd]; caches [B,KV,S,hd]; pos: scalar int32 or i32[B]
    (tokens written per row, current token included).  Returns [B,H,1,hd].

    A scalar ``pos`` broadcasts to every row (the single-stream decode
    loop); a per-batch vector is the paged multi-slot path, where each
    resident sequence sits at its own absolute position."""
    B, H, _, hd = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bk = min(bk, S)
    assert S % bk == 0

    kern = partial(_dec_kernel, scale=scale, window=window, bk=bk, S=S)
    pos_arr = jnp.asarray(pos, jnp.int32)
    if pos_arr.ndim == 0:
        pos_arr = jnp.broadcast_to(pos_arr, (B,))
    assert pos_arr.shape == (B,), pos_arr.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, pos: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, pos: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, pos: (b, h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda b, h, pos: (b, h, 0, 0)),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        interpret=interpret,
    )(pos_arr, q, k_cache, v_cache)
