"""Flash attention for TPU (Pallas): online-softmax over key blocks, GQA
native, causal and sliding-window masking.

Tiling (per grid step = one (batch, q-head, q-block)):
  q block   [bq, hd]     in VMEM  (bq=128 rows = MXU-aligned)
  k/v block [bk, hd]     streamed over the kv sequence inside a fori_loop
  acc       [bq, hd] f32 carried in registers/VMEM via the loop carry
VMEM footprint ~ (bq + 2*bk) * hd * 4B + acc — well under the 16 MB/core
budget at hd<=256.  head_dim is padded to a multiple of 128 lanes by ops.py.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
# f32 operands contract at f32: on a TPU the default is one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def _fa_kernel(qoff_ref, q_ref, k_ref, v_ref, o_ref, *, scale, causal,
               window, bq, bk, S, T):
    # refs (leading (1,1) block dims): q [1,1,bq,hd]; k/v [1,1,S,hd];
    # qoff [1] — absolute position of query row 0 (default S - T)
    iq = pl.program_id(2)
    hd = q_ref.shape[-1]
    q = q_ref[0, 0].astype(jnp.float32) * scale
    q_pos = (iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
             + qoff_ref[0])

    n_kb = S // bk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, precision=HIGHEST)  # [bq, bk]
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        ok = jnp.ones((bq, bk), bool)
        if causal:
            ok &= k_pos <= q_pos
        if window is not None:
            ok &= q_pos - k_pos < window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        m_new = jnp.maximum(m_new, -0.5 * jnp.float32(1e30))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v, precision=HIGHEST)
        return m_new, l_new, acc

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           bq=128, bk=128, interpret=True, q_offset=None):
    """q: [B,H,T,hd]; k,v: [B,KV,S,hd].  Returns [B,H,T,hd].

    ``q_offset`` is the absolute position of query row 0 within the S key
    positions; the default (``S - T``) keeps the original contract that
    queries are the last T of S (prefill: T == S).  Chunked prefill
    passes the segment start instead — which may be a traced value, so
    it enters the kernel as a scalar input, never a compile-time
    constant — letting a T-wide query slab attend causally against a
    cache that is still being filled."""
    B, H, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bq = min(bq, T)
    bk = min(bk, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    if q_offset is None:
        q_offset = S - T
    qoff = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (1,))

    kern = partial(_fa_kernel, scale=scale, causal=causal, window=window,
                   bq=bq, bk=bk, S=S, T=T)
    return pl.pallas_call(
        kern,
        grid=(B, H, T // bq),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, i: (0,)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h // g, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        interpret=interpret,
    )(qoff, q, k, v)
