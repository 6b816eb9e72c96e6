"""Real-attention LM serving path (DESIGN.md §13): paged KV cache +
batched Pallas attention kernels on the region fabric.

This is the second LM backend behind the serving engine (``--lm
attention``).  Where the surrogate LM threads an integer hidden state,
this backend runs an actual transformer-style decode step — embedding +
positional lookup, QKV projections, GQA attention over a **paged KV
cache**, output projection, greedy readout — as two region bitstreams:

- ``AttnPrefill``: batched/packed prefill.  Up to ``prefill_batch``
  sequences share one task; the prompt is folded segment-by-segment
  (one ``block_size``-wide segment per budget unit) through
  ``kernels/flash_attention`` with a *traced* ``q_offset``, writing the
  per-row K/V cache as it goes and emitting each row's first token.
- ``AttnDecode``: batched multi-slot decode.  One kernel call advances
  every active slot one token per step against its own block table via
  ``kernels/decode_attention.paged_decode_attention`` — the pools and
  the slot table ride the task's ArgBundle, so mid-round preemption,
  same-region resume, cross-region materialize, and cross-shell
  migration move the KV pages through the exact commit/spill/CRC
  machinery every other payload uses.

KV pages live in two ``[NB, block_size, kv_heads, head_dim]`` device
pools threaded round-to-round (``device_result=True``); the host-side
page accounting is ``core.context.KVBlockPool``.  Block 0 is the
reserved null page: tables are padded with it and inactive rows scatter
zeros there, so page bytes are deterministic under any batch
composition, chunk partition, or resume schedule.

Determinism contract (what the bit-identity tests lean on): every
buffer shape is fixed by config — prefill rows are always padded to
``prefill_batch`` x ``max_ctx``, decode always covers ``max_slots``
rows against the full pool — so a sequence's per-row computation runs
through the same compiled program regardless of who shares the batch;
rows are independent (row-wise matmuls, per-(row, head) Pallas grid
cells, per-row gather/scatter), so ``attention_oracle_stream`` can
replay one sequence alone through the same kernels and demand token
equality.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.controller.kernels import _REGISTRY, ctrl_kernel, get_kernel
from repro.core.context import ContextRecord, KVBlockPool
from repro.core.preemption import for_save, make_pipelined_chunk
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.serving.kernels import (COL_ACTIVE, COL_LAST_TOK, COL_N_EMIT,
                                   SLOT_POS)

# slot-table layout (AttnDecode bufs[3], i32[S, TABLE_META + blocks/seq]):
# the surrogate's three columns, plus the per-slot write position, then
# the block table itself — page ids in position order, 0-padded (null)
COL_SEQ_LEN = 3
TABLE_META = 4

PREFILL_OUT_W = 8   # first token lands in out[row, 0]
META_W = 8          # AttnPrefill per-row metadata width (col 0 = prompt_len)


@dataclass(frozen=True)
class AttentionParams:
    """Model + paging geometry.  Frozen and hashable: the weight builder
    and kernel registry key off the whole record."""
    d_model: int = 64
    vocab: int = 101
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    block_size: int = 8      # KV page size, in token positions
    max_ctx: int = 64        # prompt + generated positions per sequence
    seed: int = 7            # weight init seed

    def __post_init__(self):
        if self.n_heads % self.kv_heads:
            raise ValueError(f"n_heads={self.n_heads} must be a multiple "
                             f"of kv_heads={self.kv_heads}")
        if self.max_ctx % self.block_size:
            raise ValueError(f"max_ctx={self.max_ctx} must be a multiple "
                             f"of block_size={self.block_size}")
        if self.max_ctx > 128 and self.max_ctx % 128:
            # both attention kernels stream keys in min(128, S) tiles
            raise ValueError(f"max_ctx={self.max_ctx} > 128 must be a "
                             f"multiple of 128")
        for name in ("d_model", "vocab", "n_heads", "kv_heads", "head_dim",
                     "block_size", "max_ctx"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1")

    @property
    def blocks_per_seq(self) -> int:
        return self.max_ctx // self.block_size

    @property
    def table_width(self) -> int:
        return TABLE_META + self.blocks_per_seq


# The attention widths of Mistral 7B (mistralai/Mistral-7B-v0.1
# config.json: hidden 4096, 32 query heads, 8 KV heads, head_dim 128,
# vocab 32000), cut to one layer and a 2048-position context (the model's
# is 8192 with a 4096 sliding window), with 16-position KV pages.  The
# small defaults above are for the CPU tests, where Pallas interprets.
MISTRAL_7B = AttentionParams(d_model=4096, vocab=32000, n_heads=32,
                             kv_heads=8, head_dim=128, block_size=16,
                             max_ctx=2048)


# -- weights -------------------------------------------------------------
# One flat f32[rows, d_model] buffer shared by both kernels and the
# oracle.  Everything is stored as rows of width d_model so the kernel
# can split it with static slices derived from the (closed-over) params:
#   [E | pos_emb | Wq^T | Wk^T | Wv^T | Wo]

def _row_offsets(p: AttentionParams) -> Tuple[int, ...]:
    q = p.n_heads * p.head_dim
    kv = p.kv_heads * p.head_dim
    e0 = 0
    pe0 = p.vocab
    q0 = pe0 + p.max_ctx
    k0 = q0 + q
    v0 = k0 + kv
    o0 = v0 + kv
    return e0, pe0, q0, k0, v0, o0, o0 + q


@functools.lru_cache(maxsize=8)
def build_weights(p: AttentionParams) -> np.ndarray:
    """Deterministic seeded weights, f32[rows, d_model].  Cached per
    params — callers must treat the array as read-only."""
    e0, pe0, q0, k0, v0, o0, rows = _row_offsets(p)
    rng = np.random.default_rng(p.seed)
    w = rng.standard_normal((rows, p.d_model)).astype(np.float32)
    w[pe0:q0] *= 0.5                       # positional table, kept small
    w[q0:] *= 1.0 / np.sqrt(p.d_model)     # projections
    w.setflags(write=False)
    return w


@functools.lru_cache(maxsize=2)
def device_weights(p: AttentionParams) -> jax.Array:
    """``build_weights(p)`` uploaded once: every prefill/decode bundle
    and the oracle share it, so a task's launch copies it on the device
    instead of uploading it from the host again."""
    return jnp.asarray(build_weights(p))


def _mm(a, b):
    # f32 contraction at f32 (on a TPU the default is one bf16 pass)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _split(w, p: AttentionParams):
    """(E, pos_emb, WqT, WkT, WvT, Wo) static views of the flat buffer."""
    e0, pe0, q0, k0, v0, o0, rows = _row_offsets(p)
    return w[e0:pe0], w[pe0:q0], w[q0:k0], w[k0:v0], w[v0:o0], w[o0:rows]


# -- kernel bodies -------------------------------------------------------

def _make_prefill_fn(p: AttentionParams):
    H, KV, hd, C = p.n_heads, p.kv_heads, p.head_dim, p.block_size

    def attn_prefill(ctx: ContextRecord, bufs, ints, floats):
        """Fold each row's prompt one C-wide segment per budget unit.
        bufs: (out i32[PB, 8], k_new f32[PB, P, KV, hd], v_new ditto,
        prompt i32[PB, P], meta i32[PB, 8] with prompt_len in col 0,
        weights f32[rows, D]).  P == max_ctx always, so every prefill
        shares one bitstream and one numeric schedule."""
        out, k_new, v_new, prompt, meta, weights = bufs[:6]
        PB, P = prompt.shape
        n_seg = P // C
        plen = meta[:, 0]
        E, pe, wq, wk, wv, wo = _split(weights, p)

        def body_c(ctx, c, st):
            out, k_new, v_new = st
            start = c * C
            toks = jax.lax.dynamic_slice_in_dim(prompt, start, C, axis=1)
            pos = start + jnp.arange(C, dtype=jnp.int32)
            valid = pos[None, :] < plen[:, None]
            x = E[toks] + pe[pos][None, :, :]
            x = jnp.where(valid[..., None], x, 0.0)       # [PB, C, D]
            q = _mm(x, wq.T).reshape(PB, C, H, hd).transpose(0, 2, 1, 3)
            k = _mm(x, wk.T).reshape(PB, C, KV, hd)
            v = _mm(x, wv.T).reshape(PB, C, KV, hd)
            k_new = jax.lax.dynamic_update_slice_in_dim(k_new, k, start,
                                                        axis=1)
            v_new = jax.lax.dynamic_update_slice_in_dim(v_new, v, start,
                                                        axis=1)
            # causal flash over the cache filled so far: positions past
            # ``start + C`` are still zero, but causal masking from the
            # traced q_offset keeps them out of every valid query row
            o = flash_attention(q, k_new.transpose(0, 2, 1, 3),
                                v_new.transpose(0, 2, 1, 3),
                                causal=True, bq=C, q_offset=start)
            # no residual into the readout: y = x + o@wo would make
            # y @ E.T self-dominated (E[tok]·E[tok] ~ D) and greedy
            # decoding would just re-emit the last token forever
            o = o.transpose(0, 2, 1, 3).reshape(PB, C, H * hd)
            logits = _mm(_mm(o, wo), E.T)                 # [PB, C, vocab]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # a row emits its first token at prompt position plen-1
            emit = jnp.logical_and(valid, pos[None, :] == plen[:, None] - 1)
            hit = jnp.any(emit, axis=1)
            picked = jnp.sum(jnp.where(emit, nxt, 0), axis=1)
            out = out.at[:, 0].set(jnp.where(hit, picked, out[:, 0]))
            ctx = ctx.checkpoint(SLOT_POS, c + 1)
            return ctx, (out, k_new, v_new)

        ctx, (out, k_new, v_new) = for_save(ctx, SLOT_POS, 0, n_seg, 1,
                                            body_c, (out, k_new, v_new))
        finished = ctx.intr == 0
        done_ctx = ctx.finish()
        ctx = jax.tree.map(lambda a, b: jnp.where(finished, a, b),
                           done_ctx, ctx)
        return ctx, (out, k_new, v_new, prompt, meta, weights)

    return attn_prefill


def _make_decode_fn(p: AttentionParams):
    H, KV, hd, BS = p.n_heads, p.kv_heads, p.head_dim, p.block_size
    T_blk = p.blocks_per_seq

    def attn_decode(ctx: ContextRecord, bufs, ints, floats):
        """One decode round: every active slot advances one token per
        step, R steps, against its block table.  bufs: (out i32[S, R],
        k_pool f32[NB, BS, KV, hd], v_pool ditto, table
        i32[S, TABLE_META + T_blk], weights f32[rows, D])."""
        out, k_pool, v_pool, table, weights = bufs[:5]
        S, R = out.shape
        E, pe, wq, wk, wv, wo = _split(weights, p)

        def body_t(ctx, t, st):
            out, k_pool, v_pool, table = st
            live = jnp.logical_and(table[:, COL_ACTIVE] == 1,
                                   t < table[:, COL_N_EMIT])
            pos = table[:, COL_SEQ_LEN]
            posc = jnp.clip(pos, 0, p.max_ctx - 1)
            x = E[table[:, COL_LAST_TOK]] + pe[posc]
            x = jnp.where(live[:, None], x, 0.0)          # [S, D]
            q = _mm(x, wq.T).reshape(S, H, 1, hd)
            k = _mm(x, wk.T).reshape(S, KV, hd)
            v = _mm(x, wv.T).reshape(S, KV, hd)
            # scatter this step's K/V into each row's current page; dead
            # rows write zeros to the null page (same-value duplicates,
            # so scatter order can never matter)
            col = TABLE_META + posc // BS
            blk = jnp.take_along_axis(table, col[:, None], axis=1)[:, 0]
            bid = jnp.where(live, blk, 0)
            off = jnp.where(live, posc % BS, 0)
            k_pool = k_pool.at[bid, off].set(
                jnp.where(live[:, None, None], k, 0.0))
            v_pool = v_pool.at[bid, off].set(
                jnp.where(live[:, None, None], v, 0.0))
            tbl = table[:, TABLE_META:TABLE_META + T_blk]
            o = paged_decode_attention(q, k_pool, v_pool, tbl,
                                       jnp.where(live, posc + 1, 0))
            # readout without the residual (same rationale as prefill)
            logits = _mm(_mm(o.reshape(S, H * hd), wo), E.T)  # [S, vocab]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = out.at[:, t].set(jnp.where(live, nxt, out[:, t]))
            table = table.at[:, COL_LAST_TOK].set(
                jnp.where(live, nxt, table[:, COL_LAST_TOK]))
            table = table.at[:, COL_SEQ_LEN].set(
                jnp.where(live, pos + 1, pos))
            ctx = ctx.checkpoint(SLOT_POS, t + 1)
            return ctx, (out, k_pool, v_pool, table)

        ctx, (out, k_pool, v_pool, table) = for_save(
            ctx, SLOT_POS, 0, R, 1, body_t, (out, k_pool, v_pool, table))
        finished = ctx.intr == 0
        done_ctx = ctx.finish()
        ctx = jax.tree.map(lambda a, b: jnp.where(finished, a, b),
                           done_ctx, ctx)
        return ctx, (out, k_pool, v_pool, table, weights) + tuple(bufs[5:])

    return attn_decode


def _params_tag(p: AttentionParams) -> str:
    if p == AttentionParams():
        return ""
    return (f"@d{p.d_model}v{p.vocab}h{p.n_heads}kv{p.kv_heads}"
            f"hd{p.head_dim}b{p.block_size}c{p.max_ctx}s{p.seed}")


def register_attention_kernels(
        p: Optional[AttentionParams] = None) -> Tuple[str, str]:
    """Register (idempotently) the prefill/decode bitstreams for ``p``
    and return their kernel names.  The default params own the bare
    ``AttnPrefill``/``AttnDecode`` names; other geometries get a
    params-suffixed pair (distinct name = distinct bitstream cache key,
    exactly like any other kernel)."""
    p = p or AttentionParams()
    tag = _params_tag(p)
    names = (f"AttnPrefill{tag}", f"AttnDecode{tag}")
    if names[0] not in _REGISTRY:
        ctrl_kernel(names[0], backend="PYNQ",
                    ktile_args=("out", "k_new", "v_new", "prompt", "meta",
                                "weights"),
                    int_args=("PB", "P", "vocab"),
                    default_budget=4, device_result=True,
                    pallas=True)(_make_prefill_fn(p))
        ctrl_kernel(names[1], backend="PYNQ",
                    ktile_args=("out", "k_pool", "v_pool", "table",
                                "weights"),
                    int_args=("S", "R", "vocab"),
                    default_budget=4, device_result=True,
                    pallas=True)(_make_decode_fn(p))
    return names


# the default geometry registers at import time, exactly like the
# surrogate kernels (controller.kernels._register_builtin imports us)
register_attention_kernels()


# -- serving backend -----------------------------------------------------

class AttentionLM:
    """The engine-facing LM backend for ``ServingConfig(lm="attention")``.

    Owns the paged-KV machinery: the ``KVBlockPool`` accounting, the
    device-resident K/V pools threaded round-to-round, the per-sequence
    write positions, and the construction of prefill/decode ArgBundles.
    The ``ServingEngine`` stays LM-agnostic — it asks for bundles, runs
    them as tasks, and hands the result buffers back.
    """

    name = "attention"

    def __init__(self, cfg, metrics=None):
        p = AttentionParams(
            d_model=cfg.d_model, vocab=cfg.vocab_size,
            n_heads=cfg.attn_heads, kv_heads=cfg.attn_kv_heads,
            head_dim=cfg.attn_head_dim, block_size=cfg.kv_block_size,
            max_ctx=cfg.max_ctx, seed=cfg.weights_seed)
        self.params = p
        self.cfg = cfg
        self.prefill_name, self.decode_name = register_attention_kernels(p)
        self.weights = device_weights(p)
        # default pool: enough pages for every slot to hold a full
        # context, so admission can never deadlock (+1 for the null page)
        n_blocks = cfg.kv_blocks or (
            cfg.max_slots * p.blocks_per_seq + 1)
        self.pool = KVBlockPool(n_blocks, p.block_size, metrics=metrics)
        shape = (n_blocks, p.block_size, p.kv_heads, p.head_dim)
        self.k_pool = jnp.zeros(shape, jnp.float32)
        self.v_pool = jnp.zeros(shape, jnp.float32)
        self._kv_pending: Dict[int, tuple] = {}  # sid -> (k rows, v rows)
        self._pos: Dict[int, int] = {}           # sid -> next write position
        self._round: Optional[tuple] = None      # (occupied, n_emit)

    @property
    def prefill_batch(self) -> int:
        return max(1, int(getattr(self.cfg, "prefill_batch", 1) or 1))

    def _kv_need(self, seq) -> int:
        """Total KV positions the sequence will ever write: the prompt
        plus one per generated token after the first (the first token's
        K/V lands at position prompt_len on its first decode step)."""
        return len(seq.prompt) + seq.params.max_new_tokens - 1

    # -- admission -------------------------------------------------------
    def reject(self, seq) -> Optional[str]:
        if not seq.prompt:
            return "attention LM needs a non-empty prompt"
        need = self._kv_need(seq)
        if need > self.params.max_ctx:
            return (f"sequence needs {need} KV positions "
                    f"(prompt {len(seq.prompt)} + "
                    f"{seq.params.max_new_tokens - 1} decode writes) "
                    f"> max_ctx={self.params.max_ctx}")
        return None

    def can_admit(self, seq) -> bool:
        """Reserve every page the sequence will ever need (all-or-nothing
        through ``pool.ensure``, so a half-grab is never held).  Reserving
        here — not at insert — keeps two admissions in the same round from
        double-counting the free list; a refusal counts ``alloc_deferred``
        and the engine holds the sequence until evictions free pages."""
        return self.pool.ensure(seq.sid, self._kv_need(seq)) is not None

    # -- prefill ---------------------------------------------------------
    def prefill_bundle(self, seqs) -> Tuple[str, object]:
        p = self.params
        PB, P = self.prefill_batch, p.max_ctx
        prompt = np.zeros((PB, P), np.int32)
        meta = np.zeros((PB, META_W), np.int32)
        for r, seq in enumerate(seqs):
            prompt[r, :len(seq.prompt)] = seq.prompt
            meta[r, 0] = len(seq.prompt)
        out = np.zeros((PB, PREFILL_OUT_W), np.int32)
        kv = np.zeros((PB, P, p.kv_heads, p.head_dim), np.float32)
        kd = get_kernel(self.prefill_name)
        return self.prefill_name, kd.bundle(
            out, kv, kv.copy(), prompt, meta, self.weights,
            PB=PB, P=P, vocab=p.vocab)

    def harvest_prefill(self, seqs, bufs) -> List[int]:
        out = np.asarray(bufs[0])
        kn, vn = bufs[1], bufs[2]   # device [PB, P, KV, hd]
        firsts = []
        for r, seq in enumerate(seqs):
            self._kv_pending[seq.sid] = (kn[r], vn[r])
            firsts.append(int(out[r, 0]))
        return firsts

    # -- decode ----------------------------------------------------------
    def decode_bundle(self, occupied, inserted, n_emit):
        p, cfg = self.params, self.cfg
        S, R, BS = cfg.max_slots, cfg.round_tokens, p.block_size
        table = np.zeros((S, p.table_width), np.int32)
        inserted_set = set(inserted)
        for i, seq in occupied:
            sid = seq.sid
            if i in inserted_set:
                blocks = self.pool.ensure(sid, self._kv_need(seq))
                assert blocks is not None, "can_admit gated this insert"
                L = len(seq.prompt)
                self._pos[sid] = L
                # the prefill region may sit on another device than the
                # pools: bring its K/V rows over before the scatter
                kn, vn = jax.device_put(self._kv_pending.pop(sid),
                                        self.k_pool.sharding)
                npg = self.pool.blocks_for(L)
                ids = jnp.asarray(blocks[:npg], jnp.int32)
                self.k_pool = self.k_pool.at[ids].set(
                    kn[:npg * BS].reshape(npg, BS, p.kv_heads, p.head_dim))
                self.v_pool = self.v_pool.at[ids].set(
                    vn[:npg * BS].reshape(npg, BS, p.kv_heads, p.head_dim))
            blocks = self.pool.blocks(sid)
            table[i, COL_ACTIVE] = 1
            table[i, COL_N_EMIT] = n_emit[i]
            table[i, COL_LAST_TOK] = seq.tokens[-1]
            table[i, COL_SEQ_LEN] = self._pos[sid]
            table[i, TABLE_META:TABLE_META + len(blocks)] = blocks
        out = np.zeros((S, R), np.int32)
        kd = get_kernel(self.decode_name)
        bundle = kd.bundle(out, self.k_pool, self.v_pool, table,
                           self.weights, S=S, R=R, vocab=p.vocab)
        self._round = (list(occupied), dict(n_emit))
        return self.decode_name, bundle, not inserted

    def finish_round(self, bufs) -> np.ndarray:
        self.k_pool, self.v_pool = bufs[1], bufs[2]
        occupied, n_emit = self._round
        self._round = None
        for i, seq in occupied:
            self._pos[seq.sid] = self._pos.get(seq.sid, 0) + n_emit[i]
        return np.asarray(bufs[0])

    def fail_round(self):
        # the engine fails every resident sequence after this; their
        # pages come back through drop() as each one settles
        self._round = None

    def drop(self, sid: int):
        self._kv_pending.pop(sid, None)
        self._pos.pop(sid, None)
        self.pool.release(sid)

    # -- observability ---------------------------------------------------
    def kv_stats(self) -> Optional[dict]:
        return self.pool.stats()

    def trace_attrs(self) -> dict:
        return {"kv": self.pool.in_use}


# -- standalone oracle ---------------------------------------------------

@functools.lru_cache(maxsize=16)
def _oracle_chunk(name: str):
    # the same kernel body the regions compile, wrapped in the same
    # pipelined-chunk entry point (minus donation — the oracle threads
    # its buffers by hand)
    return jax.jit(make_pipelined_chunk(get_kernel(name).fn))


def _drive(name: str, bundle, budget: int):
    chunk = _oracle_chunk(name)
    bufs, ints, floats = bundle.padded()
    bufs = tuple(jnp.asarray(b) for b in bufs)
    ctx = ContextRecord.fresh()
    b = jnp.int32(budget)
    while True:
        ctx, bufs, done = chunk(ctx, bufs, ints, floats, b)
        if int(done):
            return bufs


def attention_oracle_stream(prompt, max_new_tokens: int,
                            p: Optional[AttentionParams] = None, *,
                            max_slots: int = 4, round_tokens: int = 4,
                            prefill_batch: int = 1,
                            kv_blocks: Optional[int] = None,
                            chunk_budget: int = 4) -> list:
    """The exact token stream the serving engine must produce for one
    sequence, replayed standalone through the same kernels with the
    same buffer shapes: batch the sequence into row 0 of an otherwise
    empty prefill/decode batch and run uninterrupted.  Row independence
    plus fixed shapes make this bit-identical to any engine schedule —
    batching, chunking, preemption, migration included."""
    p = p or AttentionParams()
    pre_name, dec_name = register_attention_kernels(p)
    w = device_weights(p)
    BS, T_blk = p.block_size, p.blocks_per_seq
    L = len(prompt)
    if not (0 < L and L + max_new_tokens - 1 <= p.max_ctx):
        raise ValueError(f"prompt {L} + {max_new_tokens - 1} decode writes "
                         f"must fit max_ctx={p.max_ctx}")

    # prefill: row 0 of a PB-row batch, everything else empty
    PB, P = max(1, prefill_batch), p.max_ctx
    prompt_buf = np.zeros((PB, P), np.int32)
    prompt_buf[0, :L] = prompt
    meta = np.zeros((PB, META_W), np.int32)
    meta[0, 0] = L
    kv = np.zeros((PB, P, p.kv_heads, p.head_dim), np.float32)
    kd = get_kernel(pre_name)
    bufs = _drive(pre_name, kd.bundle(
        np.zeros((PB, PREFILL_OUT_W), np.int32), kv, kv.copy(), prompt_buf,
        meta, w, PB=PB, P=P, vocab=p.vocab), chunk_budget)
    toks = [int(np.asarray(bufs[0])[0, 0])]
    if max_new_tokens <= 1:
        return toks

    # paginate the prompt K/V into pool blocks 1..n (allocation order)
    n_blocks = kv_blocks or (max_slots * T_blk + 1)
    n_need = -(-(L + max_new_tokens - 1) // BS)
    blocks = list(range(1, n_need + 1))
    shape = (n_blocks, BS, p.kv_heads, p.head_dim)
    k_pool, v_pool = np.zeros(shape, np.float32), np.zeros(shape, np.float32)
    kn = np.asarray(bufs[1])[0]
    vn = np.asarray(bufs[2])[0]
    for j in range(-(-L // BS)):
        k_pool[blocks[j]] = kn[j * BS:(j + 1) * BS]
        v_pool[blocks[j]] = vn[j * BS:(j + 1) * BS]
    k_pool, v_pool = jnp.asarray(k_pool), jnp.asarray(v_pool)

    # decode rounds, slot 0 of an otherwise empty S-row table
    S, R = max_slots, round_tokens
    kdd = get_kernel(dec_name)
    pos = L
    while len(toks) < max_new_tokens:
        n = min(R, max_new_tokens - len(toks))
        table = np.zeros((S, p.table_width), np.int32)
        table[0, COL_ACTIVE] = 1
        table[0, COL_N_EMIT] = n
        table[0, COL_LAST_TOK] = toks[-1]
        table[0, COL_SEQ_LEN] = pos
        table[0, TABLE_META:TABLE_META + len(blocks)] = blocks
        bufs = _drive(dec_name, kdd.bundle(
            np.zeros((S, R), np.int32), k_pool, v_pool, table, w,
            S=S, R=R, vocab=p.vocab), chunk_budget)
        toks.extend(int(t) for t in np.asarray(bufs[0])[0, :n])
        k_pool, v_pool = bufs[1], bufs[2]
        pos += n
    return toks
