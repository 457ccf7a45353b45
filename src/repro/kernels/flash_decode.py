"""Pallas TPU grouped split-KV flash-decode forward kernels.

The serving decode hot path (one query token per sequence) is memory
bound: per decoded token the roofline-optimal kernel reads every live
K/V cache byte exactly once.  The prefill flash kernel misses that
optimum twice over — GQA K/V were repeated to the full head count
before the call (``groups``× the HBM bytes) and the single-token query
was padded to a whole q block (wasted MXU tiles).  These kernels fix
both structurally:

  * **Native GQA layout.**  The ``groups`` q heads that share one KV
    head ride together as a ``(groups, head_dim)`` tile, so each K/V
    block is streamed from HBM once and contracted against all of its
    q heads.  MQA (kv=1) degenerates to one big ``(H, d)`` q tile;
    MHA to ``groups=1``.
  * **Split-KV.**  The KV axis is split across the grid
    (``grid=(B, kv_splits)``) flash-decode style: each program emits
    partial ``(acc, m, l)`` for its KV block and a log-sum-exp
    reduction epilogue combines the partials — decode parallelism
    scales with cache length instead of query length.

One kernel body serves four variants: a contiguous cache or a paged
block pool (K/V blocks gathered through a scalar-prefetched block
table), each in bf16/f32 or quantized int8/fp8 with per-(token, head)
f32 scales dequantized in-register.

TPU layout.  The cache stays in its native ``(..., tokens, K, d)``
layout: one DMA brings a whole ``(block, K, d)`` tile — the last two
block dims are (K, d), full array dims, which the TPU's tiling accepts
for any kv-head count — and each kv head is a strided sublane read of
it.  Positions ride as ``(rows, 1, tokens)`` so their blocks are
``(1, block)`` rows, and the per-row query position and the window are
scalar-prefetch operands in SMEM.  On a TPU the block (``block_k``, or
the pool's ``block_size``) must be a multiple of the sublane tile:
8 rows for f32, 16 for bf16, 32 for int8/fp8; a contiguous row's block
is also the lane width of its position row, so a multiple of 128.

In place.  The contiguous kernels read a decode loop's stacked
``(layers, B, T, K, d)`` cache directly: the layer index is one more
scalar-prefetch operand and the K/V, position and scale index maps
select that layer, so no layer is copied out for the call.  Nor is the
cache padded to whole blocks: the block is the largest lane-multiple
divisor of the row length up to ``block_k``, and only a row length
with none gets a ragged last block, masked in-kernel by column index.

Masking is position-based, identical to the prefill kernel: ``k_pos``
is ``(B, T)`` int32 with -1 marking empty ring-buffer slots, ``q_pos``
is the per-row absolute decode position (true per-slot lengths from
``SlotPool``), sliding windows ride in as a scalar operand, and the
tanh score softcap is a static parameter.

Forward only — decode never differentiates.  ``ops.flash_attention``
and ``ops.flash_decode_paged`` dispatch here (``ref.flash_decode_ref``
and ``ref.flash_decode_paged_ref`` are the pure-jnp twins).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(*refs, kv_heads: int, scale: float,
                   causal: bool, softcap: Optional[float], paged: bool,
                   quant: bool, tokens: Optional[int]):
    """One (batch row × kv block) program over every kv head.

    Scalar prefetch: block table (paged) or layer index (contiguous,
    read by the index maps only), window (1,), q_pos (B,).  Blocks:
    q (1, K, G, d); k/v (1, bk, K, d); k_pos (1, 1, bk); [k/v scales
    (1, bk, K)].  Emits the block's partial (acc, m, l) per kv head; no
    cross-program state.  A paged table entry of -1 kills the whole
    block's columns, so the pool block it was clamped to — which may
    belong to another request — contributes nothing.  ``tokens`` is the
    row length when the last block runs past it: its columns beyond
    are masked, and their V (unspecified memory) zeroed.
    """
    idx_ref, win_ref, qpos_ref, *refs = refs
    if quant:
        q_ref, k_ref, v_ref, kp_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, kp_ref, o_ref, m_ref, l_ref = refs
    b, si = pl.program_id(0), pl.program_id(1)
    qp = qpos_ref[b]                       # scalar: this row's position
    kp = kp_ref[0]                         # (1, bk)
    valid = kp >= 0
    if paged:
        valid &= idx_ref[b, si] >= 0
    if causal:
        valid &= qp >= kp
    valid &= (qp - kp) < win_ref[0]
    if tokens is not None:
        bk = kp.shape[-1]
        col = si * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid &= col < tokens
        row_ok = (si * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                  < tokens)

    for h in range(kv_heads):
        q = q_ref[0, h]                    # (G, d)
        k = k_ref[0, :, h]                 # (bk, d)
        v = v_ref[0, :, h]
        if quant:
            # dequantize first: HBM streamed the narrow bytes, every
            # contraction below runs f32
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32) * ks_ref[0, :, h:h + 1]
            v = v.astype(jnp.float32) * vs_ref[0, :, h:h + 1]
        if tokens is not None:
            v = jnp.where(row_ok, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (G, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m = s.max(axis=-1, keepdims=True)                  # (G, 1)
        # explicit zero for masked columns: a fully-dead block yields
        # l == 0 (not bk), so the epilogue drops it instead of averaging
        # garbage v
        p = jnp.where(valid, jnp.exp(s - m), 0.0)
        l = p.sum(axis=-1, keepdims=True)                  # (G, 1)
        acc = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (G, d)
        o_ref[0, 0, h] = acc
        m_ref[0, 0, h] = m
        l_ref[0, 0, h] = l


def _decode_call(q, k, v, k_pos, q_pos, window, index, *, block, splits,
                 kv_map, scale_map, pos_map, paged, scales=None, tokens=None,
                 causal, softcap, interpret):
    """Shared pallas_call for the four decode variants.

    k, v: (L, B, tokens, K, d) — a contiguous cache stacked over layers
    — or (num_blocks, tokens, K, d), a pool; k_pos and scales have the
    same leading dims, as (..., 1, tokens) and (..., tokens, K).  The
    stack's layer dim is squeezed out of every block, so the kernel
    sees the same (1, block, ...) blocks either way.  ``index`` is the
    first scalar-prefetch operand: the block table (paged) or the layer
    (1,).  ``kv_map``, ``scale_map`` and ``pos_map`` are the index maps
    of the K/V, scale and position blocks over the (B, splits) grid and
    the scalar-prefetch refs.
    """
    # repro-lint: divisible — a contiguous block divides the row or the
    # kernel masks the ragged tail (``tokens``); a pool is whole blocks
    B, _, H, d = q.shape
    K = k.shape[-2]
    G = H // K
    quant = scales is not None
    if window is None:
        window = 1 << 30
    window = jnp.asarray(window, jnp.int32).reshape(1)
    qp = q_pos.astype(jnp.int32).reshape(B, -1)[:, 0]
    prefetch = (index.astype(jnp.int32), window, qp)
    lead = (None,) * (k.ndim - 4)

    q_map = lambda b, si, *_: (b, 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, K, G, d), q_map),
        pl.BlockSpec(lead + (1, block, K, d), kv_map),
        pl.BlockSpec(lead + (1, block, K, d), kv_map),
        pl.BlockSpec(lead + (1, 1, block), pos_map),
    ]
    operands = [q[:, 0].reshape(B, K, G, d), k, v, k_pos.astype(jnp.int32)]
    if quant:
        in_specs += [pl.BlockSpec(lead + (1, block, K), scale_map)] * 2
        operands += [s.astype(jnp.float32) for s in scales]
    out_map = lambda b, si, *_: (b, si, 0, 0, 0)
    o_part, m_part, l_part = pl.pallas_call(
        functools.partial(_decode_kernel, kv_heads=K,
                          scale=1.0 / math.sqrt(d), causal=causal,
                          softcap=softcap, paged=paged, quant=quant,
                          tokens=tokens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, splits),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, K, G, d), out_map),
                pl.BlockSpec((1, 1, K, G, 1), out_map),
                pl.BlockSpec((1, 1, K, G, 1), out_map),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, splits, K, G, d), jnp.float32),
            jax.ShapeDtypeStruct((B, splits, K, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, splits, K, G, 1), jnp.float32),
        ],
        # every grid step writes its own output block (the split
        # combine happens outside the kernel), so all dims are parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="flash_decode" + "_paged" * paged + "_quant" * quant,
    )(*prefetch, *operands)

    out = combine_partials(o_part, m_part, l_part)         # (B, K, G, d)
    return out.reshape(B, 1, H, d).astype(q.dtype)


def _check_decode(q, K):
    S, H = q.shape[1], q.shape[2]
    if S != 1:
        raise NotImplementedError("flash decode handles a single query "
                                  f"token per row (got S={S})")
    if H % K:
        raise NotImplementedError(f"q heads {H} not grouped over kv {K}")


LANES = 128     # a contiguous block is the lane width of its position row


def contiguous_block(T: int, block_k: int) -> Tuple[int, bool]:
    """(block, ragged) for a contiguous row of ``T`` tokens.

    A row that fits in ``block_k`` is one block.  Otherwise the block is
    the largest divisor of ``T`` that is a multiple of the lane width
    (of ``block_k`` itself when that is smaller, as in interpret-mode
    tests) and at most ``block_k``; only where none exists is the block
    the largest such multiple, the grid runs ``cdiv(T, block)`` and the
    last block is ragged (masked in-kernel).  1,280 -> 256, 5,120 -> 512
    at the default ``block_k`` of 512.
    """
    if T <= block_k:
        return T, False
    step = min(LANES, block_k)
    top = block_k - block_k % step
    for bk in range(top, 0, -step):
        if T % bk == 0:
            return bk, False
    return top, True


def _contiguous(q, k, v, q_pos, k_pos, scales, layer, *, causal, window,
                softcap, block_k, interpret):
    if layer is None:             # one layer's cache: a stack of one
        k, v, k_pos = k[None], v[None], k_pos[None]
        if scales is not None:
            scales = tuple(s[None] for s in scales)
        layer = 0
    Lr, B, T, K, _ = k.shape
    _check_decode(q, K)
    bk, ragged = contiguous_block(T, block_k)
    return _decode_call(
        q, k, v, k_pos.reshape(Lr, B, 1, T), q_pos, window,
        jnp.asarray(layer, jnp.int32).reshape(1), block=bk,
        splits=-(-T // bk),
        kv_map=lambda b, si, lyr, *_: (lyr[0], b, si, 0, 0),
        scale_map=lambda b, si, lyr, *_: (lyr[0], b, si, 0),
        pos_map=lambda b, si, lyr, *_: (lyr[0], b, 0, si),
        paged=False, scales=scales, tokens=T if ragged else None,
        causal=causal, softcap=softcap, interpret=interpret)


def _paged(q, k_pool, v_pool, q_pos, kp_pool, block_tables, scales, *,
           causal, window, softcap, interpret):
    NB, BS, K, d = k_pool.shape
    _check_decode(q, K)
    # unmapped entries still index the pool (clamped to block 0); their
    # columns are masked dead in-kernel via the table entry
    blk = lambda b, si, bt, *_: jnp.maximum(bt[b, si], 0)
    return _decode_call(
        q, k_pool, v_pool,
        kp_pool.reshape(NB, 1, BS), q_pos, window, block_tables, block=BS,
        splits=block_tables.shape[1],
        kv_map=lambda b, si, *r: (blk(b, si, *r), 0, 0, 0),
        scale_map=lambda b, si, *r: (blk(b, si, *r), 0, 0),
        pos_map=lambda b, si, *r: (blk(b, si, *r), 0, 0),
        paged=True, scales=scales, causal=causal, softcap=softcap,
        interpret=interpret)


def flash_decode_pallas(q, k, v, q_pos, k_pos, *, causal: bool = True,
                        window=None, softcap: Optional[float] = None,
                        block_k: int = 512, interpret: bool = False,
                        layer=None):
    """Grouped split-KV flash decode over a contiguous cache.

    q: (B, 1, H, d) — ONE query token per row; k, v: (B, T, K, d) at the
    native kv-head count (H % K == 0, no repeat); q_pos: (B, 1) or (B,);
    k_pos: (B, T) int32 with -1 = empty slot.  ``window`` may be None,
    an int, or a traced scalar.  With ``layer`` (an int or a traced
    scalar), k, v are the stacked (L, B, T, K, d) cache of every layer
    and k_pos (L, B, T): the kernel reads that layer in place.  Returns
    (B, 1, H, d).
    """
    return _contiguous(q, k, v, q_pos, k_pos, None, layer, causal=causal,
                       window=window, softcap=softcap, block_k=block_k,
                       interpret=interpret)


def flash_decode_pallas_quant(q, kq, vq, q_pos, k_pos, k_scale, v_scale,
                              *, causal: bool = True, window=None,
                              softcap: Optional[float] = None,
                              block_k: int = 512,
                              interpret: bool = False, layer=None):
    """Grouped split-KV flash decode over a quantized contiguous cache.

    Same contract as ``flash_decode_pallas`` except ``kq, vq (B, T, K,
    d)`` are int8/fp8 and ``k_scale, v_scale (B, T, K)`` carry the f32
    per-(token, head) scales, blocked on the same map as their data
    (stacked (L, B, T, K) with ``layer``).
    """
    return _contiguous(q, kq, vq, q_pos, k_pos, (k_scale, v_scale), layer,
                       causal=causal, window=window, softcap=softcap,
                       block_k=block_k, interpret=interpret)


def flash_decode_paged(q, k_pool, v_pool, q_pos, kp_pool, block_tables, *,
                       causal: bool = True, window=None,
                       softcap: Optional[float] = None,
                       interpret: bool = False):
    """Grouped split-KV flash decode through per-request block tables.

    q: (B, 1, H, d) — ONE query token per row.  K/V live in a global
    paged pool shared by every request: k_pool, v_pool are
    (num_blocks, block_size, K, d), kp_pool is (num_blocks, block_size)
    int32 with -1 marking unwritten slots.  block_tables is (B, max_blocks)
    int32 — entry j is the pool block holding row positions
    [j*block_size, (j+1)*block_size), -1 = not yet mapped.

    The table rides in as a scalar-prefetch operand so the BlockSpec
    index maps gather pool blocks directly inside the Pallas grid — the
    kv-split axis of the contiguous kernel becomes the block-table axis
    and the log-sum-exp combine epilogue is unchanged.  With
    block_size == block_k the per-split arithmetic is identical to the
    contiguous kernel, so f32 outputs match bit-for-bit.
    """
    return _paged(q, k_pool, v_pool, q_pos, kp_pool, block_tables, None,
                  causal=causal, window=window, softcap=softcap,
                  interpret=interpret)


def flash_decode_paged_quant(q, kq_pool, vq_pool, q_pos, kp_pool,
                             block_tables, ks_pool, vs_pool, *,
                             causal: bool = True, window=None,
                             softcap: Optional[float] = None,
                             interpret: bool = False):
    """Paged flash decode over a quantized block pool.

    Same contract as ``flash_decode_paged`` except ``kq_pool, vq_pool
    (NB, BS, K, d)`` are int8/fp8 and ``ks_pool, vs_pool (NB, BS, K)``
    are the f32 scale pools, block-mapped alongside the data through the
    same scalar-prefetched table.
    """
    return _paged(q, kq_pool, vq_pool, q_pos, kp_pool, block_tables,
                  (ks_pool, vs_pool), causal=causal, window=window,
                  softcap=softcap, interpret=interpret)


def combine_partials(o_part, m_part, l_part):
    """Log-sum-exp reduction over the split axis.

    o_part: (B, splits, K, G, d); m_part, l_part: (B, splits, K, G, 1).
    Fully-dead splits carry (m=NEG_INF, l=0) and contribute nothing; a
    row with NO live key anywhere returns zeros (matches the oracle's
    zeroing of fully-masked rows).
    """
    m_star = m_part.max(axis=1, keepdims=True)             # (B, 1, K, G, 1)
    alpha = jnp.exp(m_part - m_star)                       # (B, s, K, G, 1)
    l_star = (l_part * alpha).sum(axis=1)                  # (B, K, G, 1)
    acc = (o_part * alpha).sum(axis=1)                     # (B, K, G, d)
    return acc / jnp.maximum(l_star, 1e-30)
