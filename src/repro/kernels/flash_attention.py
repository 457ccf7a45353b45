"""Pallas TPU flash-attention kernel (forward) with the recompute backward.

Online-softmax over KV blocks with the score tile resident in VMEM —
the TPU-native adaptation of the memory-bounded attention the MLPerf
GPT-3 recipe relies on (DESIGN.md C2).  Grid: (batch, heads, q_blocks,
kv_blocks); the kv dimension is innermost and TPU grids execute
sequentially, so the (m, l, acc) running state lives in VMEM scratch
across kv steps.

Masking is position-based ((B,S) q_pos / (B,T) k_pos with -1 = empty
slot), so the same kernel serves training, prefill and ring-buffer
decode.  Positions ride as (B, 1, S) rows so their blocks are (1, block)
rows that the TPU tiling accepts at any batch size; sliding windows
ride in as a one-element operand.

``flash_attention_pallas`` is differentiable: a ``custom_vjp`` whose
forward is this kernel (which also emits the per-row log-sum-exp) and
whose backward is the score-recomputing flash backward of
``repro.kernels.ref`` — the same backward the jnp twin uses.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

NEG_INF = -1e30


def _flash_fwd_kernel(win_ref, qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
                      o_ref, lse_ref, m_scr, l_scr, acc_scr, *, causal: bool,
                      scale: float, kv_steps: int,
                      softcap: Optional[float]):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                       # (bq, d)
    k = k_ref[0, 0]                       # (bk, d)
    v = v_ref[0, 0]                       # (bk, d)
    qp = qpos_ref[0, 0]                   # (bq,)
    kp = kpos_ref[0, 0]                   # (bk,)
    window = win_ref[0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (bq, bk)
    if softcap is not None:
        # gemma-style tanh score cap; static param, same math as the ref
        s = softcap * jnp.tanh(s / softcap)

    valid = (kp >= 0)[None, :]
    if causal:
        valid &= qp[:, None] >= kp[None, :]
    valid &= (qp[:, None] - kp[None, :]) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        # rows with no live key get +big, so the backward's exp(s - lse)
        # is exactly zero there (the ref forward's convention)
        lse = jnp.where(l > 0, m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)),
                        _ref.POS_BIG)
        lse_ref[0, 0] = lse[None, :]


def _flash_call(static, q, k, v, q_pos, k_pos, window):
    """Kernel forward: (out (B,S,H,d), lse (B,H,S) f32)."""
    # repro-lint: divisible — flash_attention_pallas admits only shapes
    # for which flash_attention_fits holds
    causal, softcap, bq, bk, interpret = static
    B, S, H, d = q.shape
    T = k.shape[1]
    # kernel layout: (B, H, S, d)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    kv_steps = T // bk
    grid = (B, H, S // bq, kv_steps)

    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, causal=causal,
                          scale=1.0 / math.sqrt(d), kv_steps=kv_steps,
                          softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, qi, ki: (0,)),          # window
            pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, 0, qi)),
            pl.BlockSpec((1, 1, bk), lambda b, h, qi, ki: (b, 0, ki)),
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, qi, ki: (b, h, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        # the output block is revisited across the kv axis (online-softmax
        # accumulation): that dim must stay sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(window, q_pos.astype(jnp.int32).reshape(B, 1, S),
      k_pos.astype(jnp.int32).reshape(B, 1, T), qt, kt, vt)
    return jnp.swapaxes(out, 1, 2), lse[:, :, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(static, q, k, v, q_pos, k_pos, window):
    return _flash_call(static, q, k, v, q_pos, k_pos, window)[0]


def _flash_fwd(static, q, k, v, q_pos, k_pos, window):
    out, lse = _flash_call(static, q, k, v, q_pos, k_pos, window)
    return out, (q, k, v, q_pos, k_pos, window, out, lse)


def _flash_bwd(static, res, dout):
    causal, softcap, _, bk, _ = static
    return _ref._flash_bwd((causal, softcap, bk), res, dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_fits(S: int, T: int, block_q: int = 512,
                         block_k: int = 512) -> bool:
    """Whether the kernel tiles (S, T): each length divides into its block
    (a length below the block is one whole block)."""
    return S % min(block_q, S) == 0 and T % min(block_k, T) == 0


def flash_attention_pallas(q, k, v, q_pos, k_pos, *, causal: bool = True,
                           window=None, softcap: Optional[float] = None,
                           block_q: int = 512,
                           block_k: int = 512, interpret: bool = False):
    """q: (B,S,H,d); k,v: (B,T,H,d); q_pos: (B,S); k_pos: (B,T).

    Returns (B,S,H,d).  Differentiable (recompute backward).  Raises
    NotImplementedError where ``flash_attention_fits`` is false."""
    S, T = q.shape[1], k.shape[1]
    if not flash_attention_fits(S, T, block_q, block_k):
        raise NotImplementedError("seq not divisible by block size")
    if window is None:
        window = 1 << 30
    window = jnp.asarray(window, jnp.int32).reshape(1)
    static = (causal, softcap, min(block_q, S), min(block_k, T), interpret)
    return _flash(static, q, k, v, q_pos, k_pos, window)
