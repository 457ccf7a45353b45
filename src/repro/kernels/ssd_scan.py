"""Pallas TPU Mamba2 SSD chunk-scan kernel.

Implements the chunked state-space-duality algorithm (arXiv:2405.21060)
with the recurrent (P × N) state in VMEM scratch: the grid is
(B, H, chunks) with chunks innermost — TPU grids are sequential, so the
state survives across chunk steps and never round-trips HBM (the jnp
reference scans with a lax.scan carry instead).

Per chunk (Q = chunk length):
  intra:  Y_diag = (L ∘ (C Bᵀ)) (X·dt)      L = exp(segsum(dt·A))
  inter:  Y_off  = (C Sᵀ) ∘ exp(cumsum)      S = running state
  state:  S ← S·exp(sum) + (B ∘ decay)ᵀ (X·dt)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
                state_scr, *, nchunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)     # (Q,)
    a = a_ref[0]                                  # () per-head decay rate
    b = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)          # (Q, N)

    dtA = dt * a                                  # (Q,) negative
    cum = jnp.cumsum(dtA)                         # (Q,)
    xdt = x * dt[:, None]                         # (Q, P)

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    Q = x.shape[0]
    diff = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(L * scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the incoming state
    state = state_scr[...]                        # (P, N)
    y += jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[:, None]

    # state update
    decay_states = jnp.exp(cum[-1] - cum)         # (Q,)
    binj = b * decay_states[:, None]              # (Q, N)
    state_scr[...] = state * jnp.exp(cum[-1]) + jax.lax.dot_general(
        xdt, binj, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (P, N)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nchunks - 1)
    def _emit_state():
        st_ref[0, 0] = state_scr[...].astype(st_ref.dtype)


def ssd_scan_pallas(x, dt, a, b, c, *, chunk: int = 256,
                    interpret: bool = False):
    """x:(B,S,H,P) dt:(B,S,H) a:(H,) b,c:(B,S,N) -> (y (B,S,H,P),
    final_state (B,H,P,N))."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    if S % Q:
        raise NotImplementedError("seq not divisible by chunk")
    nc = S // Q

    # kernel layouts
    xk = x.transpose(0, 2, 1, 3).reshape(B, H, nc, Q, P)
    dtk = dt.transpose(0, 2, 1).reshape(B, H, nc, Q)
    bk_ = b.reshape(B, nc, Q, N)
    ck_ = c.reshape(B, nc, Q, N)

    y, st = pl.pallas_call(
        functools.partial(_ssd_kernel, nchunks=nc),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda bi, h, ci: (bi, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1,), lambda bi, h, ci: (h,)),
            pl.BlockSpec((1, 1, Q, N), lambda bi, h, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda bi, h, ci: (bi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda bi, h, ci: (bi, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        # the running state (st_ref) carries across chunks: the chunk axis
        # is a sequential scan, not a parallel dim
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xk, dtk, a.astype(jnp.float32), bk_, ck_)

    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    return y, st
