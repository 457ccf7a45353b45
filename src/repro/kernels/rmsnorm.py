"""Pallas TPU fused RMSNorm kernel.

One pass over rows resident in VMEM: mean-of-squares, rsqrt, scale — the
fused norm that on GPU would be a Transformer-Engine/apex fused op.
Grid over row blocks of the flattened (rows, D) view.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)            # (rb, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
                   interpret: bool = False):
    orig_shape = x.shape
    D = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    xf = x.reshape(rows, D)
    rb = min(block_rows, rows)
    if rows % rb:
        raise NotImplementedError("rows not divisible by block")
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // rb,),
        in_specs=[
            pl.BlockSpec((rb, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rb, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(xf, scale)
    return out.reshape(orig_shape)
