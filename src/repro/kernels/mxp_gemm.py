"""Pallas TPU mixed-precision (emulated FP8) blocked GEMM — the compute
core of the HPL-MxP reproduction (paper §6.4, Table 7).

Adaptation notes (DESIGN.md §2): the paper runs HPL-MxP in *Sloppy FP8*
on H100 tensor cores.  On TPU v5e the MXU consumes bf16/int8 (v5p+: fp8),
so the kernel emulates e4m3 quantization of each (block_m × block_k) /
(block_k × block_n) tile — per-tile max-abs scaling, 3-mantissa-bit
round-to-nearest — and accumulates in fp32, preserving HPL-MxP's numeric
structure (low-precision multiplies + high-precision accumulate +
iterative refinement on top, see benchmarks/hpl_mxp.py).

Grid (M/bm, N/bn, K/bk), K innermost; fp32 accumulator in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

E4M3_MAX = 448.0


def _quantize_e4m3(x):
    """Emulated e4m3: clamp + keep 3 mantissa bits (round to nearest)."""
    x = jnp.clip(x, -E4M3_MAX, E4M3_MAX)
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _mxp_kernel(a_ref, b_ref, o_ref, acc_scr, *, k_steps: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    at = a_ref[...].astype(jnp.float32)              # (bm, bk)
    bt = b_ref[...].astype(jnp.float32)              # (bk, bn)
    sa = jnp.maximum(jnp.max(jnp.abs(at), axis=1, keepdims=True), 1e-30)
    sb = jnp.maximum(jnp.max(jnp.abs(bt), axis=0, keepdims=True), 1e-30)
    aq = _quantize_e4m3(at / sa * E4M3_MAX) / E4M3_MAX * sa
    bq = _quantize_e4m3(bt / sb * E4M3_MAX) / E4M3_MAX * sb
    acc_scr[...] += jax.lax.dot_general(
        aq, bq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == k_steps - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def mxp_gemm_fits(M: int, N: int, K: int, *, block: int = 128,
                  block_m: int = 128, block_n: int = 128) -> bool:
    """Whether the kernel tiles an (M, K) x (K, N) product."""
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block, K)
    return M % bm == 0 and N % bn == 0 and K % bk == 0


def mxp_gemm_pallas(a, b, *, block: int = 128, block_m: int = 128,
                    block_n: int = 128, interpret: bool = False):
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block, K)
    if not mxp_gemm_fits(M, N, K, block=block, block_m=block_m,
                         block_n=block_n):
        raise NotImplementedError("dims not divisible by block")
    # repro-lint: divisible — guarded by mxp_gemm_fits just above
    k_steps = K // bk
    return pl.pallas_call(
        functools.partial(_mxp_kernel, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        # the (i, j) output tile accumulates over the k axis: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mxp_gemm",
    )(a, b)
