"""Jit-ready kernel wrappers with backend dispatch.

Each op picks the Pallas TPU kernel when (a) running on TPU or (b)
``REPRO_FORCE_PALLAS=interpret`` (CI validation on CPU), else the pure-jnp
reference from ``repro.kernels.ref`` — which is itself production-grade
(flash custom-VJP etc.), so models never change semantics across backends.

The path is chosen before the call, from the backend and an explicit
shape check (``*_fits``) where a kernel tiles only some shapes.  A
kernel that is chosen and then fails raises: nothing here catches it
and quietly runs the reference instead.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref
from repro.parallel.sharding import current_mesh, logical_to_spec


def _use_pallas() -> Optional[str]:
    """None | 'tpu' | 'interpret'."""
    if os.environ.get("REPRO_FORCE_PALLAS", "") in ("interpret", "1"):
        return "interpret"
    return "tpu" if jax.default_backend() == "tpu" else None


def _per_shard(kernel, q, k, v, q_pos, k_pos, window):
    """Run a (B, S, H, d) attention kernel on each device's shard.

    The compiler cannot partition a Pallas kernel, so under an active
    mesh it runs inside ``shard_map`` over the batch and head axes the
    activations are laid out on (their ``constrain`` names); each device
    attends over its own rows and heads, which needs no communication.
    """
    window = jnp.asarray(1 << 30 if window is None else window, jnp.int32)
    mesh = current_mesh()
    if mesh is None:
        return kernel(q, k, v, q_pos, k_pos, window=window)
    heads = logical_to_spec(("batch", None, "act_heads", None), q.shape)
    rows = logical_to_spec(("batch", None), q_pos.shape)
    return jax.shard_map(
        lambda q, k, v, qp, kp, w: kernel(q, k, v, qp, kp, window=w),
        mesh=mesh, in_specs=(heads, heads, heads, rows, rows, P()),
        out_specs=heads, check_vma=False)(q, k, v, q_pos, k_pos, window)


def flash_attention(q, k, v, q_pos, k_pos, *, causal=True, window=None,
                    softcap=None, chunk=1024, k_scale=None, v_scale=None,
                    layer=None):
    """Backend-dispatched flash attention.

    q: (B, S, H, d); k, v: (B, T, K, d) where K may be the NATIVE
    kv-head count (GQA/MQA, H % K == 0) — callers no longer repeat K/V
    to the full head count.  Single-token queries (S == 1, the serving
    decode hot path) dispatch to the grouped split-KV flash-decode
    kernel, which reads each K/V cache byte exactly once; everything
    else takes the prefill/train flash path (grouped K/V expanded
    shard-locally first), on the kernel where it tiles (S, T).

    When ``k_scale``/``v_scale`` (B, T, K) are given, k/v are a
    quantized (int8/fp8) cache and decode dispatches to the
    dequantize-in-kernel variant; only S == 1 supports scales here
    (multi-token callers dequantize before calling).

    With ``layer``, k, v (and the scales) are a decode loop's stacked
    (L, B, T, ...) cache and k_pos (L, B, T): the decode kernel reads
    that layer in place; every other path takes the layer's slice.
    """
    mode = _use_pallas()
    interpret = mode == "interpret"
    quant = k_scale is not None
    if layer is not None and (mode is None or q.shape[1] != 1):
        k, v, k_pos = k[layer], v[layer], k_pos[layer]
        if quant:
            k_scale, v_scale = k_scale[layer], v_scale[layer]
        layer = None
    if q.shape[1] == 1:
        # decode: grouped split-KV kernel / pure-jnp twin (forward-only)
        if mode is not None:
            from repro.kernels.flash_decode import (flash_decode_pallas,
                                                    flash_decode_pallas_quant)
            if quant:
                return flash_decode_pallas_quant(
                    q, k, v, q_pos, k_pos, k_scale, v_scale,
                    causal=causal, window=window, softcap=softcap,
                    interpret=interpret, layer=layer)
            return flash_decode_pallas(
                q, k, v, q_pos, k_pos, causal=causal, window=window,
                softcap=softcap, interpret=interpret, layer=layer)
        if quant:
            from repro.kernels.quant import flash_decode_quant_ref
            return flash_decode_quant_ref(
                q, k, v, q_pos, k_pos, k_scale, v_scale, causal=causal,
                window=window, softcap=softcap)
        return _ref.flash_decode_ref(q, k, v, q_pos, k_pos, causal=causal,
                                     window=window, softcap=softcap)
    if quant:
        raise NotImplementedError(
            "quantized K/V reach flash_attention only on the S == 1 "
            "decode path; dequantize before multi-token attention")
    if k.shape[2] != q.shape[2]:
        # grouped K/V on a multi-token path: expand to per-shard MHA
        groups = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    if mode is not None:
        from repro.kernels.flash_attention import (flash_attention_fits,
                                                   flash_attention_pallas)
        if flash_attention_fits(q.shape[1], k.shape[1]):
            return _per_shard(
                functools.partial(flash_attention_pallas, causal=causal,
                                  softcap=softcap, interpret=interpret),
                q, k, v, q_pos, k_pos, window)
    return _ref.flash_attention_ref(q, k, v, q_pos, k_pos, causal=causal,
                                    window=window, softcap=softcap,
                                    chunk=chunk)


def flash_decode_paged(q, k_pool, v_pool, q_pos, kp_pool, block_tables, *,
                       causal=True, window=None, softcap=None,
                       k_scale=None, v_scale=None):
    """Backend-dispatched paged flash decode.

    q: (B, 1, H, d); k_pool, v_pool: (num_blocks, block_size, K, d) —
    the GLOBAL block pool shared by all requests; kp_pool:
    (num_blocks, block_size) int32 positions (-1 = unwritten);
    block_tables: (B, max_blocks) int32, -1 = unmapped.  The Pallas
    kernel gathers pool blocks through the scalar-prefetched table
    inside the grid; the pure-jnp twin gathers with take + reshape.

    ``k_scale``/``v_scale`` (num_blocks, block_size, K) mark the pools
    as quantized (int8/fp8): the scale pools ride the same block-table
    gather and the kernel dequantizes in-register.
    """
    mode = _use_pallas()
    quant = k_scale is not None
    if mode is not None:
        from repro.kernels.flash_decode import (flash_decode_paged as _paged,
                                                flash_decode_paged_quant)
        if quant:
            return flash_decode_paged_quant(
                q, k_pool, v_pool, q_pos, kp_pool, block_tables,
                k_scale, v_scale, causal=causal, window=window,
                softcap=softcap, interpret=(mode == "interpret"))
        return _paged(q, k_pool, v_pool, q_pos, kp_pool, block_tables,
                      causal=causal, window=window, softcap=softcap,
                      interpret=(mode == "interpret"))
    if quant:
        from repro.kernels.quant import flash_decode_paged_quant_ref
        return flash_decode_paged_quant_ref(
            q, k_pool, v_pool, q_pos, kp_pool, block_tables,
            k_scale, v_scale, causal=causal, window=window, softcap=softcap)
    return _ref.flash_decode_paged_ref(q, k_pool, v_pool, q_pos, kp_pool,
                                       block_tables, causal=causal,
                                       window=window, softcap=softcap)


def mxp_gemm(a, b, *, block: int = 128):
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels.mxp_gemm import mxp_gemm_fits, mxp_gemm_pallas
        if mxp_gemm_fits(a.shape[0], b.shape[1], a.shape[1], block=block):
            return mxp_gemm_pallas(a, b, block=block,
                                   interpret=(mode == "interpret"))
    return _ref.mxp_gemm_ref(a, b, block=block)


def moe_gemm(xe, counts, w1, w3, w2, *, act: str = "silu"):
    """Backend-dispatched grouped-expert gated FFN.

    xe: (B, E, C, D) capacity blocks from the MoE sort-based dispatch
    (rows past ``counts[b, e]`` are zero padding); counts: (B, E) int32;
    w1, w3: (E, D, F); w2: (E, F, D); ``act`` names the gate activation
    ("silu" | "gelu_tanh").  The Pallas kernel runs the fused blocked
    GEMM only for single-shard lowering — under an active mesh the
    caller keeps the einsum formulation so the TP/EP sharding
    constraints on the hidden tile stay in effect.
    """
    mode = _use_pallas()
    if mode is not None:
        from repro.kernels.moe_gemm import moe_gemm_pallas
        if current_mesh() is None:
            return moe_gemm_pallas(xe, counts, w1, w3, w2, act=act,
                                   interpret=(mode == "interpret"))
    return _ref.moe_gemm_ref(xe, counts, w1, w3, w2, act=act)
