"""Pallas TPU grouped-expert GEMM — the MoE sorted-capacity compute core.

The sort-based dispatch (``models.moe._dispatch_one``) packs each
sequence's routed tokens into dense ``(E, C, D)`` capacity blocks where
the first ``counts[b, e]`` rows of each block are real tokens (rank
order) and the rest are zero padding.  The jnp path runs the gated FFN
as three dense einsums, materializing the ``(B, E, C, F)`` hidden
activations in HBM twice — at Mixtral geometry (F=16384 ≫ D=6144) that
is the dominant bytes term of the whole MoE layer.

This kernel fuses the gated FFN ``w2ᵀ·(act(x·w1) ⊙ (x·w3))`` into one
blocked pass: the grid walks (row blocks × F blocks), the per-F-block
hidden tile lives in registers, and the output accumulates in an f32
VMEM scratch across the F axis (megablox-style).  The per-expert group
sizes ride in via ``PrefetchScalarGridSpec`` (the same scalar-prefetch
pattern as ``flash_decode_paged``'s block tables): ``expert_ids`` steers
each row block to its expert's weights through the index maps, and
``block_valid`` lets fully-empty blocks (capacity the router never
filled) skip their MXU work entirely.

Padded rows are exact zeros, so skipped/padded outputs match the dense
einsum bit-for-bit: act(0)·0 @ w2 == 0 in both formulations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import moe_gemm_ref, resolve_moe_act


def _moe_kernel(eid_ref, cnt_ref, x_ref, w1_ref, w3_ref, w2_ref, o_ref,
                acc_scr, *, f_steps: int, act_fn):
    i = pl.program_id(0)
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(cnt_ref[i] > 0)
    def _compute():
        x = x_ref[...]                                   # (bm, D)
        h1 = jax.lax.dot_general(x, w1_ref[0], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        h3 = jax.lax.dot_general(x, w3_ref[0], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        h = (act_fn(h1) * h3).astype(x.dtype)            # (bm, bf)
        acc_scr[...] += jax.lax.dot_general(
            h, w2_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(fi == f_steps - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _padded(C: int) -> int:
    """Capacity rounded up to whole 8-row sublane tiles."""
    return -(-C // 8) * 8


def _vmem_limit(bm: int, bf: int, D: int, x_bytes: int, w_bytes: int) -> int:
    """Scoped-VMEM bytes one grid step needs, with a quarter headroom.

    Double-buffered x and out row tiles and w1/w3/w2 weight tiles, the
    f32 accumulator, and the step's f32 hidden tiles.  At Mixtral widths
    (D = 6144, bf = 512) this is ~60 MiB: above the 16 MiB a TPU v5e
    scopes by default, well inside its 128 MiB of VMEM.
    """
    need = (2 * (2 * bm * D * x_bytes + 3 * D * bf * w_bytes)
            + bm * D * 4 + 3 * bm * bf * 4)
    return need + need // 4


def _moe_gemm_call(xe, counts, w1, w3, w2, *, act: str, bm: int, bf: int,
                   interpret: bool):
    B, E, C0, D = xe.shape
    F = w1.shape[-1]
    # pad capacity to whole 8-row sublane tiles (a TPU row block must be
    # one); the pad rows are zeros, which the FFN maps to zeros
    C = _padded(C0)
    xe = jnp.pad(xe, ((0, 0), (0, 0), (0, C - C0), (0, 0)))
    if C % bm or F % bf:
        raise NotImplementedError("dims not divisible by block")
    act_fn = resolve_moe_act(act)
    per = C // bm                       # row blocks per (b, e) group
    nb = B * E * per
    f_steps = F // bf

    xr = xe.reshape(B * E * C, D)
    # scalar-prefetch tables: which expert each row block belongs to, and
    # how many of its rows the dispatch actually filled (group offsets)
    expert_ids = jnp.tile(jnp.repeat(jnp.arange(E, dtype=jnp.int32), per), B)
    block_off = jnp.tile(jnp.arange(per, dtype=jnp.int32) * bm, B * E)
    block_valid = jnp.clip(jnp.repeat(counts.reshape(-1), per) - block_off,
                           0, bm).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, f_steps),
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, fi, eid, cnt: (i, 0)),
            pl.BlockSpec((1, D, bf),
                         lambda i, fi, eid, cnt: (eid[i], 0, fi)),
            pl.BlockSpec((1, D, bf),
                         lambda i, fi, eid, cnt: (eid[i], 0, fi)),
            pl.BlockSpec((1, bf, D),
                         lambda i, fi, eid, cnt: (eid[i], fi, 0)),
        ],
        out_specs=pl.BlockSpec((bm, D), lambda i, fi, eid, cnt: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_moe_kernel, f_steps=f_steps, act_fn=act_fn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * E * C, D), xe.dtype),
        # each (row-block) output tile accumulates over the F axis
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(bm, bf, D, xe.dtype.itemsize,
                                         w1.dtype.itemsize)),
        interpret=interpret,
        name="moe_gemm",
    )(expert_ids, block_valid, xr, w1, w3, w2)
    return out.reshape(B, E, C, D)[:, :, :C0]


# pallas_call has no autodiff rule; training differentiates the MoE FFN,
# so wrap the kernel with a custom VJP whose backward is jax.vjp of the
# pure-jnp einsum formulation (recompute, flash-style). ``counts`` is an
# integer operand → float0 cotangent.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _moe_gemm(static, xe, counts, w1, w3, w2):
    act, bm, bf, interpret = static
    return _moe_gemm_call(xe, counts, w1, w3, w2, act=act, bm=bm, bf=bf,
                          interpret=interpret)


def _moe_gemm_fwd(static, xe, counts, w1, w3, w2):
    out = _moe_gemm(static, xe, counts, w1, w3, w2)
    return out, (xe, counts, w1, w3, w2)


def _moe_gemm_bwd(static, res, dout):
    act = static[0]
    xe, counts, w1, w3, w2 = res
    f = functools.partial(moe_gemm_ref, counts=counts, act=act)
    _, vjp = jax.vjp(lambda x_, a_, b_, c_: f(x_, w1=a_, w3=b_, w2=c_),
                     xe, w1, w3, w2)
    dxe, dw1, dw3, dw2 = vjp(dout.astype(xe.dtype))
    zero_counts = np.zeros(counts.shape, jax.dtypes.float0)
    return dxe, zero_counts, dw1, dw3, dw2


_moe_gemm.defvjp(_moe_gemm_fwd, _moe_gemm_bwd)


def moe_gemm_pallas(xe, counts, w1, w3, w2, *, act: str = "silu",
                    block_rows=None, block_f=None,
                    interpret: bool = False):
    """Grouped-expert gated FFN over capacity blocks (differentiable).

    xe: (B, E, C, D) sort-dispatched token blocks; counts: (B, E) int32
    valid rows per block (rank-ordered prefix); w1, w3: (E, D, F);
    w2: (E, F, D).  Returns (B, E, C, D) in ``xe.dtype``.

    Raises NotImplementedError when C/F are not divisible by an
    explicit row/F block.
    """
    B, E, C, D = xe.shape
    E2, D2, F = w1.shape
    if (E2, D2) != (E, D) or w3.shape != (E, D, F) or w2.shape != (E, F, D):
        raise ValueError(f"inconsistent expert weight shapes "
                         f"{w1.shape}/{w3.shape}/{w2.shape} for xe {xe.shape}")
    bm = block_rows if block_rows is not None else \
        (128 if _padded(C) % 128 == 0 else _padded(C))
    bf = block_f if block_f is not None else \
        (512 if F % 512 == 0 else F)
    resolve_moe_act(act)      # raise ValueError early on bad names
    return _moe_gemm((act, bm, bf, interpret), xe, counts, w1, w3, w2)
