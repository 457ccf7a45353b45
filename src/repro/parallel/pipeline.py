"""Pipeline parallelism over a ``pipe`` mesh axis (paper C2 — the MLPerf
GPT-3 recipe runs PP=16, VP=6).

Implementation: GPipe-style microbatch pipelining inside ``shard_map``.
Each device holds the stacked params of its stage (layers sharded over
``pipe``); activations move stage-to-stage with ``collective_permute``
inside a ``lax.scan`` over ticks.  Differentiating through the scan +
ppermute gives the backward pipeline automatically (the transpose of a
permute is the reverse permute), so one code path serves fwd and bwd.

Virtual pipelining (VP) runs the V chunk rounds sequentially (each round
is a full GPipe sweep over its chunk of layers).  The interleaved-1F1B
schedule the paper's Megatron config uses reduces the bubble from
(P-1)/(M+P-1) per round to (P-1)/(P·V·M'); we model that analytically in
benchmarks/mlperf_gpt3.py and note the schedule gap in DESIGN.md.

The bubble is structural: of the ``M + P - 1`` ticks each device computes
during ``M`` — tests assert both the tick count and exact equivalence
with the unpipelined model.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params_stacked, x_micro: jax.Array,
                   *, axis: str = "pipe") -> jax.Array:
    """Run microbatches through P pipeline stages. Call INSIDE shard_map.

    stage_fn(stage_params, x) -> x          (one stage's layers)
    params_stacked: this device's stage params (leading layer dim already
    sliced to the stage's layers by the shard_map in_spec).
    x_micro: (M, mb, ...) microbatched input, replicated across stages.

    Returns (M, mb, ...) outputs as produced by the LAST stage (valid on
    every device after the final gather)."""
    n_stage = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    M = x_micro.shape[0]
    ticks = M + n_stage - 1
    fwd = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    state = jnp.zeros_like(x_micro[0])
    outputs = jnp.zeros_like(x_micro)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (when in range)
        mb_idx = jnp.clip(t, 0, M - 1)
        inject = x_micro[mb_idx]
        x_in = jnp.where(stage == 0, inject, state)
        y = stage_fn(params_stacked, x_in)
        # last stage emits microbatch t - (P - 1)
        out_idx = t - (n_stage - 1)
        valid_out = (stage == n_stage - 1) & (out_idx >= 0)
        outputs = jax.lax.cond(
            valid_out,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(out_idx, 0, M - 1), 0),
            lambda o: o, outputs)
        state = jax.lax.ppermute(y, axis, fwd)
        return (state, outputs), None

    (state, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                       jnp.arange(ticks))
    # broadcast last stage's outputs to all stages (so loss is global):
    # only the last stage holds non-zero outputs, so a psum is a broadcast
    outputs = jnp.where(stage == n_stage - 1, outputs,
                        jnp.zeros_like(outputs))
    return jax.lax.psum(outputs, axis)


def make_pipelined_loss(mesh: Mesh, stage_fn: Callable, loss_fn: Callable,
                        *, num_micro: int, axis: str = "pipe",
                        vp: int = 1):
    """Builds loss(params_stacked, batch) with layers sharded over `axis`.

    params_stacked: full stacked layer params (L, ...); shard_map slices
    L/P per stage.  With vp > 1 the layer dim is split into V sequential
    rounds (chunk c holds layers [c·L/V, (c+1)·L/V) sharded over stages).

    loss_fn(final_activations, batch) -> scalar (computed at last stage,
    psum'd)."""

    n_stage = mesh.shape[axis]

    def _inner(params, x, batch_rest):
        # x: (M, mb, ...) microbatches (replicated across pipe axis)
        if vp > 1:
            # local leaf: (1, V, Lc, ...) — chunk c = this stage's layers of
            # virtual round c
            h = x
            for c in range(vp):
                p_c = jax.tree.map(lambda a: a[0, c], params)
                h = pipeline_apply(stage_fn, p_c, h, axis=axis)
        else:
            h = pipeline_apply(stage_fn, params, x, axis=axis)
        return loss_fn(h, batch_rest)

    pspec = P(axis)     # stage dim sharded over pipe
    xspec = P()         # microbatches replicated
    inner = jax.shard_map(_inner, mesh=mesh,
                          in_specs=(pspec, xspec, xspec),
                          out_specs=P(), check_vma=False)

    if vp == 1:
        return inner

    def prepped(params, x, batch_rest):
        # global layer order 0..L-1 -> (P, V, Lc, ...): virtual round c on
        # stage s holds layers [c·L/V + s·Lc, c·L/V + (s+1)·Lc)
        def prep(a):
            L = a.shape[0]
            assert L % (vp * n_stage) == 0, (L, vp, n_stage)
            lc = L // (vp * n_stage)
            a = a.reshape((vp, n_stage, lc) + a.shape[1:])
            return jnp.swapaxes(a, 0, 1)
        return inner(jax.tree.map(prep, params), x, batch_rest)

    return prepped


def split_microbatches(batch: Dict, num_micro: int) -> Dict:
    def r(a):
        return a.reshape((num_micro, a.shape[0] // num_micro) + a.shape[1:])
    return jax.tree.map(r, batch)
