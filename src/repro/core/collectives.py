"""Rail-aware hierarchical collectives (paper C1, §4.2).

The paper's fabric confines most collective bytes to the high-bandwidth
intra-pod rails and crosses the spine with pre-reduced data (hierarchical
NCCL algorithms over the rail-optimized leaf-spine).  The TPU adaptation
(DESIGN.md §2) expresses the same decomposition with shard_map +
jax.lax collectives over the (pod, data, model) mesh:

    all-reduce(x; pod×data) ≡ reduce-scatter(intra data rail)
                              → all-reduce(cross-pod, 1/N of bytes)
                              → all-gather(intra data rail)

Cross-pod traffic drops from ``bytes`` to ``bytes / data_size`` — the hop
the paper engineered ECN/DCQCN around is exactly the narrow one here.
The cross-pod leg optionally compresses to bf16/int8+EF (C6-inspired,
optim/compression.py).

These functions are used by the explicit-DP training driver
(examples/hierarchical_dp.py), the interconnect benchmark (Table 14) and
the distributed tests.  The pjit path gets the same effect implicitly via
GSPMD; here the schedule is explicit and auditable.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim.compression import compress_grads, decompress_grads


def hierarchical_psum(x: jax.Array, *, intra_axis: str = "data",
                      inter_axis: Optional[str] = "pod",
                      compress: str = "none") -> jax.Array:
    """Two-level all-reduce from INSIDE shard_map.

    reduce-scatter over the intra (rail) axis, all-reduce the 1/N shard
    over the inter (spine) axis, all-gather back over intra."""
    n_intra = jax.lax.axis_size(intra_axis)
    if x.size % n_intra != 0:
        # fall back to flat psum for tiny/ragged tensors
        y = jax.lax.psum(x, intra_axis)
        return jax.lax.psum(y, inter_axis) if inter_axis else y

    shape = x.shape
    flat = x.reshape(n_intra, -1)
    shard = jax.lax.psum_scatter(flat, intra_axis, scatter_dimension=0,
                                 tiled=False)
    if inter_axis is not None:
        if compress == "bf16":
            shard = jax.lax.psum(shard.astype(jnp.bfloat16),
                                 inter_axis).astype(x.dtype)
        elif compress == "int8":
            scale = jnp.maximum(jnp.max(jnp.abs(shard)), 1e-12) / 127.0
            q = jnp.round(shard / scale).astype(jnp.int8)
            # int8 summation overflows; widen to int32 on the wire-equivalent
            deq = jax.lax.psum(q.astype(jnp.int32), inter_axis)
            scale_sum = jax.lax.psum(scale, inter_axis) / jax.lax.axis_size(
                inter_axis)
            shard = (deq.astype(jnp.float32) * scale_sum).astype(x.dtype)
        else:
            shard = jax.lax.psum(shard, inter_axis)
    out = jax.lax.all_gather(shard, intra_axis, axis=0, tiled=False)
    return out.reshape(shape)


def ring_all_reduce(x: jax.Array, axis: str) -> jax.Array:
    """Explicit ring all-reduce via collective_permute (reduce-scatter ring
    + all-gather ring) — the RingAllReduce pattern the paper's ECN tuning
    was validated against (§8.2).  For benchmarking/teaching; numerically
    identical to psum."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]

    chunks = list(jnp.split(x.reshape(n, -1), n, axis=0))
    buf = jnp.stack([c[0] for c in chunks])          # (n, chunk)

    def rs_step(i, buf):
        # each step: send chunk (idx - i) mod n, receive and accumulate
        send_idx = (idx - i) % n
        sent = buf[send_idx]
        recv = jax.lax.ppermute(sent, axis, perm_fwd)
        tgt = (idx - i - 1) % n
        return buf.at[tgt].add(recv)

    buf = jax.lax.fori_loop(0, n - 1, rs_step, buf)

    def ag_step(i, buf):
        send_idx = (idx + 1 - i) % n
        sent = buf[send_idx]
        recv = jax.lax.ppermute(sent, axis, perm_fwd)
        tgt = (idx - i) % n
        return buf.at[tgt].set(recv)

    buf = jax.lax.fori_loop(0, n - 1, ag_step, buf)
    return buf.reshape(x.shape)


def make_hierarchical_grad_reduce(mesh: Mesh, compress: str = "none"):
    """Returns grads -> all-reduced grads, as a shard_map over the mesh.

    Used by the explicit-DP driver: per-device grads (replicated-spec
    inputs with per-device values) are reduced intra-rail first, then
    cross-pod on 1/N bytes."""
    axes = mesh.axis_names
    inter = "pod" if "pod" in axes else None
    intra = "data"

    def _reduce(g):
        return jax.tree.map(
            functools.partial(hierarchical_psum, intra_axis=intra,
                              inter_axis=inter, compress=compress), g)

    spec = P()  # grads enter replicated-per-device (manual DP)
    return jax.shard_map(_reduce, mesh=mesh, in_specs=(spec,),
                         out_specs=spec, check_vma=False)
