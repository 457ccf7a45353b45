"""Rail-aware hierarchical data parallelism (paper C1/C6) — explicit
shard_map training on a (pod, data) mesh with two-level gradient
all-reduce and optional cross-pod compression.

Run with fake devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/hierarchical_dp.py --compress bf16
"""
import argparse
import os
import sys

if __name__ == "__main__" and "--respawned" not in sys.argv and \
        "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, "src")

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.collectives import hierarchical_psum
from repro.parallel.plan import resolve_plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--plan", default="pod=2,data=4",
                    help="ParallelPlan spec (pod axis = spine hop)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--respawned", action="store_true")
    args = ap.parse_args()

    # The plan carries mesh AND collective schedule: the pod axis is the
    # spine hop, so the gradient reduction below pre-reduces over the
    # intra-pod rail axis before crossing it (paper C1).
    plan = resolve_plan(args.plan)
    sched = plan.collectives
    mesh = plan.mesh()
    dp_axes = tuple(a for a in plan.axis_names if a in ("pod", "data"))
    n_dp = plan.chips
    assert dp_axes, "this example data-parallelizes: plan needs pod/data"
    print(plan.describe())
    D, H, C = 64, 128, 16
    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.standard_normal((D, H)) * 0.05, jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((H, C)) * 0.05, jnp.float32),
    }

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"])
        logits = h @ p["w2"]
        return -jnp.mean(jax.nn.log_softmax(logits)[
            jnp.arange(x.shape[0]), y])

    def step(p, x, y):
        # per-device local grads, then the paper's hierarchical reduction:
        # reduce-scatter intra-rail -> cross-pod all-reduce (1/N bytes,
        # optionally compressed) -> all-gather intra-rail
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        if sched.intra_axis is not None:
            g = jax.tree.map(functools.partial(
                hierarchical_psum, intra_axis=sched.intra_axis,
                inter_axis=sched.inter_axis, compress=args.compress), g)
        else:                       # no rail axis to pre-reduce over
            for ax in dp_axes:
                g = jax.tree.map(
                    functools.partial(jax.lax.psum, axis_name=ax), g)
        g = jax.tree.map(lambda v: v / n_dp, g)
        for ax in dp_axes:
            loss = jax.lax.pmean(loss, ax)
        p = jax.tree.map(lambda w, gw: w - 0.3 * gw, p, g)
        return p, loss

    sharded_step = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(dp_axes), P(dp_axes)),
        out_specs=(P(), P()), check_vma=False))

    losses = []
    w_true = rng.standard_normal((D, C))      # fixed ground-truth mapping
    for i in range(args.steps):
        x = jnp.asarray(rng.standard_normal((64, D)), jnp.float32)
        y = jnp.asarray(np.argmax(np.asarray(x) @ w_true, -1), jnp.int32)
        params, loss = sharded_step(params, x, y)
        losses.append(float(loss))
        if i % 10 == 0:
            print(f"step {i:3d} loss {losses[-1]:.4f}")
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(compress={args.compress})")
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
