"""Pipeline-parallel training demo (paper C2: GPT-3 runs PP=16 VP=6) —
GPipe schedule with virtual stages over fake devices, verified exactly
against the unpipelined model.

    PYTHONPATH=src python examples/pipeline_parallel.py --vp 2
"""
import argparse
import os
import sys

if __name__ == "__main__" and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.pipeline import make_pipelined_loss
from repro.parallel.plan import PipelineSpec, resolve_plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vp", type=int, default=2)
    ap.add_argument("--micro", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    L, D = 8, 32
    # staging comes from the plan: 4 pipeline stages over the pipe axis
    plan = resolve_plan("pipe=4").replace(pipeline=PipelineSpec(
        stages=4, vp=args.vp, microbatches=args.micro))
    P_ = plan.pipeline.stages
    mesh = plan.mesh()
    print(plan.describe())
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.standard_normal((L, D, D)) * 0.2, jnp.float32)

    def stage_fn(p, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, p)
        return h

    def loss_fn(h, target):
        return jnp.mean((h - target) ** 2)

    ploss = make_pipelined_loss(mesh, stage_fn, loss_fn,
                                num_micro=plan.pipeline.microbatches,
                                axis=plan.pipeline.axis,
                                vp=plan.pipeline.vp)
    gfn = jax.jit(jax.value_and_grad(ploss))

    x = jnp.asarray(rng.standard_normal((args.micro, 2, D)), jnp.float32)
    tgt = jnp.asarray(rng.standard_normal((args.micro, 2, D)) * 0.1,
                      jnp.float32)
    w = ws
    for i in range(args.steps):
        loss, g = gfn(w, x, tgt)
        w = w - 0.1 * g
        if i % 5 == 0:
            print(f"step {i:3d} pipelined loss {float(loss):.5f}")

    # exact-equivalence check vs unpipelined
    ref = loss_fn(stage_fn(ws, x.reshape(-1, D)).reshape(x.shape), tgt)
    got = ploss(ws, x, tgt)
    print(f"pipelined == unpipelined: {bool(jnp.allclose(ref, got, atol=1e-6))} "
          f"(bubble ticks: {args.micro + P_ - 1} for {args.micro} micro)")


if __name__ == "__main__":
    main()
