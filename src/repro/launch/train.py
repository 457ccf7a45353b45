"""End-to-end training driver — a thin CLI over the elastic runtime.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b \
        --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

The step loop itself lives in :class:`repro.train.runtime.Trainer`: an
event-driven state machine (INIT → RUNNING → DRAINING → REPLANNING →
RESTORING → RUNNING) that wires every substrate together — config
registry -> model -> data pipeline (packed, cursor-checkpointed) ->
train_step (AdamW, clip, remat, grad compression) -> checkpoint manager
(async, atomic, drain barrier) -> telemetry (steps + recoveries) — and
survives node loss by re-planning the parallelism layout over the
surviving devices and resuming from a resharded checkpoint (paper §8.7).

``--restore`` resumes exactly (including the data-pipeline cursor).
``--plan`` picks the parallelism layout (repro.parallel.plan).
``--fault-at step:node`` injects device-loss events (fake devices;
``--gpus-per-node`` sets the failure-domain size) and ``--recovery``
picks the policy: ``replan`` (full auto re-plan) or ``shrink``
(legacy data-axis shrink).
"""
from __future__ import annotations

import argparse
import sys

import jax

from repro.configs import get_config, reduced_config
from repro.core.compile_cache import enable_compilation_cache
from repro.core.config import (OptimizerConfig, ParallelConfig, RunConfig,
                               ShapeConfig, StepKind)
from repro.core.telemetry import RunTelemetry
from repro.parallel.plan import resolve_plan
from repro.train.runtime import (DevicePool, FaultMonitor, LoggingCallback,
                                 Trainer)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen3-32b")
    # BooleanOptionalAction: plain store_true with default=True silently
    # made full configs unreachable (--no-reduced would not exist)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (default; --no-reduced = full size)")
    ap.add_argument("--plan", default=None,
                    help="parallelism plan: auto | single-pod | multi-pod | "
                         "JSON plan file | pod=2,data=16,model=16 "
                         "(default: no sharding — single-process run)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--telemetry", default="",
                    help="JSONL path for step + recovery telemetry")
    # -- elastic runtime knobs (§8.7 fault-recovery loop) ----------------
    ap.add_argument("--recovery", default="replan",
                    choices=("replan", "shrink"),
                    help="post-fault policy: full auto re-plan vs legacy "
                         "data-axis shrink")
    ap.add_argument("--fault-at", default="",
                    help="inject node losses: step:node[,step:node...] "
                         "(drain semantics; prefix step with '!' for a "
                         "hard fault that rolls back to the last ckpt)")
    ap.add_argument("--gpus-per-node", type=int, default=0,
                    help="failure-domain size for --fault-at "
                         "(default: all devices = one node)")
    return ap


def parse_fault_spec(spec: str) -> FaultMonitor:
    """``step:node[,step:node...]`` with optional ``!step`` = hard."""
    events = []
    for part in spec.split(","):
        s, _, n = part.partition(":")
        s = s.strip()
        hard = s.startswith("!")
        events.append((int(s.lstrip("!")), int(n), hard))
    mon = FaultMonitor()
    for step, node, hard in events:
        mon.inject(step, node, component="operator", hard=hard)
    return mon


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compilation_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    shape = ShapeConfig("train", args.seq, args.batch, StepKind.TRAIN)
    run_cfg = RunConfig(
        model=cfg, shape=shape,
        parallel=ParallelConfig(microbatch=args.microbatch,
                                remat=args.remat),
        optimizer=OptimizerConfig(lr=args.lr, warmup_steps=10,
                                  total_steps=args.steps,
                                  grad_compression=args.grad_compression),
        seed=args.seed)

    plan = None
    if args.plan:
        plan = resolve_plan(args.plan, cfg, chips=jax.device_count(),
                            shape=shape)
        if plan.is_trivial:
            plan = None                 # single device: nothing to shard
        else:
            print(plan.describe(), flush=True)

    pool = DevicePool(gpus_per_node=args.gpus_per_node)
    telem = RunTelemetry(args.telemetry or None, cfg, shape,
                         n_chips=plan.chips if plan else len(jax.devices()),
                         device_kind=jax.devices()[0].device_kind)
    trainer = Trainer(
        run_cfg, plan=plan, pool=pool,
        callbacks=[LoggingCallback(every=args.log_every)],
        telemetry=telem,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        restore=args.restore,
        fault_monitor=parse_fault_spec(args.fault_at) if args.fault_at
        else None,
        recovery=args.recovery)
    report = trainer.run(args.steps)

    summ = telem.utilization_summary()
    if "mean_mfu" in summ:
        print(f"telemetry: mean_mfu={summ['mean_mfu']:.4f} "
              f"low_util_fraction={summ['low_util_fraction']:.2f}")
    rsum = telem.recovery_summary()
    if rsum:
        print(f"recoveries: {rsum['recoveries']} "
              f"(lost {rsum['total_lost_steps']} steps, "
              f"{rsum['total_recovery_s']:.2f}s downtime, "
              f"{rsum['chips_final']} chips final)")
    ok = report.improved
    print(f"final: loss {report.losses[0]:.4f} -> {report.losses[-1]:.4f} "
          f"({'improved' if ok else 'NOT improved'})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
