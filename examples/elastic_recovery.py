"""Elastic training demo: survive a mid-run node loss (paper §8.7).

Runs on 8 fake CPU devices (4 "nodes" of 2 GPUs).  A Table-13-style
fault schedule is drawn from :mod:`repro.sched.faults` and adapted onto
the run by :class:`FaultMonitor`; when the GPU fault lands, the runtime
drains at the next checkpoint boundary, re-plans the parallelism layout
over the 6 surviving devices (full auto re-plan — compare
``--recovery shrink``), reshards the checkpoint onto the new mesh, and
resumes with the data cursor intact.

    PYTHONPATH=src python examples/elastic_recovery.py [--steps 16]
"""
import argparse
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
sys.path.insert(0, "src")

import tempfile                                            # noqa: E402

from repro.configs import reduced_config                   # noqa: E402
from repro.core.config import (OptimizerConfig, RunConfig,  # noqa: E402
                               ShapeConfig, StepKind)
from repro.core.telemetry import RunTelemetry              # noqa: E402
from repro.parallel.plan import resolve_plan               # noqa: E402
from repro.train.runtime import (DevicePool, FaultMonitor,  # noqa: E402
                                 LoggingCallback, Trainer)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--recovery", default="replan",
                    choices=("replan", "shrink"))
    args = ap.parse_args()

    cfg = reduced_config("gemma-2b")
    shape = ShapeConfig("t", 32, 8, StepKind.TRAIN)
    run_cfg = RunConfig(model=cfg, shape=shape,
                        optimizer=OptimizerConfig(lr=3e-4, warmup_steps=2,
                                                  total_steps=args.steps))

    # Table 13 fault arrivals, compressed onto this short run: one gpu
    # fault mid-run with drain (advance-notice) semantics
    monitor = FaultMonitor.from_pairs([(args.steps // 2, 1)])

    plan = resolve_plan("data=4,model=2")
    print(plan.describe(), flush=True)
    telem = RunTelemetry(None, cfg, shape, n_chips=plan.chips)
    trainer = Trainer(run_cfg, plan=plan,
                      pool=DevicePool(gpus_per_node=2),
                      callbacks=[LoggingCallback(every=2)], telemetry=telem,
                      ckpt_dir=tempfile.mkdtemp(), ckpt_every=4,
                      fault_monitor=monitor, recovery=args.recovery)
    report = trainer.run(args.steps)

    print("\nstate machine:",
          " -> ".join(s.value for s in report.state_history))
    for r in report.recoveries:
        print(f"recovery @{r.resume_step}: {r.component} on node {r.node}, "
              f"{r.chips_before}->{r.chips_after} chips via {r.policy} "
              f"({r.plan_before} -> {r.plan_after}), lost {r.lost_steps} "
              f"steps, {r.time_to_recover_s:.2f}s")
        if r.modeled_step_s_before and r.modeled_step_s_after:
            print(f"  modeled step: {r.modeled_step_s_before:.2e}s -> "
                  f"{r.modeled_step_s_after:.2e}s")
    print(f"loss {report.losses[0]:.4f} -> {report.losses[-1]:.4f} over "
          f"{report.steps_run} steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
