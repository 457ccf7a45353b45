"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and record memory / cost / roofline artifacts.

The dry-run needs 512 placeholder CPU devices to build the (2, 16, 16)
production mesh.  Run as a script it sets them up itself, before the
first backend use locks the device count, and pins the CPU backend so
it never takes an accelerator another process holds.  A caller that
imports ``run_cell`` starts its process with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=512``.

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    python -m repro.launch.dryrun --all                  # 40-cell sweep
    python -m repro.launch.dryrun --all --multi-pod      # (2,16,16) sweep
"""
import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import ASSIGNED, get_config, list_archs
from repro.core.config import SHAPES, StepKind, shape_applicable
from repro.core.roofline import analyze, memory_analysis_dict
from repro.launch.cells import Cell, SkipCell, build_cell
from repro.launch.mesh import mesh_chips
from repro.parallel import sharding as shd
from repro.parallel.plan import resolve_plan

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             plan=None, rules=None, run_overrides=None, out_dir=OUT_DIR,
             tag: str = "", verbose: bool = True):
    if plan is None:
        plan = resolve_plan("multi-pod" if multi_pod else "single-pod")
    mesh = plan.mesh()
    rules = rules if rules is not None else plan.rules
    mesh_name = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    chips = mesh_chips(mesh)
    t0 = time.time()

    with shd.use_sharding(mesh, rules):
        cell = build_cell(arch, shape_name, mesh, rules=rules,
                          run_overrides=run_overrides)
        with mesh:
            lowered = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
            ).lower(*cell.abstract_args)
            compiled = lowered.compile()

    mem = memory_analysis_dict(compiled)
    cost_raw = compiled.cost_analysis() or {}
    if isinstance(cost_raw, (list, tuple)):   # jax<0.5 returns [dict]
        cost_raw = cost_raw[0] if cost_raw else {}
    cost = dict(cost_raw)
    hlo = compiled.as_text()

    cfg = cell.cfg
    model_flops = _model_flops(cfg, cell.shape)
    rep = analyze(arch=arch, shape=shape_name, mesh_name=mesh_name,
                  chips=chips, cost=cost, hlo_text=hlo,
                  model_flops=model_flops,
                  tokens_per_step=cell.shape.tokens_per_step,
                  memory_stats=mem, ideal_bytes=_ideal_bytes(cell),
                  notes=tag)

    if verbose:
        print(f"== {arch} × {shape_name} on {mesh_name} "
              f"({time.time()-t0:.1f}s compile+lower) ==")
        print("memory_analysis:", json.dumps(mem, indent=1))
        print(f"cost_analysis: flops={cost.get('flops', 0):.3e} "
              f"bytes={cost.get('bytes accessed', 0):.3e}")
        print(f"collectives: { {k: f'{v:.3e}' for k, v in rep.coll_breakdown.items()} }")
        print(f"terms[s]: compute={rep.compute_s:.4f} memory={rep.memory_s:.4f} "
              f"collective={rep.collective_s:.4f}  dominant={rep.dominant}")
        print(f"useful_ratio={rep.useful_ratio:.3f} "
              f"roofline_fraction={rep.roofline_fraction():.3f}")

    out_dir.mkdir(parents=True, exist_ok=True)
    stem = f"{arch}_{shape_name}_{mesh_name}" + (f"_{tag}" if tag else "")
    (out_dir / f"{stem}.json").write_text(rep.to_json())
    return rep


def _ideal_bytes(cell) -> float:
    """Irreducible HBM traffic per step: every weight byte + (decode) every
    live cache byte read once, cache updates written once."""
    import math

    def nbytes(t):
        return sum(math.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(t))
    if cell.shape.kind.value == "train":
        # fwd+bwd touch params ~3x (read, read, write) + adam state 3x
        state_abs = cell.abstract_args[0]
        return 1.0 * nbytes(state_abs.params) * 3 + \
            nbytes(state_abs.opt.m) * 3
    params_abs = cell.abstract_args[0]
    total = float(nbytes(params_abs))
    if cell.shape.kind.value == "decode":
        total += nbytes(cell.abstract_args[1])          # the cache
    return total


def _model_flops(cfg, shape) -> float:
    """6·N·D for train; 2·N·tokens for single forward (prefill/decode)."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == StepKind.TRAIN:
        return 6.0 * n_active * shape.tokens_per_step
    return 2.0 * n_active * shape.tokens_per_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--plan", default=None,
                    help="auto | single-pod | multi-pod | JSON plan file | "
                         "pod=2,data=16,model=16 (overrides --multi-pod)")
    ap.add_argument("--include-paper-archs", action="store_true",
                    help="also run gpt3-175b / llama2-70b extras")
    args = ap.parse_args(argv)
    if args.plan and args.both_meshes:
        ap.error("--plan overrides the mesh choice; it cannot be combined "
                 "with --both-meshes (run twice with different --plan)")

    cells = []
    if args.all:
        archs = list_archs(assigned_only=not args.include_paper_archs)
        for a in archs:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures, skips = [], []
    for multi_pod in meshes:
        for arch, shape_name in cells:
            cfg = get_config(arch)
            ok, why = shape_applicable(cfg, SHAPES[shape_name])
            if not ok:
                skips.append((arch, shape_name, why))
                print(f"-- SKIP {arch} × {shape_name}: {why}")
                continue
            try:
                plan = None
                if args.plan:
                    plan = resolve_plan(args.plan, cfg,
                                        chips=jax.device_count(),
                                        shape=SHAPES[shape_name])
                    if plan.scorecard is not None:
                        print(plan.scorecard)
                run_cell(arch, shape_name, multi_pod=multi_pod, plan=plan)
            except Exception as e:  # noqa: BLE001 - report and continue
                traceback.print_exc()
                failures.append((arch, shape_name, multi_pod, repr(e)))

    print(f"\n=== dry-run summary: {len(failures)} failures, "
          f"{len(skips)} documented skips ===")
    for f in failures:
        print("FAIL:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    jax.config.update("jax_platforms", "cpu")
    sys.exit(main())
