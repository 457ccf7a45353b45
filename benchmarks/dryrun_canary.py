"""Dry-run artifact canary — catches silent HLO-lowering regressions.

The JSON artifacts under ``experiments/dryrun/`` record the modeled cost
of every compiled (arch × shape × mesh) cell (while-aware HLO FLOPs /
bytes / collectives).  Model-code changes that silently regress lowering
(e.g. a cache write that turns a contiguous dynamic-update-slice into a
scatter) show up as artifact drift long before any hardware run.  This
suite regenerates every committed artifact through the ParallelPlan path
in a 512-fake-device subprocess and FAILS on any field drift, so the
regression is caught at PR time instead of being committed as noise.

    PYTHONPATH=src python -m benchmarks.run --only canary
"""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import tempfile
import time

from benchmarks.common import emit, fake_device_env

ART_DIR = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "dryrun"

_CHILD = r"""
import sys, pathlib
sys.path.insert(0, "src")
from repro.launch.dryrun import run_cell
from repro.parallel.plan import resolve_plan

arch, shape, mesh_name, out = sys.argv[1:5]
dims = mesh_name.split("x")
spec = (f"pod={dims[0]},data={dims[1]},model={dims[2]}" if len(dims) == 3
        else f"data={dims[0]},model={dims[1]}")
run_cell(arch, shape, plan=resolve_plan(spec),
         out_dir=pathlib.Path(out), verbose=False)
print("DONE")
"""


def _parse_stem(stem: str):
    """'gemma-2b_decode_32k_2x16x16' -> (arch, shape, mesh)."""
    from repro.core.config import SHAPES
    parts = stem.split("_")
    if len(parts) < 3:
        return None
    mesh, shape = parts[-1], "_".join(parts[-3:-1])
    arch = "_".join(parts[:-3])
    if shape not in SHAPES or not arch:
        return None
    return arch, shape, mesh


def _diff(old: dict, new: dict):
    keys = sorted(set(old) | set(new))
    return [(k, old.get(k), new.get(k)) for k in keys
            if old.get(k) != new.get(k)]


def run():
    artifacts = sorted(ART_DIR.glob("*.json"))
    assert artifacts, f"no dry-run artifacts under {ART_DIR}"
    drifted = []
    for art in artifacts:
        parsed = _parse_stem(art.stem)
        if parsed is None:
            print(f"# canary: skipping tagged/unparseable {art.name}")
            continue
        arch, shape, mesh = parsed
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory() as tmp:
            out = subprocess.run(
                [sys.executable, "-c", _CHILD, arch, shape, mesh, tmp],
                capture_output=True, text=True, cwd=".",
                env=fake_device_env(512), timeout=1200)
            if "DONE" not in out.stdout:
                emit(f"canary.{art.stem}", 0.0,
                     f"FAILED:{out.stderr[-160:]}")
                raise RuntimeError(out.stderr[-2000:])
            regen = json.loads((pathlib.Path(tmp) / art.name).read_text())
        us = (time.perf_counter() - t0) * 1e6
        diffs = _diff(json.loads(art.read_text()), regen)
        emit(f"canary.{art.stem}", us,
             "clean" if not diffs else
             "DRIFT:" + "|".join(k for k, _, _ in diffs))
        if diffs:
            drifted.append((art.name, diffs))
    if drifted:
        lines = []
        for name, diffs in drifted:
            for k, old, new in diffs:
                lines.append(f"  {name}: {k}: {old!r} -> {new!r}")
        raise AssertionError(
            "dry-run artifacts drifted — a model/sharding change altered "
            "the compiled HLO cost; fix the regression or regenerate the "
            "artifacts deliberately (python -m repro.launch.dryrun):\n"
            + "\n".join(lines))


if __name__ == "__main__":
    run()
