#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json``.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled window.  The
last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, last, ``checks``: each number
compared with the reference beside its limit); the checks are also the
last lines on stderr.  Exits non-zero with no result when JAX finds no
TPU or fewer chips than the cell asks for, or outside a checkout that
holds the program.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench" / "trace"
CACHE_DIR = ROOT / ".jax_cache"


def _paths() -> bool:
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chipbench: no program under {src}; run from a checkout of "
              "the repository", file=sys.stderr)
        return False
    for p in (str(ROOT), str(src)):
        if p not in sys.path:
            sys.path.insert(0, p)
    # libtpu logs under /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".chipbench" / "tpu_logs"))
    return True


def devices_for(chips: int):
    """The first ``chips`` TPU devices, or None."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return None
    return devs[:chips]


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every program."""
    import jax
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    from repro.core.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def reduce_trace(trace_dir):
    from chipbench.tracing import reduce_file
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    summary = reduce_file(files[-1])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not _paths():
        return 2
    from chipbench.bench import find_cell
    cell = find_cell(args.workload)
    devs = devices_for(cell.chips)
    if devs is None:
        return 1
    enable_cache()
    from chipbench import cells
    return cells.run(cell, devs, seed=args.seed, seconds=args.seconds,
                     trace_dir=TRACE_DIR if args.trace else None,
                     t_start=T_START, reduce_trace=reduce_trace)


if __name__ == "__main__":
    sys.exit(main())
