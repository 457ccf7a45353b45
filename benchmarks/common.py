"""Shared benchmark utilities: timing, CSV emission, hardware constants."""
from __future__ import annotations

import os
import time
from typing import Callable, Dict, List

import jax
import numpy as np

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.3f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def fake_device_env(devices: int) -> Dict[str, str]:
    """Environment for a child process on ``devices`` fake CPU devices.

    Pinned to the CPU backend: an accelerator belongs to one process at a
    time, and the parent (or another process) may hold it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time (µs) of a jax callable (blocks on result)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


# paper hardware (H100 SXM) constants for the analytic models
H100_BF16_DENSE = 989.4e12      # dense bf16 TFLOP/s (no sparsity)
H100_FP8_DENSE = 1978.9e12      # dense fp8 TFLOP/s — the paper's MFU basis
                                 # ("dense Tensor Core peak of 1,979 TFLOPS")
H100_FP64 = 33.5e12             # per paper Table 5 context (SXM fp64 w/ FMA)
H100_TF32 = 494.7e12
NVLINK_BW = 450e9               # per-direction per GPU (NVLink4)
