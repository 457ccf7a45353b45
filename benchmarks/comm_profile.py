"""Paper Table 10 — communication profile of the pipelined training step.

The paper profiles GPT-3 at 32/64 nodes with PyTorch Profiler and finds
NCCL time dominated by PP SendRecv (91.2%), with RS/AG (TP) and AR (DP)
minor.  We reproduce the *profile shape* structurally: lower the
framework's own pipeline-parallel loss (parallel/pipeline.py, reduced
GPT-3 stage) on an 8-stage mesh in a subprocess, parse the compiled HLO,
and report per-collective byte shares — collective-permute is the
SendRecv analog.
"""
from __future__ import annotations

import json
import subprocess
import sys
import time

from benchmarks.common import emit, fake_device_env

_CHILD = r"""
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.parallel.pipeline import make_pipelined_loss
from repro.parallel.plan import resolve_plan
from repro.core.hlo_cost import analyze_hlo

L, D, F = 8, 128, 512
M, mb, S = 8, 2, 64
mesh = resolve_plan("pipe=8").mesh()
import numpy as np
ws = {
    "w1": jnp.asarray(np.random.randn(L, D, F), jnp.float32) * 0.05,
    "w2": jnp.asarray(np.random.randn(L, F, D), jnp.float32) * 0.05,
}
def stage_fn(p, x):
    def body(h, w):
        return h + jnp.tanh(h @ w["w1"]) @ w["w2"], None
    h, _ = jax.lax.scan(body, x, p)
    return h
def loss_fn(h, _):
    return jnp.mean(h ** 2)
ploss = make_pipelined_loss(mesh, stage_fn, loss_fn, num_micro=M)
x = jnp.zeros((M, mb, S, D), jnp.float32)
grad = jax.grad(lambda w: ploss(w, x, jnp.zeros(())))
lowered = jax.jit(grad).lower(ws)
hlo = lowered.compile().as_text()
t = analyze_hlo(hlo)
print("RESULT " + json.dumps({k: v for k, v in t.coll_bytes.items()}))
"""


def run():
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, "-c", _CHILD],
                         capture_output=True, text=True, cwd=".",
                         env=fake_device_env(8),
                         timeout=600)
    us = (time.perf_counter() - t0) * 1e6
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    if not line:
        emit("comm_profile.table10", us, f"FAILED:{out.stderr[-200:]}")
        raise RuntimeError(out.stderr[-2000:])
    coll = json.loads(line[0][len("RESULT "):])
    total = sum(coll.values()) or 1.0
    shares = {k: v / total for k, v in coll.items()}
    sendrecv = shares.get("collective-permute", 0.0)
    emit("comm_profile.table10", us,
         f"sendrecv_share={sendrecv:.3f};paper_sendrecv_share=0.912;"
         + ";".join(f"{k}={v:.3f}" for k, v in sorted(shares.items())))
    return shares


if __name__ == "__main__":
    run()
