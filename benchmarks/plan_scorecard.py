"""Parallelism-planner scorecard matrix (repro.parallel.plan).

For ≥3 registered configs × {single-pod (256 chips), multi-pod (512)} the
auto-planner enumerates (pod, data, model[, pipe]) layouts, scores them
with the fabric analytical model, and must pick a layout whose modeled
cross-pod spine traffic is never worse — and for at least one config
strictly lower — than the naive hard-coded production mesh (flat
collective schedule).  A subprocess additionally demonstrates the HLO
probe: the top finalists for an 8-chip plan are actually lowered and
re-ranked with while-aware HLO cost totals (core.hlo_cost).

The EP case covers the expert mesh axis: for mixtral-8x22b at 512 chips
the planner must pick a plan with a real expert axis, and among the
layouts that pay cross-pod spine traffic (pipe intra-pod, DP or EP
spanning the pod boundary) the best expert-axis layout must model
strictly fewer cross-pod bytes/step than the best dense-folded one —
expert grads stay rail-local while a dense fold all-reduces them over
the spine.  A second probe subprocess lowers EP finalists on 8 fake
devices and re-ranks them with compiled HLO cost.
"""
from __future__ import annotations

import json
import subprocess
import sys
import time

from benchmarks.common import emit, fake_device_env

CONFIGS = ("qwen3-32b", "mixtral-8x22b", "gemma3-4b")
SCENARIOS = (("single-pod", 256), ("multi-pod", 512))

_PROBE_CHILD = r"""
import sys, json, tempfile, time
sys.path.insert(0, "src")
from repro.configs import reduced_config, register_config
from repro.core.config import ShapeConfig, StepKind
from repro.parallel.plan import plan_parallelism

cfg = reduced_config("qwen3-32b")
register_config("plan-probe", cfg, cfg)
shape = ShapeConfig("probe", 64, 8, StepKind.TRAIN)
with tempfile.TemporaryDirectory() as cache:
    t0 = time.perf_counter()
    plan = plan_parallelism(cfg, chips=8, shape=shape, hlo_probe=True,
                            probe_arch="plan-probe", probe_shape=shape,
                            probe_top_k=2, probe_cache_dir=cache)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    plan2 = plan_parallelism(cfg, chips=8, shape=shape, hlo_probe=True,
                             probe_arch="plan-probe", probe_shape=shape,
                             probe_top_k=2, probe_cache_dir=cache)
    t_warm = time.perf_counter() - t0
rows = [{"layout": str(s.layout), "hlo_coll": s.hlo_coll_bytes,
         "hlo_flops": s.hlo_flops}
        for s in plan.scorecard.scores if s.hlo_coll_bytes is not None]
rows2 = [{"layout": str(s.layout), "hlo_coll": s.hlo_coll_bytes,
          "hlo_flops": s.hlo_flops}
         for s in plan2.scorecard.scores if s.hlo_coll_bytes is not None]
assert rows == rows2, (rows, rows2)   # cached probes == measured probes
print("RESULT " + json.dumps({"chosen": str(plan.score.layout),
                              "probed": rows, "t_cold_s": t_cold,
                              "t_warm_s": t_warm}))
"""


_EP_PROBE_CHILD = r"""
import sys, json, tempfile
sys.path.insert(0, "src")
from repro.configs import reduced_config, register_config
from repro.core.config import ShapeConfig, StepKind
from repro.parallel.plan import plan_parallelism

cfg = reduced_config("mixtral-8x22b")
register_config("plan-probe-moe", cfg, cfg)
shape = ShapeConfig("probe", 64, 8, StepKind.TRAIN)
with tempfile.TemporaryDirectory() as cache:
    plan = plan_parallelism(cfg, chips=8, shape=shape, hlo_probe=True,
                            probe_arch="plan-probe-moe", probe_shape=shape,
                            probe_top_k=2, probe_cache_dir=cache)
rows = [{"layout": str(s.layout), "expert": s.layout.expert,
         "hlo_coll": s.hlo_coll_bytes, "hlo_flops": s.hlo_flops}
        for s in plan.scorecard.scores if s.hlo_coll_bytes is not None]
print("RESULT " + json.dumps({"chosen": str(plan.score.layout),
                              "chosen_expert": plan.score.layout.expert,
                              "probed": rows}))
"""


def _fmt(layout) -> str:
    """CSV-safe compact layout spelling."""
    return str(layout).replace("⊗", "x").replace(", ", "/") \
        .replace("(", "").replace(")", "")


def run():
    from repro.configs import get_config
    from repro.parallel.plan import plan_parallelism

    strict_wins = 0
    show = None
    for arch in CONFIGS:
        cfg = get_config(arch)
        for scenario, chips in SCENARIOS:
            t0 = time.perf_counter()
            plan = plan_parallelism(cfg, chips=chips,
                                    objective="min_cross_pod_bytes")
            us = (time.perf_counter() - t0) * 1e6
            chosen, naive = plan.score, plan.scorecard.naive
            assert chosen.cross_pod_bytes <= naive.cross_pod_bytes, (
                f"{arch}/{scenario}: planner chose MORE cross-pod traffic "
                f"than the naive mesh ({chosen.cross_pod_bytes:.3e} > "
                f"{naive.cross_pod_bytes:.3e})")
            if chosen.cross_pod_bytes < naive.cross_pod_bytes:
                strict_wins += 1
                if show is None:
                    show = plan.scorecard
            emit(f"plan.{arch}.{scenario}", us,
                 f"layout={_fmt(chosen.layout)};"
                 f"xpod_GB={chosen.cross_pod_bytes / 1e9:.2f};"
                 f"naive_xpod_GB={naive.cross_pod_bytes / 1e9:.2f};"
                 f"step_s={chosen.step_s:.3f};"
                 f"naive_step_s={naive.step_s:.3f}")
    assert strict_wins >= 1, (
        "planner never strictly beat the naive mesh on cross-pod bytes")
    if show is not None:
        print(show)

    # EP: the expert axis must carry the MoE config and relieve the spine
    cfg = get_config("mixtral-8x22b")
    t0 = time.perf_counter()
    plan = plan_parallelism(cfg, chips=512)
    us = (time.perf_counter() - t0) * 1e6
    chosen = plan.score
    assert chosen.layout.expert > 1, (
        f"planner folded mixtral experts into dense axes: {chosen.layout}")
    # cross-pod shapes: layouts whose DP/EP group actually spans the pod
    # boundary (pipe stays intra-pod, so its tiny boundary bytes can't
    # hide the gradient traffic this comparison is about)
    xpod = [s for s in plan.scorecard.scores
            if s.layout.pipe == 1 and s.cross_pod_bytes > 0]
    ep_best = min((s for s in xpod if s.layout.expert > 1),
                  key=lambda s: s.cross_pod_bytes)
    dense_best = min((s for s in xpod if s.layout.expert == 1),
                     key=lambda s: s.cross_pod_bytes)
    assert ep_best.cross_pod_bytes < dense_best.cross_pod_bytes, (
        f"EP layout {ep_best.layout} models {ep_best.cross_pod_bytes:.3e} "
        f"cross-pod bytes/step, not better than dense-folded "
        f"{dense_best.layout} at {dense_best.cross_pod_bytes:.3e}")
    dense_fast = min((s for s in plan.scorecard.scores
                      if s.layout.expert == 1), key=lambda s: s.step_s)
    emit("plan.moe_ep.mixtral-8x22b", us,
         f"layout={_fmt(chosen.layout)};step_s={chosen.step_s:.3f};"
         f"dense_step_s={dense_fast.step_s:.3f};"
         f"ep_xpod_GB={ep_best.cross_pod_bytes / 1e9:.2f};"
         f"dense_xpod_GB={dense_best.cross_pod_bytes / 1e9:.2f}")

    # EP HLO probe: lower expert-axis finalists for real on fake devices
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, "-c", _EP_PROBE_CHILD],
                         capture_output=True, text=True, cwd=".",
                         env=fake_device_env(8),
                         timeout=900)
    us = (time.perf_counter() - t0) * 1e6
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    if not line:
        emit("plan.moe_ep.hlo_probe", us, f"FAILED:{out.stderr[-200:]}")
        raise RuntimeError(out.stderr[-2000:])
    res = json.loads(line[0][len("RESULT "):])
    assert any(r["expert"] > 1 and r["hlo_flops"] > 0
               for r in res["probed"]), res   # an EP finalist really lowered
    assert res["chosen_expert"] > 1, res      # re-rank kept the EP plan
    emit("plan.moe_ep.hlo_probe", us,
         f"chosen={_fmt(res['chosen'])};"
         + ";".join(f"{_fmt(r['layout'])}:coll={r['hlo_coll']:.3e}"
                    for r in res["probed"]))

    # HLO probe: lower the finalists for real and re-rank on compiled cost
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, "-c", _PROBE_CHILD],
                         capture_output=True, text=True, cwd=".",
                         env=fake_device_env(8),
                         timeout=900)
    us = (time.perf_counter() - t0) * 1e6
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    if not line:
        emit("plan.hlo_probe", us, f"FAILED:{out.stderr[-200:]}")
        raise RuntimeError(out.stderr[-2000:])
    res = json.loads(line[0][len("RESULT "):])
    assert len(res["probed"]) == 2 and all(
        r["hlo_flops"] > 0 for r in res["probed"]), res
    assert res["t_warm_s"] < res["t_cold_s"], res   # cache skips recompiles
    emit("plan.hlo_probe", us,
         f"chosen={_fmt(res['chosen'])};"
         f"cold_s={res['t_cold_s']:.2f};warm_s={res['t_warm_s']:.2f};"
         + ";".join(f"{_fmt(r['layout'])}:coll={r['hlo_coll']:.3e}"
                    for r in res["probed"]))


if __name__ == "__main__":
    run()
