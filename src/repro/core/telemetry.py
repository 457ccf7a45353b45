"""Run telemetry (paper §8.7 lesson 3: "observability and user control —
real-time telemetry enables human-in-the-loop optimization").

``RunTelemetry`` streams JSONL step records (loss, grad-norm, step time,
tokens/s, MFU against the published peak of the device kind it ran on,
None where that kind has no entry) — the signals the paper's
practitioners watched to decide the cancellations that dominate
Observation 1 — plus utilization summaries compatible with the cluster
simulator's per-job records (Observation 3's methodology).  ``span``
marks the host phases of a serving tick or a train step in the JAX
profiler's trace, on the clock of the device's ops.
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from repro.core.config import ModelConfig, ShapeConfig, chip_peaks


def span(name: str, **attrs) -> TraceAnnotation:
    """A host span ``name`` in the JAX profiler's trace, with ``attrs``
    as its arguments.  ``Engine`` and ``Trainer`` put them around the
    phases of a tick and of a step (``engine.*``, ``trainer.*``; a name
    ending in ``_sync`` is one in which the host waits on the device).
    Nearly free when no trace is being taken: about a microsecond, no
    device work and no sync."""
    return TraceAnnotation(name, **attrs)


class RunTelemetry:
    def __init__(self, path: Optional[str], cfg: ModelConfig,
                 shape: ShapeConfig, n_chips: int = 1,
                 device_kind: Optional[str] = None):
        self.path = pathlib.Path(path) if path else None
        self.cfg = cfg
        self.shape = shape
        self.n_chips = n_chips
        self.peaks = chip_peaks(device_kind)
        self._t_last = time.time()
        self._fh = self.path.open("a") if self.path else None
        self.records = []
        self.recovery_records: List[Dict] = []
        self.flops_per_token = cfg.flops_per_token()

    def step(self, step: int, metrics: Dict):
        now = time.time()
        dt = now - self._t_last
        self._t_last = now
        tokens = self.shape.tokens_per_step
        rec = {
            "step": step,
            "time": now,
            "step_s": dt,
            "loss": float(metrics.get("loss", float("nan"))),
            "grad_norm": float(metrics.get("grad_norm", float("nan"))),
            "tokens_per_s": tokens / max(dt, 1e-9),
            "mfu": None if self.peaks is None else
                   (self.flops_per_token * tokens / max(dt, 1e-9))
                   / (self.n_chips * self.peaks.peak_bf16_flops),
        }
        if "dropped_frac" in metrics:
            # MoE capacity-truncation drop rate (0 for dense models; a
            # sustained nonzero value means the capacity factor is tight)
            rec["dropped_frac"] = float(metrics["dropped_frac"])
        self.records.append(rec)
        if self._fh:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        return rec

    def recovery(self, step: int, *, time_to_recover_s: float,
                 lost_steps: int, chips_before: int, chips_after: int,
                 policy: str, component: str = "", plan: str = "") -> Dict:
        """Record one fault-recovery cycle (§8.7: drain → re-plan →
        resharded resume).  ``lost_steps`` is the work rolled back (0 for
        a drained soft fault); ``time_to_recover_s`` spans re-plan +
        resharded restore.  Subsequent MFU is computed against the
        surviving chip count."""
        rec = {
            "event": "recovery",
            "step": step,
            "time": time.time(),
            "time_to_recover_s": time_to_recover_s,
            "lost_steps": lost_steps,
            "lost_tokens": lost_steps * self.shape.tokens_per_step,
            "chips_before": chips_before,
            "chips_after": chips_after,
            "policy": policy,
            "component": component,
            "plan": plan,
        }
        self.recovery_records.append(rec)
        self.n_chips = chips_after
        self._t_last = time.time()      # don't bill recovery to a step
        if self._fh:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        return rec

    def recovery_summary(self) -> Dict:
        """Aggregate recovery stats: events, total downtime, lost work."""
        if not self.recovery_records:
            return {}
        return {
            "recoveries": len(self.recovery_records),
            "total_recovery_s": sum(r["time_to_recover_s"]
                                    for r in self.recovery_records),
            "total_lost_steps": sum(r["lost_steps"]
                                    for r in self.recovery_records),
            "chips_final": self.recovery_records[-1]["chips_after"],
        }

    def utilization_summary(self, low_threshold_mfu: float = 0.05) -> Dict:
        """Observation-3-style per-job stats from the step records
        (MFU fields only where the device's peaks are known)."""
        if not self.records:
            return {}
        out = {"steps": len(self.records)}
        mfus = [r["mfu"] for r in self.records if r["mfu"] is not None]
        if mfus:
            out["mean_mfu"] = sum(mfus) / len(mfus)
            out["low_util_fraction"] = sum(
                1 for m in mfus if m < low_threshold_mfu) / len(mfus)
        return out

    def close(self):
        if self._fh:
            self._fh.close()


# ---------------------------------------------------------------------------
def percentile(xs: List[float], q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100]); nan when empty."""
    if not xs:
        return float("nan")
    import numpy as np
    return float(np.percentile(xs, q))


class ServingTelemetry:
    """Request-level serving telemetry (the inference-side twin of
    ``RunTelemetry``): one JSONL record per finished/cancelled request
    with queue wait, TTFT, and TPOT, plus a percentile summary — the
    signals the paper's small-interactive-job-dominated workload mix
    (§7, Observation 2) turns into the serving SLOs a production
    deployment watches.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = pathlib.Path(path) if path else None
        self._fh = self.path.open("a") if self.path else None
        self.records: List[Dict] = []

    def record_request(self, result) -> Dict:
        """Record a ``repro.serving.GenerationResult`` (duck-typed: needs
        .rid, .state.value, .done_reason, .metrics.as_dict())."""
        rec = {
            "rid": result.rid,
            "state": result.state.value,
            "done_reason": result.done_reason,
            "time": time.time(),
            **result.metrics.as_dict(),
        }
        self.records.append(rec)
        if self._fh:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        return rec

    def summary(self) -> Dict:
        """p50/p99 TTFT / TPOT / queue wait (ms) over finished requests."""
        fin = [r for r in self.records if r["state"] == "finished"]

        def pick(key):
            return [r[key] for r in fin if r.get(key) is not None]

        ttft, tpot, qw = pick("ttft_s"), pick("tpot_s"), pick("queue_wait_s")
        out = {
            "requests": len(self.records),
            "finished": len(fin),
            "cancelled": sum(r["state"] == "cancelled" for r in self.records),
            "output_tokens": sum(r["output_tokens"] for r in self.records),
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p99_ms": percentile(ttft, 99) * 1e3,
            "tpot_p50_ms": percentile(tpot, 50) * 1e3,
            "tpot_p99_ms": percentile(tpot, 99) * 1e3,
            "queue_wait_p50_ms": percentile(qw, 50) * 1e3,
            "queue_wait_p99_ms": percentile(qw, 99) * 1e3,
        }
        # cache-memory accounting (paged-KV serving; absent on records
        # from engines predating it — duck-typed .get keeps old callers)
        alloc, used = pick("kv_allocated_bytes"), pick("kv_used_bytes")
        if alloc:
            out["kv_allocated_mb"] = sum(alloc) / 1e6
            out["kv_used_mb"] = sum(used) / 1e6
            out["kv_utilization"] = (sum(used) / sum(alloc)) if sum(alloc) \
                else 0.0
        pft = pick("prefilled_tokens")
        if pft:
            out["prefilled_tokens"] = sum(pft)
        pct = pick("prefix_cached_tokens")
        if any(pct):
            out["prefix_cached_tokens"] = sum(pct)
        return out

    def close(self):
        if self._fh:
            self._fh.close()
