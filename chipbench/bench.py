"""What every cell shares: the manifest, the cell's files, the device,
compile counting, percentiles, the per-layer readers and the result line."""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "chipbench"


def load_json(path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration, mix and limits."""
    name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def find_cell(name: str, manifest: Optional[Dict] = None) -> Cell:
    manifest = manifest or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = next(c for c in manifest["configs"] if c["name"] == w["config"])

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(ROOT / cfg["file"]),
        mix=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{name}.json"),
        end_to_end=[m for m in manifest["end_to_end"] if mine(m)],
        per_layer=[m for m in manifest["per_layer"] if mine(m)])


def program_config(config: Dict):
    """The program's ModelConfig with the sizes of the configuration file."""
    import dataclasses as dc
    from repro.configs import get_config
    base = get_config(config["program_model"])
    return dc.replace(
        base, num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]))


def leaf_name(path) -> str:
    """``layers/attn/wq`` for a pytree key path."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def arch_module(config: Dict):
    return importlib.import_module(f"chipbench.arch.{config['arch']}")


class CompileCounter:
    """Counts the programs JAX lowers (each then compiles or loads from
    the persistent cache), from its own monitoring hooks."""

    def __init__(self):
        import jax
        self.events = 0
        self.seconds = 0.0

        def on_event(name, secs, **_):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.events += 1
                self.seconds += secs
        jax.monitoring.register_event_duration_secs_listener(on_event)


_COUNTER: List[CompileCounter] = []


def compile_counter() -> CompileCounter:
    """The process's one counter (JAX's listeners cannot be removed)."""
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


class GcClock:
    """Seconds the interpreter spent in garbage collection while on,
    in all and in its longest single collection."""

    def __init__(self):
        self.total = self.longest = 0.0
        self._t = None

    def _cb(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None:
            dt, self._t = now - self._t, None
            self.total += dt
            self.longest = max(self.longest, dt)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


def span(name: str):
    """A host span in the profiler's trace (free when nobody traces)."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def percentile(values, q: float) -> float:
    """The q-th percentile of all values, linear between the two order
    statistics around it (numpy's default); a missing value counts as
    inf, so a tail that reaches one is inf."""
    v = sorted(float(x) for x in values)
    if not v:
        return math.nan
    pos = q / 100.0 * (len(v) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(v[hi]):
        return math.inf
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def check(cell: Cell, name: str, value: float) -> Check:
    return Check(name, float(value), float(cell.limits[name]["limit"]))


# ---------------------------------------------------------------------------
def load_reader(metric: str):
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_metrics(cell: Cell, run) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of the cell that its reader found."""
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict, device: Dict, checks: List[Check],
                breakdown: Optional[Dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)


def log(*a):
    print(*a, file=sys.stderr, flush=True)
