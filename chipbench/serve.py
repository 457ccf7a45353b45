"""Serve cells: the program's ``Engine`` under a traffic mix.

Set-up makes the weights on the device from the seed, builds the
``Engine`` and warms every prefill bucket the mix can use and the decode
tick.  The window then drives ``Engine.submit`` / ``Engine.step`` on the
harness's clock (``time.perf_counter``, which the engine is given too):

* open loop: a request is submitted once it is due; its time to first
  token runs from when it was due, so a stall delays every request
  behind it.  After the window, stepping goes on (no new requests) until
  every request due in the window has its first token, at most
  ``DRAIN_S``: a late first token is late, one that never comes fails.
* closed loop: each client sends its next request when its previous one
  finished; the first wave is admitted before the window opens.

Once the window has closed and the peak memory is read, the engine is
freed and a sample of finished requests (drawn from the seed, with the
longest in it) is replayed through the float32 reference: the number
compared is the widest gap by which a served token's reference logit
lies below the reference's best at that position (greedy traffic).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import traffic
from chipbench.bench import (Cell, GcClock, arch_module, compile_counter,
                             percentile, program_config, span)

DRAIN_S = 60.0
SAMPLE_MIN_TOKENS = 512           # served tokens the check compares, at least
SAMPLE_MAX_REQUESTS = 8


@dataclasses.dataclass
class Served:
    rid: int
    due: float                     # harness clock
    prompt: np.ndarray
    max_new: int
    client: int = 0
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    submitted: Optional[float] = None


@dataclasses.dataclass
class ServeRecord:
    """What the per-layer readers see of a serve run."""
    config: Dict
    peak: Dict
    chips: int
    t0: float
    t1: float
    requests: List[Served]         # submitted in the window
    program: Dict[int, object]     # rid -> the program's RequestMetrics
    ticks: List[tuple]             # (time, live rows, keys summed) per tick
    trace: object = None           # TraceSummary in a --trace 1 run
    gc: Optional[GcClock] = None   # collections in the window

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t0 <= t < self.t1


def engine_sizes(config: Dict, mix: Dict) -> Dict:
    """The engine's sizes for a mix: a contiguous cache whose rows each
    hold the mix's longest request (rounded up to ``block_size``), as
    many rows as the KV budget holds, at most ``max_slots``."""
    sv = config["serve"]
    if sv["kv_cache"] != "contiguous":
        raise ValueError(f"serve cells run contiguous KV, not "
                         f"{sv['kv_cache']!r}")
    chunk, bs = sv["prefill_chunk"], sv["block_size"]
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    cache_len = -(-longest // bs) * bs
    return {"cache_len": cache_len, "prefill_chunk": chunk,
            "prefill_len": min(sv["prefill_len"], cache_len,
                               -(-mix["prompt"]["max"] // chunk) * chunk),
            "slots": min(sv["max_slots"], sv["kv_pool_tokens"] // cache_len)}


def _engine(cell: Cell, model, params):
    from repro.serving import Engine
    return Engine(model, params, clock=time.perf_counter,
                  **engine_sizes(cell.config, cell.mix))


def _sampling(max_new: int):
    from repro.serving import SamplingParams
    return SamplingParams(max_new_tokens=int(max_new), temperature=0.0,
                          eos_token=None)


def setup(cell: Cell, seed: int):
    """Weights from the seed, the engine, and every shape warmed."""
    import jax.numpy as jnp
    from repro.models.model import build_model
    cfg = program_config(cell.config)
    model = build_model(cfg, remat="none")
    arch = arch_module(cell.config)
    params = arch.make_params(model.abstract_params(jnp.bfloat16),
                              traffic.seed32(seed), jnp.bfloat16)
    engine = _engine(cell, model, params)
    rng = traffic.rng_for(seed, 99)
    for n in traffic.warmup_lengths(cell.mix, engine.prefill_chunk,
                                    engine.prefill_len):
        # a prompt that fills its bucket and one that is padded take
        # different programs
        for m in (n, n - 1):
            engine.submit(rng.integers(2, cfg.vocab_size, m, dtype=np.int32),
                          _sampling(2))
    engine.run(max_ticks=10_000)
    engine.reap()
    return engine, params


def _submit(engine, r: Served, on_token):
    with span("submit"):
        r.rid = engine.submit(r.prompt, _sampling(r.max_new),
                              on_token=on_token)
        r.submitted = time.perf_counter()


def _step(engine, ticks):
    n0 = engine.ticks
    with span("engine_step"):
        engine.step()
    if engine.ticks != n0:
        lengths = engine.pool.lengths
        ticks.append((time.perf_counter(), int(np.count_nonzero(lengths)),
                      int(lengths.sum())))


def window(cell: Cell, engine, seed: int, seconds: float,
           trace_dir=None) -> tuple:
    """Drive the window; returns (ServeRecord, compile events in it)."""
    import jax
    vocab = cell.config["vocab_size"]
    by_rid: Dict[int, Served] = {}
    ticks: List[tuple] = []
    reqs: List[Served] = []
    clients: List[List[Served]] = []
    follow: List[Served] = []

    def on_token(rid, tok, last):
        r = by_rid[rid]
        r.stamps.append(time.perf_counter())
        r.tokens.append(int(tok))
        if last and clients and clients[r.client]:
            follow.append(clients[r.client].pop(0))

    def track(r: Served):
        _submit(engine, r, on_token)
        by_rid[r.rid] = r
        reqs.append(r)

    kind = cell.mix["kind"]
    if kind == "closed_loop":
        clients = [[Served(-1, 0.0, q.prompt, q.max_new, client=c)
                    for q in queue] for c, queue in enumerate(
                        traffic.closed_loop(cell.mix, seed, vocab))]
        for queue in clients:
            track(queue.pop(0))
        while engine.queue:                        # first wave admitted
            _step(engine, ticks)
    elif kind == "open_loop":
        due = traffic.open_loop(cell.mix, seed, seconds, vocab)
    else:
        raise ValueError(f"serve cells take open or closed loops, not {kind}")

    counter = compile_counter()
    c0 = counter.events
    with span("ready"):           # the first annotation sets up the tracer
        pass
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    win = span("window")
    win.__enter__()
    gc_clock = GcClock().__enter__()
    t0 = time.perf_counter()
    t1 = t0 + seconds
    if kind == "open_loop":
        pending = [Served(-1, t0 + q.due_s, q.prompt, q.max_new) for q in due]
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t1:
                break
            while i < len(pending) and pending[i].due <= now:
                track(pending[i])
                i += 1
            if engine.queue or engine.pool.num_active:
                _step(engine, ticks)
            else:
                nxt = pending[i].due if i < len(pending) else t1
                with span("wait_arrival"):
                    time.sleep(max(0.0, min(nxt, t1) - time.perf_counter()))
        for r in pending[i:]:                      # due before the close
            track(r)
    else:
        while time.perf_counter() < t1:
            while follow:
                track(follow.pop(0))
            _step(engine, ticks)
    gc_clock.__exit__()
    win.__exit__(None, None, None)
    in_window = counter.events - c0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    if kind == "open_loop":
        t_stop = time.perf_counter() + DRAIN_S
        while (any(not r.stamps for r in reqs)
               and time.perf_counter() < t_stop):
            _step(engine, ticks)
    program = {r.rid: engine.requests[r.rid].metrics for r in reqs}
    return ServeRecord(cell.config, None, cell.chips, t0, t1, reqs, program,
                       ticks, gc=gc_clock), in_window


# ---------------------------------------------------------------------------
def ttfts(rec: ServeRecord) -> List[float]:
    """First token minus due time of every request due in the window;
    inf for one that never got a first token."""
    return [(r.stamps[0] - r.due) if r.stamps else float("inf")
            for r in rec.requests if r.due >= rec.t0]


def end_to_end(rec: ServeRecord) -> Dict[str, float]:
    """Every serve metric the harness takes on its own clock."""
    ttft = ttfts(rec)
    gaps = [b - a for r in rec.requests
            for a, b in zip(r.stamps, r.stamps[1:]) if rec.t0 <= b < rec.t1]
    emitted = sum(rec.t0 <= t < rec.t1 for r in rec.requests
                  for t in r.stamps)
    out = {"output_tokens_per_s": emitted / rec.window_s}
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    return out


def lateness_ms(rec: ServeRecord) -> float:
    """How late the generator submitted open-loop requests: the largest
    delay from due to submitted (a starved generator reads high)."""
    late = [r.submitted - r.due for r in rec.requests if r.due >= rec.t0]
    return max(late) * 1e3 if late else 0.0


def host_stalls(rec: ServeRecord) -> str:
    """The window's longest time between two decode ticks (a prefill,
    a wait for an arrival or a stall), when it began, the time spent in
    garbage collection, and the KV tokens held per tick on average."""
    ticks = [x for x in rec.ticks if rec.t0 <= x[0] < rec.t1]
    t = [x[0] for x in ticks]
    gap, at = max(((b - a, a - rec.t0) for a, b in zip(t, t[1:])),
                  default=(0.0, 0.0))
    kv = float(np.mean([x[2] for x in ticks])) if ticks else 0.0
    g = rec.gc
    return (f"longest time between ticks {gap * 1e3:.1f} ms at "
            f"{at:.2f} s; gc {g.total * 1e3:.1f} ms in all, longest "
            f"{g.longest * 1e3:.1f} ms; KV tokens held per tick {kv:.0f}")


def attempted_failed(rec: ServeRecord) -> tuple:
    """Open loop: requests due in the window, and those with no first
    token by the end of the drain.  Closed loop: requests that ran in
    the window; none can fail but by an error."""
    due = [r for r in rec.requests if r.due >= rec.t0]
    if due:
        return len(due), sum(not r.stamps for r in due)
    ran = [r for r in rec.requests if any(rec.in_window(t) for t in r.stamps)]
    return len(ran), 0


def sample(rec: ServeRecord, seed: int) -> List[Served]:
    """Finished requests to compare: the longest, then others drawn from
    the seed until SAMPLE_MIN_TOKENS served tokens or
    SAMPLE_MAX_REQUESTS requests."""
    done = [r for r in rec.requests if len(r.tokens) == r.max_new]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    picks, n = [longest], len(longest.tokens)
    for j in traffic.rng_for(seed, 3).permutation(len(done)):
        if n >= SAMPLE_MIN_TOKENS or len(picks) >= SAMPLE_MAX_REQUESTS:
            break
        if done[j] is not longest:
            picks.append(done[j])
            n += len(done[j].tokens)
    return picks


def logit_gaps(arch, params, config: Dict, picks: List[Served],
               control: bool = False) -> tuple:
    """(widest gap of a served token below the reference's best, the same
    for the token the fp8 control puts first, or None) over every served
    token of ``picks``."""
    prog, ctrl = [], []
    for r in picks:
        S, n = len(r.prompt), len(r.tokens)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        rows = np.arange(S - 1, S - 1 + n)
        ref = arch.logits(params, config, seq, rows)
        best = ref.max(-1)
        prog.append(float(np.max(best - ref[np.arange(n), r.tokens])))
        if control:
            low = arch.logits(params, config, seq, rows, low=True)
            ctrl.append(float(np.max(
                best - ref[np.arange(n), low.argmax(-1)])))
    return max(prog), (max(ctrl) if control else None)


def free(engine) -> None:
    """Drop the engine's cache and programs; the weights stay with the
    caller for the reference."""
    engine.cache = None
    engine.params = None
    gc.collect()
