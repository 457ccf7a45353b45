"""Serve-side metric arithmetic: open-loop timing from the due time,
tails over every request and every gap."""
import time
from types import SimpleNamespace

import numpy as np

import repro.serving  # noqa: F401  (imported by a real set-up)
from chipbench import serve
from chipbench_tiny import tiny_cell


class StallingEngine:
    """Engine stand-in: admits all queued requests each tick, emits one
    token per running request per tick of ``tick_s``, and stalls once
    for ``stall_s`` at tick ``stall_at``."""

    def __init__(self, tick_s=0.01, stall_at=10, stall_s=0.3):
        self.tick_s, self.stall_at, self.stall_s = tick_s, stall_at, stall_s
        self.queue, self.running, self.requests = [], [], {}
        self.ticks = 0
        self.pool = SimpleNamespace(num_active=0, lengths=np.zeros(4, int))

    def submit(self, prompt, sampling, on_token=None):
        rid = len(self.requests)
        r = SimpleNamespace(rid=rid, left=sampling.max_new_tokens,
                            cb=on_token, metrics=SimpleNamespace(
                                t_submit=time.perf_counter(),
                                t_prefill_start=None, prefilled_tokens=None))
        self.requests[rid] = r
        self.queue.append(r)
        return rid

    def step(self):
        if self.ticks == self.stall_at:
            time.sleep(self.stall_s)
        time.sleep(self.tick_s)
        for r in self.queue:
            r.metrics.t_prefill_start = time.perf_counter()
            r.metrics.prefilled_tokens = 8
        self.running += self.queue
        self.queue = []
        for r in list(self.running):
            r.left -= 1
            r.cb(r.rid, 7, r.left == 0)
            if r.left == 0:
                self.running.remove(r)
        self.ticks += 1
        self.pool.num_active = len(self.running)


def _chat(rate):
    cell = tiny_cell("qwen3-32b-serve.chat")
    cell.mix["rate_per_s"] = rate
    return cell


def test_a_stall_delays_the_requests_behind_it():
    cell = _chat(40.0)
    eng = StallingEngine(stall_at=20, stall_s=0.3)
    rec, _ = serve.window(cell, eng, seed=3, seconds=1.0)
    stamps = sorted({t for r in rec.requests for t in r.stamps})
    gap, s0 = max((b - a, a) for a, b in zip(stamps, stamps[1:]))
    assert gap > 0.25                              # the stall
    behind = [r for r in rec.requests if s0 < r.due < s0 + 0.1]
    assert behind
    # timed from when they were due, they waited out the stall; timed
    # from their submission (after the stall) they would not show it
    assert min(r.stamps[0] - r.due for r in behind) > 0.15
    assert max(r.stamps[0] - r.submitted for r in behind) < 0.1
    early = [r.stamps[0] - r.due for r in rec.requests if r.due < s0 - 0.1]
    assert early and max(early) < 0.1
    e2e = serve.end_to_end(rec)
    ttft = [r.stamps[0] - r.due for r in rec.requests]
    assert np.isclose(e2e["ttft_p50_ms"], np.median(ttft) * 1e3)
    attempted, failed = serve.attempted_failed(rec)
    assert attempted == len(rec.requests) and failed == 0


def _record(stamps_by_request, due=0.0):
    reqs = [serve.Served(i, due, np.zeros(4, np.int32), len(s),
                         stamps=list(s)) for i, s in
            enumerate(stamps_by_request)]
    return serve.ServeRecord({}, None, 1, 0.0, 100.0, reqs, {}, [])


def test_tails_are_over_every_gap_not_a_median_of_requests():
    fast = np.arange(0, 10, 0.01)                 # 999 gaps of 10 ms
    slow = np.arange(0, 10, 0.5)                  # 19 gaps of 500 ms
    e2e = serve.end_to_end(_record([fast, slow, slow.copy()]))
    gaps = np.concatenate([np.diff(fast), np.diff(slow), np.diff(slow)])
    assert np.isclose(e2e["itl_p95_ms"], np.percentile(gaps, 95) * 1e3)
    assert e2e["itl_p95_ms"] < 20                # a median of per-request
    # tails would read 500 ms


def test_a_request_without_a_first_token_counts_as_missing():
    rec = _record([[0.1] * 1] * 4 + [[]] * 6)
    e2e = serve.end_to_end(rec)
    assert e2e["ttft_p50_ms"] == float("inf")
    assert serve.attempted_failed(rec) == (10, 6)


def test_throughput_counts_tokens_in_the_window_only():
    rec = _record([[50.0, 99.0, 101.0]])
    assert serve.end_to_end(rec)["output_tokens_per_s"] == 2 / 100.0


def test_host_stalls_names_the_longest_time_between_ticks():
    import gc
    from chipbench.bench import GcClock
    rec = _record([[1.0]])
    rec.ticks = [(t, 1, 1) for t in (0.5, 1.0, 1.1, 3.1, 3.2)]
    with GcClock() as rec.gc:
        gc.collect()
    assert rec.gc.total > 0 and rec.gc.longest <= rec.gc.total
    assert "2000.0 ms at 1.10 s" in serve.host_stalls(rec)
