"""The program's own host spans in a profiler trace, and chip 0's idle
time split by them.

``Engine`` and ``Trainer`` mark the phases of a tick and of a step with
host spans (``engine.*``, ``trainer.*``; ``repro.core.telemetry.span``).
A span whose name ends in ``_sync`` is one in which the host waits on
the device.  This reads them, beside the harness's ``bench.*`` spans,
from the trace that ``chipbench/tracing.py`` reduces, on the same clock,
and adds what that reduction leaves out:

* ``idle_by_span``: seconds of chip 0's idle time in the window by the
  span that covers it: each idle gap is cut at span edges and each piece
  goes to the shortest span (program or harness) covering it, or to
  ``NO_SPAN``.  The values sum to the idle time.
* ``host_idle_s``: the part under a program span that is not a ``_sync``
  span (Python work of the loop while the chip waits); ``sync_idle_s``:
  the part under a ``_sync`` span (the host blocked in the runtime:
  transfers, allocation, defragmentation).  Idle time under harness
  spans only, or none, is in neither.
* ``span_s``: the durations of each program span that starts in the
  window.
* ``gaps``: the longest idle gaps, each named by the span that holds the
  largest piece of it in that split.  (The reducer names a gap by the
  span covering most of it, so a gap across two phases of a tick goes
  to the harness's ``bench.engine_step`` around them.)

    python3 chipbench/spans.py <trace.xplane.pb>

prints the split of one trace as JSON.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
from collections import defaultdict
from typing import Dict, List, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench.tracing import (_DEVICE, WINDOW_SPAN, _events,  # noqa: E402
                               merge)

PROGRAM_PREFIXES = ("engine.", "trainer.")
HARNESS_PREFIX = "bench."
SYNC_SUFFIX = "_sync"
NO_SPAN = "no span"

Span = Tuple[float, float, str]          # start ns, end ns, name


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def is_sync(name: str) -> bool:
    return is_program(name) and name.endswith(SYNC_SUFFIX)


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    idle_s: float                        # chip 0
    idle_by_span: Dict[str, float]
    span_s: Dict[str, List[float]]
    gaps: List[Tuple[str, float]]        # chip 0's longest idle gaps

    @property
    def host_idle_s(self) -> float:
        return sum(v for k, v in self.idle_by_span.items()
                   if is_program(k) and not is_sync(k))

    @property
    def sync_idle_s(self) -> float:
        return sum(v for k, v in self.idle_by_span.items() if is_sync(k))

    def as_dict(self) -> Dict:
        w = self.window_s

        def pct(x):
            return 100.0 * x / w if w > 0 else None

        return {"window_s": w, "idle_share": pct(self.idle_s),
                "host_idle_share": pct(self.host_idle_s),
                "sync_idle_share": pct(self.sync_idle_s),
                "idle_by_span": dict(sorted(self.idle_by_span.items(),
                                            key=lambda kv: -kv[1])),
                "spans": {k: {"count": len(v), "total_s": sum(v),
                              "p50_ms": 1e3 * sorted(v)[len(v) // 2]}
                          for k, v in sorted(self.span_s.items())},
                "idle_gaps": [[n, g] for n, g in self.gaps]}


def host_spans(profile) -> List[Span]:
    """Every program and harness span on the host planes, by start."""
    keep = PROGRAM_PREFIXES + (HARNESS_PREFIX,)
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [(s, e, n) for n, s, e in _events(ln)
                        if n.startswith(keep)]
    return sorted(out)


def split_idle(gaps: List[Tuple[float, float]], spans: List[Span]
               ) -> Dict[str, float]:
    """Length of ``gaps`` (sorted, disjoint) by the shortest span of
    ``spans`` covering each piece, or ``NO_SPAN``: one sweep over the
    span edges."""
    edges = sorted([(s, 1, i) for i, (s, _, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, e, _) in enumerate(spans)])
    active: Dict[int, Tuple[float, str]] = {}
    out: Dict[str, float] = defaultdict(float)

    def apply(edge):
        _, opens, i = edge
        if opens:
            s, e, n = spans[i]
            active[i] = (e - s, n)
        else:
            active.pop(i, None)

    def give(a, b):
        out[min(active.values())[1] if active else NO_SPAN] += b - a

    k = 0
    for gs, ge in gaps:
        while k < len(edges) and edges[k][0] <= gs:
            apply(edges[k])
            k += 1
        cur = gs
        while k < len(edges) and edges[k][0] < ge:
            if edges[k][0] > cur:
                give(cur, edges[k][0])
                cur = edges[k][0]
            apply(edges[k])
            k += 1
        give(cur, ge)
    return dict(out)


def summarize(profile, n_gaps: int = 10) -> SpanSummary:
    """``profile``: a ``jax.profiler.ProfileData`` with a TPU plane."""
    chips = {}
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chips[int(m.group(1))] = plane
    if not chips:
        raise ValueError("the trace has no TPU plane")
    ops = [ev for ln in chips[min(chips)].lines if ln.name == "XLA Ops"
           for ev in _events(ln)]
    spans = host_spans(profile)
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    w0, w1 = win[0] if win else (min(s for _, s, _ in ops),
                                 max(e for _, _, e in ops))
    busy = merge([(max(s, w0), min(e, w1)) for _, s, e in ops
                  if e > w0 and s < w1])
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    by_span = split_idle(gaps, inner)
    durations: Dict[str, List[float]] = defaultdict(list)
    for s, e, n in inner:
        if is_program(n) and w0 <= s < w1:
            durations[n].append((e - s) * 1e-9)
    top = sorted(gaps, key=lambda g: -(g[1] - g[0]))[:n_gaps]
    named = []
    for a, b in top:
        pieces = split_idle([(a, b)], inner)
        named.append((max(pieces, key=pieces.get), (b - a) * 1e-9))
    return SpanSummary(
        window_s=(w1 - w0) * 1e-9,
        idle_s=sum(b - a for a, b in gaps) * 1e-9,
        idle_by_span={k: v * 1e-9 for k, v in by_span.items()},
        span_s=dict(durations), gaps=named)


def summarize_file(path) -> SpanSummary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(str(path)))


if __name__ == "__main__":
    print(json.dumps(summarize_file(sys.argv[1]).as_dict(), indent=1))
