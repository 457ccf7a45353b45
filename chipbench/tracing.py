"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The trace holds one plane per TPU (``/device:TPU:<n>``) whose ``XLA Ops``
line has every operation that ran on that chip, and host planes whose
thread lines carry the benchmark's own spans (``TraceAnnotation`` names
starting with ``bench.``).  Host and device events share one clock.

* busy time: the union of the operation intervals of a chip inside the
  traced window, averaged over the chips;
* time by program (``XLA Modules``), and by operation within its program:
  an operation's event name is its HLO text (``%fusion.169 = bf16[...]
  fusion(...)``), read as its short name and its opcode, or for a custom
  call its target (``tpu_custom_call`` is a Pallas kernel); control-flow
  operations (``while``, ``conditional``, ``call``) span their bodies and
  are left out of the sums;
* exposed collective time: collective intervals during which no other
  operation ran on that chip;
* idle gaps of chip 0, each named by the host span that covers most of
  it (the innermost, where spans nest).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter", re.I)
_SUFFIX = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z0-9_\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CONTAINERS = {"while", "conditional", "call"}


def op_kind(name: str) -> tuple:
    """(short name, opcode or custom-call target) of an HLO op event."""
    short, _, rest = name.partition(" = ")
    m = _OPCODE.search(rest)
    kind = m.group(1) if m else (rest.split("(")[0].split()[-1]
                                 if rest else short)
    if kind == "custom-call":
        t = _TARGET.search(rest)
        kind = t.group(1) if t else kind
    return short, kind

Interval = Tuple[float, float]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals, sorted and non-overlapping."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: List[Interval], b: List[Interval]) -> float:
    """Length of the union of ``a`` not covered by the union of ``b``."""
    a, b = merge(a), merge(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s: float                       # per chip, mean
    op_s: Dict[str, float]              # "program/short opcode", per chip
    kind_s: Dict[str, float]            # "program/opcode", per chip
    kind_count: Dict[str, float]
    module_s: Dict[str, float]          # per chip, mean
    module_count: Dict[str, float]
    collective_s: float                 # per chip, mean
    collective_exposed_s: float         # per chip, mean
    spans: Dict[str, int]               # bench.* span name -> count
    gaps: List[Tuple[str, float]]       # chip 0's longest idle gaps

    def kernel(self, program: str, kind: str = "tpu_custom_call"
               ) -> Tuple[float, float]:
        """(seconds, calls) per chip of operations of ``kind`` inside the
        programs whose name matches the regex ``program``."""
        rx = re.compile(program)
        hits = [k for k in self.kind_s
                if k.endswith("/" + kind) and rx.search(k.rsplit("/", 1)[0])]
        return (sum(self.kind_s[k] for k in hits),
                sum(self.kind_count[k] for k in hits))

    def modules_matching(self, pattern: str) -> Tuple[float, float]:
        rx = re.compile(pattern)
        hits = [n for n in self.module_s if rx.search(n)]
        return (sum(self.module_s[n] for n in hits),
                sum(self.module_count[n] for n in hits))

    def breakdown(self, n: int = 10) -> Dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def reduce_profile(profile) -> TraceSummary:
    """``profile``: a ``jax.profiler.ProfileData``."""
    devices, spans = {}, []
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {ln.name: list(_events(ln)) for ln in plane.lines}
            devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith(SPAN_PREFIX)]
    if not devices:
        raise ValueError("the trace has no TPU plane")
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = win[0]
    else:
        every = [ev for d in devices.values() for ev in d.get("XLA Ops", [])]
        w0, w1 = min(s for _, s, _ in every), max(e for _, _, e in every)

    def clip(evs):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                if e > w0 and s < w1]

    n_chips = len(devices)
    busy = coll = exposed = 0.0
    op_s, kind_s, kind_n = (defaultdict(float), defaultdict(float),
                            defaultdict(float))
    mod_s, mod_n = defaultdict(float), defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    inner = sorted(((s, e, n) for n, s, e in spans if n != WINDOW_SPAN))
    longest = max((e - s for s, e, _ in inner), default=0.0)
    for chip, lines in sorted(devices.items()):
        ops = clip(lines.get("XLA Ops", []))
        iv = merge([(s, e) for _, s, e in ops])
        busy += sum(e - s for s, e in iv)
        mods = sorted((s, e, _SUFFIX.sub("", n))
                      for n, s, e in clip(lines.get("XLA Modules", [])))
        for s, e, n in mods:
            mod_s[n] += e - s
            mod_n[n] += 1
        starts = [m[0] for m in mods]
        kinds = []
        for n, s, e in ops:
            short, kind = op_kind(n)
            kinds.append(kind)
            if kind in _CONTAINERS:
                continue
            j = bisect.bisect_right(starts, s) - 1
            prog = mods[j][2] if j >= 0 and mods[j][1] >= e else "?"
            op_s[f"{prog}/{short} {kind}"] += e - s
            kind_s[f"{prog}/{kind}"] += e - s
            kind_n[f"{prog}/{kind}"] += 1
        cl = [(s, e) for (n, s, e), k in zip(ops, kinds)
              if _COLLECTIVE.search(k)]
        rest = [(s, e) for (n, s, e), k in zip(ops, kinds)
                if not _COLLECTIVE.search(k) and k not in _CONTAINERS]
        coll += covered(cl)
        exposed += subtract(cl, rest)
        if chip == min(devices):
            edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
            for gs, ge in zip(edges[0::2], edges[1::2]):
                if ge > gs:
                    gaps.append((_host_activity(inner, longest, gs, ge),
                                 ge - gs))
    ns = 1e-9 / n_chips
    gaps.sort(key=lambda g: -g[1])
    counts = defaultdict(int)
    for n, _, _ in spans:
        counts[n] += 1
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, chips=n_chips, busy_s=busy * ns,
        op_s={k: v * ns for k, v in op_s.items()},
        kind_s={k: v * ns for k, v in kind_s.items()},
        kind_count={k: v / n_chips for k, v in kind_n.items()},
        module_s={k: v * ns for k, v in mod_s.items()},
        module_count={k: v / n_chips for k, v in mod_n.items()},
        collective_s=coll * ns, collective_exposed_s=exposed * ns,
        spans=dict(counts), gaps=[(n, g * 1e-9) for n, g in gaps])


def _host_activity(spans, longest, gs, ge) -> str:
    """Name of the innermost host span covering most of [gs, ge).
    ``spans``: (start, end, name) sorted by start, none longer than
    ``longest``."""
    best, best_key = "no bench span", (0.0, 0.0)
    j = bisect.bisect_left(spans, (ge,)) - 1
    while j >= 0 and spans[j][0] >= gs - longest:
        s, e, n = spans[j]
        ov = min(e, ge) - max(s, gs)
        if ov > 0:
            key = (ov, -(e - s))
            if key > best_key:
                best, best_key = n, key
        j -= 1
    return best


def reduce_file(path) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))
