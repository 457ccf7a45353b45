"""The program's spans in a trace (``chipbench/spans.py``): idle time
split by the innermost span, host work against device syncs, gaps named
over program and harness spans; the admission reader; and the
reducer's values on the trace recorded before the program had spans."""
import gzip
import hashlib
import json
import pathlib
import shutil
from types import SimpleNamespace as NS

import pytest

from chipbench import spans, tracing
from chipbench.bench import load_reader

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def op(short, start, dur):
    return ev(f"%{short} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p)", start, dur)


def profile(device_ops, host):
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops",
                                          events=device_ops)]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=host)])])


# one tick after one admission, nanoseconds
TICK = [ev("bench.window", 0, 300), ev("bench.engine_step", 0, 200),
        ev("engine.admit", 0, 100), ev("engine.prefill", 10, 50),
        ev("engine.first_token_sync", 60, 30), ev("engine.prepare", 100, 10),
        ev("engine.decode", 110, 5), ev("engine.decode_sync", 115, 75),
        ev("engine.emit", 190, 10), ev("bench.wait_arrival", 200, 50)]
# busy [20,85) [105,185) [195,220): idle [0,20) [85,105) [185,195)
# [220,300)
OPS = [op("a", 20, 65), op("b", 105, 80), op("c", 195, 25)]


def test_split_gives_each_piece_to_the_shortest_covering_span():
    sp = sorted((s.start_ns, s.start_ns + s.duration_ns, s.name)
                for s in TICK[1:])
    got = spans.split_idle([(0, 20), (85, 105), (185, 195), (220, 300)], sp)
    assert got == {"engine.admit": 10 + 10, "engine.prefill": 10,
                   "engine.first_token_sync": 5, "engine.prepare": 5,
                   "engine.decode_sync": 5, "engine.emit": 5,
                   "bench.wait_arrival": 30, spans.NO_SPAN: 50}


def test_split_of_nothing_and_of_gaps_under_no_span():
    assert spans.split_idle([], []) == {}
    assert spans.split_idle([(0, 5), (7, 9)], []) == {spans.NO_SPAN: 7}
    # a span that ends where a gap begins does not cover it
    assert spans.split_idle([(10, 20)], [(0, 10, "engine.emit")]) \
        == {spans.NO_SPAN: 10}


def test_summary_splits_host_work_from_syncs():
    s = spans.summarize(profile(OPS, TICK))
    assert s.window_s == pytest.approx(300e-9)
    assert s.idle_s == pytest.approx(130e-9)
    assert sum(s.idle_by_span.values()) == pytest.approx(s.idle_s)
    assert s.host_idle_s == pytest.approx((20 + 10 + 5 + 5) * 1e-9)
    assert s.sync_idle_s == pytest.approx((5 + 5) * 1e-9)
    assert s.host_idle_s + s.sync_idle_s <= s.idle_s
    assert s.span_s["engine.decode_sync"] == [pytest.approx(75e-9)]
    assert "bench.engine_step" not in s.span_s      # program spans only
    # each gap goes to the span holding its largest piece, the first on
    # a tie: [220,300) no span (50 of 80); [0,20) admit and prefill (10
    # each); [85,105) admit (10 of 20); [185,195) decode_sync and emit
    assert [(n, round(g * 1e9)) for n, g in s.gaps] == [
        (spans.NO_SPAN, 80), ("engine.admit", 20), ("engine.admit", 20),
        ("engine.decode_sync", 10)]
    d = s.as_dict()
    assert d["host_idle_share"] == pytest.approx(100 * 40 / 300)
    assert d["sync_idle_share"] == pytest.approx(100 * 10 / 300)
    assert d["spans"]["engine.admit"]["count"] == 1


def test_a_trace_without_a_tpu_is_refused():
    with pytest.raises(ValueError):
        spans.summarize(NS(planes=[NS(name="/host:CPU", lines=[])]))


# ---------------------------------------------------------------------------
ADMIT = load_reader("admit_ms_p50.chat")


def _run(metrics, trace=object()):
    return NS(trace=trace, program=dict(enumerate(metrics)),
              in_window=lambda t: t is not None and 0.0 <= t < 10.0)


def test_admit_reader_reads_admissions_that_began_in_the_window():
    from repro.serving.request import RequestMetrics
    ms = [RequestMetrics(t_prefill_start=t, t_admitted=t + d)
          for t, d in ((1.0, 0.05), (2.0, 0.07), (3.0, 0.09),
                       (11.0, 5.0))]            # the last began after
    assert ADMIT(_run(ms)) == pytest.approx(70.0)


def test_admit_reader_finds_nothing_without_spans_or_trace():
    from repro.serving.request import RequestMetrics
    assert ADMIT(_run([RequestMetrics(t_prefill_start=1.0,
                                      t_admitted=1.1)], trace=None)) is None
    # a program that does not close its admissions (the parent's)
    assert ADMIT(_run([NS(t_prefill_start=1.0)])) is None
    assert ADMIT(_run([RequestMetrics(t_prefill_start=1.0)])) is None
    assert ADMIT(_run([])) is None


# ---------------------------------------------------------------------------
def _unzip(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name.replace(".gz", "")
    with gzip.open(FIXTURES / name) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def old_trace(tmp_path_factory):
    """A tiny chat cell recorded before the program had spans."""
    return _unzip(tmp_path_factory, "tinychat.xplane.pb.gz")


def _digest(x):
    return hashlib.sha256(json.dumps(x, sort_keys=True).encode()
                          ).hexdigest()[:16]


def test_the_reducer_reads_the_old_trace_as_it_did(old_trace):
    """Busy, op, kind, module and gap values as the reducer gave them
    when the benchmark was built (digests of the exact floats)."""
    s = tracing.reduce_file(old_trace)
    assert s.busy_s == 0.02540985 and s.window_s == 1.001196396
    assert {k: _digest(getattr(s, k)) for k in (
        "op_s", "kind_s", "kind_count", "module_s", "module_count",
        "spans")} == {
        "op_s": "69c4f6a1ba6ef542", "kind_s": "290bc2b0eff3a9d6",
        "kind_count": "9b50e0647d031f3e", "module_s": "a512bd8ba8587be6",
        "module_count": "c933da2ca4e5f336", "spans": "9ad77824cb14d38f"}
    assert len(s.gaps) == 3305 and _digest(s.gaps) == "a7895b7560e5336b"


def test_the_old_trace_has_no_program_spans(old_trace):
    old = tracing.reduce_file(old_trace)
    s = spans.summarize_file(old_trace)
    assert s.span_s == {}
    assert s.host_idle_s == s.sync_idle_s == 0
    assert s.idle_s == pytest.approx(old.window_s - old.busy_s, rel=1e-9)
    assert set(s.idle_by_span) <= {"bench.wait_arrival",
                                   "bench.engine_step", "bench.submit",
                                   spans.NO_SPAN}
    # with harness spans alone, gaps are named as the reducer names them
    assert s.gaps == old.gaps[:10]


@pytest.fixture(scope="module")
def new_trace(tmp_path_factory):
    """A tiny chat cell (two layers, 1 s window) through the harness with
    ``--trace 1`` on a TPU v5e, the program's spans in it."""
    return _unzip(tmp_path_factory, "tinychat_spans.xplane.pb.gz")


def test_the_trace_with_program_spans_splits_its_idle_time(new_trace):
    old = tracing.reduce_file(new_trace)
    assert old.spans == {"bench.window": 1, "bench.submit": 6,
                         "bench.engine_step": 46, "bench.wait_arrival": 4}
    s = spans.summarize_file(new_trace)
    assert s.window_s == old.window_s
    assert s.idle_s == pytest.approx(old.window_s - old.busy_s, rel=1e-9)
    assert sum(s.idle_by_span.values()) == pytest.approx(s.idle_s)
    idle_share = 100 * (1 - old.busy_s / old.window_s)
    host = 100 * s.host_idle_s / s.window_s
    sync = 100 * s.sync_idle_s / s.window_s
    assert host == pytest.approx(24.1831814, rel=1e-6)
    assert sync == pytest.approx(7.0573829, rel=1e-6)
    assert host + sync <= idle_share
    # six admissions and 46 ticks, as the harness's own spans count
    assert {k: len(v) for k, v in s.span_s.items()} == {
        "engine.admit": 6, "engine.prefill": 6,
        "engine.first_token_sync": 6, "engine.prepare": 46,
        "engine.decode": 46, "engine.decode_sync": 46, "engine.emit": 46}
    # idle while waiting for arrivals is the harness's; the rest is named
    # by program spans
    names = [n for n, _ in s.gaps]
    assert names[:4] == ["bench.wait_arrival"] * 4
    assert all(n.startswith("engine.") for n in names[4:])


def test_admissions_hold_their_prefill_and_first_token(new_trace):
    from jax.profiler import ProfileData
    sp = spans.host_spans(ProfileData.from_file(str(new_trace)))
    admits = [x for x in sp if x[2] == "engine.admit"]
    for child in ("engine.prefill", "engine.first_token_sync"):
        kids = [x for x in sp if x[2] == child]
        assert all(a[0] <= k[0] and k[1] <= a[1]
                   for k, a in zip(kids, admits))
    steps = [x for x in sp if x[2] == "bench.engine_step"]
    decodes = [x for x in sp if x[2] == "engine.decode"]
    assert all(any(t[0] <= d[0] and d[1] <= t[1] for t in steps)
               for d in decodes)
