"""The trace reducer: busy union, time by program and kernel, exposed
collectives, idle gaps named by host spans."""
import pathlib
from types import SimpleNamespace as NS

import pytest

from chipbench import tracing

FIXTURES = pathlib.Path(__file__).parent / "fixtures"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def op(short, kind, start, dur, target=None):
    body = (f'custom-call(bf16[8]{{0}} %p), custom_call_target="{target}"'
            if target else f"{kind}(bf16[8]{{0}} %p)")
    return ev(f"%{short} = bf16[8]{{0:T(128)}} {body}", start, dur)


def profile(device_ops, modules, host, chips=1):
    planes = [NS(name=f"/device:TPU:{i}", lines=[
        NS(name="XLA Ops", events=device_ops),
        NS(name="XLA Modules", events=modules)]) for i in range(chips)]
    planes.append(NS(name="/host:CPU", lines=[NS(name="python3",
                                                 events=host)]))
    return NS(planes=planes)


def test_interval_arithmetic():
    assert tracing.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.covered([(0, 2), (1, 3)]) == 3
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert tracing.subtract([(0, 4), (6, 8)], [(3, 7)]) == 4


def test_op_kind_reads_hlo_text():
    assert tracing.op_kind("%fusion.1 = bf16[19,25600]{1,0} fusion(%a)") \
        == ("%fusion.1", "fusion")
    assert tracing.op_kind(
        '%closed_call.10 = (f32[1]{0}, f32[2]{0}) custom-call(s32[1]{0} %x),'
        ' custom_call_target="tpu_custom_call"') \
        == ("%closed_call.10", "tpu_custom_call")
    assert tracing.op_kind("%while.3 = (s32[], bf16[2]{0}) while((s32[]) "
                           "%t), condition=%c")[1] == "while"


def test_reduce_profile():
    ops = [op("while.1", "while", 100, 600),            # spans its body
           op("fusion.1", "fusion", 100, 200),
           op("cc.1", "custom-call", 300, 300, "tpu_custom_call"),
           op("all-reduce.1", "all-reduce", 550, 100),   # 50 exposed
           op("fusion.2", "fusion", 800, 100)]
    mods = [ev("jit_generate_step(7)", 100, 600), ev("jit_prefill(9)", 800,
                                                      100)]
    host = [ev("bench.window", 0, 1000), ev("bench.engine_step", 0, 720),
            ev("bench.wait_arrival", 720, 80)]
    s = tracing.reduce_profile(profile(ops, mods, host))
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(700e-9)      # [100,700) + [800,900)
    assert s.kernel("generate_step") == (pytest.approx(300e-9), 1)
    assert s.kernel("prefill") == (0, 0)
    assert s.kernel("prefill", "fusion") == (pytest.approx(100e-9), 1)
    assert s.modules_matching("generate_step")[0] == pytest.approx(600e-9)
    assert s.collective_s == pytest.approx(100e-9)
    assert s.collective_exposed_s == pytest.approx(50e-9)
    assert not any("while" in k for k in s.op_s)
    # gaps: [0,100) under engine_step, [700,800) mostly wait_arrival,
    # [900,1000) under no bench span but the window
    names = sorted(n for n, _ in s.gaps)
    assert names == ["bench.engine_step", "bench.wait_arrival",
                     "no bench span"]
    assert len(s.breakdown()["device_ops"]) == 4


def test_chips_are_averaged():
    ops = [op("fusion.1", "fusion", 0, 500)]
    s = tracing.reduce_profile(profile(ops, [ev("jit_f(1)", 0, 500)],
                                       [ev("bench.window", 0, 1000)],
                                       chips=4))
    assert s.chips == 4 and s.busy_s == pytest.approx(500e-9)


def test_a_trace_without_a_tpu_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce_profile(NS(planes=[NS(name="/host:CPU", lines=[])]))
