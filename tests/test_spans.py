"""Host spans of the serving engine and the trainer in the JAX profiler's
trace (``repro.core.telemetry.span``): their names, how they nest, how
many there are per admission, tick and step, and that with no trace
being taken they change neither the compiled programs nor the ticks."""
import contextlib
import pathlib

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import reduced_config
from repro.core.config import OptimizerConfig, RunConfig, ShapeConfig, StepKind
from repro.models.model import build_model
from repro.serving import Engine, SamplingParams
import repro.serving.engine as engine_mod
from repro.train.runtime import Trainer

ENGINE_TICK = ["engine.prepare", "engine.decode", "engine.decode_sync",
               "engine.emit"]
TRAINER_STEP = ["trainer.data", "trainer.put", "trainer.step",
                "trainer.loss_sync", "trainer.callbacks"]


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced_config("gemma-2b")
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(0))
    return cfg, model, params


def traced(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler.trace``; (its result, the program
    spans as (name, start, end, thread line, args), by start)."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "trainer.")):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns, line.name,
                                  dict(ev.stats)))
    return out, sorted(spans, key=lambda s: s[1])


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def named(spans, name):
    return [s for s in spans if s[0] == name]


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 500, k).astype(np.int32)
            for k in (5, 23, 12, 7, 31)[:n]]


def _engine(model, params, paged):
    kw = {"block_size": 16} if paged else {}
    return Engine(model, params, slots=2, prefill_len=32, cache_len=48, **kw)


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_engine_spans_name_and_nest(gemma, tmp_path, paged):
    _, model, params = gemma
    eng = _engine(model, params, paged)
    sampling = SamplingParams(max_new_tokens=4)
    eng.generate(_prompts(2), sampling)             # compile outside
    rids = [eng.submit(p, sampling) for p in _prompts(5, seed=1)]
    t0 = eng.ticks
    _, spans = traced(tmp_path, lambda: eng.run(max_ticks=99))
    ticks = eng.ticks - t0
    assert all(r in eng.finished for r in rids)

    assert len({s[3] for s in spans}) == 1          # the loop's thread only
    admits = named(spans, "engine.admit")
    assert [a[4]["rid"] for a in admits] == rids    # one per admission
    assert [a[4]["tokens"] for a in admits] == [len(p) for p in
                                                _prompts(5, seed=1)]
    for child in ("engine.prefill", "engine.first_token_sync"):
        kids = named(spans, child)
        assert len(kids) == len(admits)
        assert all(inside(k, a) for k, a in zip(kids, admits))
    for name in ENGINE_TICK:
        assert len(named(spans, name)) == ticks, name
        assert not any(inside(s, a) for s in named(spans, name)
                       for a in admits)
    # each tick's phases follow one another in order
    tick = [s for s in spans if s[0] in ENGINE_TICK]
    assert [s[0] for s in tick] == ENGINE_TICK * ticks
    assert all(a[2] <= b[1] for a, b in zip(tick, tick[1:]))
    for r in rids:
        m = eng.finished[r].metrics
        assert 0 < m.admit_time <= m.t_first_token - m.t_submit + 1.0


def test_spans_cost_no_compile_and_no_tick_when_not_traced(gemma,
                                                           monkeypatch):
    """The same requests through an engine with its spans and through
    one whose spans do nothing: the same tokens, ticks and programs."""
    _, model, params = gemma
    sampling = SamplingParams(max_new_tokens=5)

    def serve():
        eng = _engine(model, params, paged=False)
        out = [r.tokens for r in eng.generate(_prompts(5), sampling)]
        sizes = [f._cache_size() for f in (eng._prefill, eng._generate,
                                           eng._sample1)]
        return out, eng.ticks, sizes

    with_spans = serve()
    monkeypatch.setattr(engine_mod, "span",
                        lambda *a, **k: contextlib.nullcontext())
    assert serve() == with_spans


def test_trainer_spans_one_per_step_on_the_loop_thread(tmp_path):
    cfg = reduced_config("gemma-2b")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 4, StepKind.TRAIN),
                    optimizer=OptimizerConfig(total_steps=8, lr=3e-4,
                                              warmup_steps=2))
    tr = Trainer(run, ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2)
    tr.run(1)                                        # compile outside
    rep, spans = traced(tmp_path / "trace", lambda: tr.run(4))
    steps = rep.steps_run
    assert steps == 4
    assert len({s[3] for s in spans}) == 1          # none in the prefetcher
    for name in TRAINER_STEP:
        assert len(named(spans, name)) == steps, name
    step = [s for s in spans if s[0] in TRAINER_STEP]
    assert [s[0] for s in step] == TRAINER_STEP * steps
    assert all(a[2] <= b[1] for a, b in zip(step, step[1:]))
    # four steps cross the boundaries at 2 and 4
    assert len(named(spans, "trainer.checkpoint")) == 2

