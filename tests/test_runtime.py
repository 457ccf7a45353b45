"""Elastic training runtime: state machine, fault adapter, drain barrier,
recovery telemetry, re-planning, and the train-step satellites
(error-feedback compression, aux-metric accumulation, positions
microbatching).  Multi-device recovery paths run in
tests/distributed/test_distributed.py on fake devices; here we pin the
runtime semantics that hold on one device."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import reduced_config
from repro.core.config import (OptimizerConfig, ParallelConfig, RunConfig,
                               ShapeConfig, StepKind)
from repro.core.telemetry import RunTelemetry
from repro.train.runtime import (DeviceLossEvent, DevicePool, FaultMonitor,
                                 RunnerState, Trainer, TrainerCallback)


@pytest.fixture(scope="module")
def cfg():
    return reduced_config("gemma-2b")


@pytest.fixture(scope="module")
def shape():
    return ShapeConfig("t", 32, 4, StepKind.TRAIN)


def _run_cfg(cfg, shape, steps=8, **opt):
    opt.setdefault("lr", 3e-4)
    opt.setdefault("warmup_steps", 2)
    return RunConfig(model=cfg, shape=shape,
                     optimizer=OptimizerConfig(total_steps=steps, **opt))


class _Spy(TrainerCallback):
    def __init__(self):
        self.transitions = []
        self.steps = []
        self.faults = []
        self.recoveries = []
        self.ckpts = []

    def on_state_change(self, trainer, old, new):
        self.transitions.append((old, new))

    def on_step(self, trainer, step, metrics):
        self.steps.append(step)

    def on_fault(self, trainer, event):
        self.faults.append(event)

    def on_recovery(self, trainer, rec):
        self.recoveries.append(rec)

    def on_checkpoint(self, trainer, step):
        self.ckpts.append(step)


# ---------------------------------------------------------------------------
# State machine
def test_happy_path_states_and_loss(cfg, shape, tmp_path):
    spy = _Spy()
    tr = Trainer(_run_cfg(cfg, shape, steps=6), ckpt_dir=str(tmp_path),
                 ckpt_every=3, callbacks=[spy])
    rep = tr.run(6)
    assert rep.final_state == RunnerState.DONE
    assert rep.state_history == [RunnerState.INIT, RunnerState.RUNNING,
                                 RunnerState.DONE]
    assert rep.steps_run == 6 and spy.steps == list(range(6))
    assert not rep.recoveries
    assert 3 in spy.ckpts          # async save committed + observed


def test_drain_recovery_cycle_and_loss_continuity(cfg, shape, tmp_path):
    run = _run_cfg(cfg, shape, steps=8)
    ref = Trainer(run, ckpt_dir=str(tmp_path / "ref"), ckpt_every=2).run(8)

    spy = _Spy()
    tr = Trainer(run, ckpt_dir=str(tmp_path / "el"), ckpt_every=2,
                 fault_monitor=FaultMonitor.from_pairs([(3, 1)]),
                 pool=DevicePool(gpus_per_node=1), callbacks=[spy])
    rep = tr.run(8)
    assert rep.final_state == RunnerState.DONE
    # the full §8.7 cycle, in order
    assert rep.state_history == [
        RunnerState.INIT, RunnerState.RUNNING, RunnerState.DRAINING,
        RunnerState.REPLANNING, RunnerState.RESTORING, RunnerState.RUNNING,
        RunnerState.DONE]
    assert len(spy.faults) == 1 and len(rep.recoveries) == 1
    rec = rep.recoveries[0]
    assert rec.lost_steps == 0           # drained at the boundary
    assert rec.resume_step == 4          # fault@3, ckpt_every=2 -> barrier@4
    assert rec.time_to_recover_s > 0
    # restart from the drain checkpoint is exact: losses match the
    # uninterrupted run bitwise
    np.testing.assert_allclose(rep.losses, ref.losses, atol=0)


def test_hard_fault_rolls_back_and_replays(cfg, shape, tmp_path):
    run = _run_cfg(cfg, shape, steps=8)
    ref = Trainer(run, ckpt_dir=str(tmp_path / "ref"), ckpt_every=2).run(8)

    tr = Trainer(run, ckpt_dir=str(tmp_path / "hard"), ckpt_every=2,
                 fault_monitor=FaultMonitor.from_pairs([(3, 0)], hard=True),
                 pool=DevicePool(gpus_per_node=1))
    rep = tr.run(8)
    rec = rep.recoveries[0]
    assert rec.hard and rec.lost_steps == 1      # step 2 redone (ckpt@2)
    assert rec.resume_step == 2
    # replayed steps reproduce the same trajectory: final losses agree
    np.testing.assert_allclose(rep.losses[-4:], ref.losses[-4:], atol=0)
    assert rep.steps_run == 8 + rec.lost_steps


def test_final_boundary_checkpoint_is_written(cfg, shape, tmp_path):
    """The last boundary checkpoint must be durable (a later --restore
    resumes from the end of the run, not halfway through it)."""
    from repro.checkpoint import CheckpointManager
    tr = Trainer(_run_cfg(cfg, shape, steps=8), ckpt_dir=str(tmp_path),
                 ckpt_every=4)
    tr.run(8)
    assert CheckpointManager(str(tmp_path)).all_steps() == [4, 8]


def test_fault_at_final_step_drains_without_recovery(cfg, shape, tmp_path):
    """A fault drained at the end of the run commits the barrier
    checkpoint and stops — no re-plan, no misleading RecoveryRecord."""
    from repro.checkpoint import CheckpointManager
    tr = Trainer(_run_cfg(cfg, shape, steps=6), ckpt_dir=str(tmp_path),
                 ckpt_every=2,
                 fault_monitor=FaultMonitor.from_pairs([(5, 1)]),
                 pool=DevicePool(gpus_per_node=1))
    rep = tr.run(6)
    assert rep.final_state == RunnerState.DONE
    assert RunnerState.DRAINING in rep.state_history
    assert RunnerState.REPLANNING not in rep.state_history
    assert not rep.recoveries
    assert 6 in CheckpointManager(str(tmp_path)).all_steps()


def test_prefetcher_close_unblocks_producer():
    """close() must drain the bounded queue so a producer blocked in
    q.put can observe _done and exit (no leaked thread per recovery)."""
    import time
    from repro.data import Prefetcher

    def gen():
        while True:
            yield 1

    p = Prefetcher(gen(), depth=2)
    next(p)                                  # producer refills, then blocks
    time.sleep(0.05)
    p.close()
    p._thread.join(timeout=5.0)
    assert not p._thread.is_alive()


def test_hard_fault_mid_drain_abandons_the_drain(cfg, shape, tmp_path):
    """A hard fault arriving while DRAINING rolls back immediately —
    the state it was draining toward is already gone."""
    run = _run_cfg(cfg, shape, steps=8)
    mon = FaultMonitor(events=[DeviceLossEvent(step=3, node=1),
                               DeviceLossEvent(step=4, node=2, hard=True)])
    tr = Trainer(run, ckpt_dir=str(tmp_path), ckpt_every=3,
                 fault_monitor=mon, pool=DevicePool(gpus_per_node=1))
    rep = tr.run(8)
    assert rep.final_state == RunnerState.DONE
    assert len(rep.recoveries) == 1          # one recovery covers both
    rec = rep.recoveries[0]
    assert rec.hard and rec.resume_step == 3 and rec.lost_steps == 1
    assert RunnerState.DRAINING in rep.state_history


def test_device_loss_without_checkpoints_fails_closed(cfg, shape, tmp_path):
    # a fault before the first checkpoint cannot be recovered
    tr = Trainer(_run_cfg(cfg, shape, steps=6), ckpt_dir=str(tmp_path),
                 ckpt_every=10,
                 fault_monitor=FaultMonitor.from_pairs([(1, 0)], hard=True),
                 pool=DevicePool(gpus_per_node=1))
    with pytest.raises(RuntimeError, match="before the first checkpoint"):
        tr.run(6)
    assert tr.state == RunnerState.FAILED


def test_invalid_recovery_policy_rejected(cfg, shape):
    with pytest.raises(ValueError, match="recovery"):
        Trainer(_run_cfg(cfg, shape), recovery="pray")


# ---------------------------------------------------------------------------
# FaultMonitor: sched.faults adapter + device pool
def test_fault_monitor_adapts_sched_schedule():
    from repro.sched.faults import draw_fault_schedule
    rng = np.random.default_rng(7)
    sched = draw_fault_schedule(rng, days=60.0)
    assert sched, "60-day window must draw some faults"
    mon = FaultMonitor.from_fault_schedule(sched, n_nodes=16,
                                           steps_per_hour=10.0, seed=3)
    node_scope = {"gpu", "nvlink_pcie", "nic_transceiver"}
    expected = [c for _, c in sched if c in node_scope]
    assert mon.pending == len(expected)
    # drain everything; events arrive step-ordered with node-scope
    # components only, nodes within range
    got = mon.poll(10**9)
    assert sorted(e.component for e in got) == sorted(expected)
    assert all(e.component in node_scope for e in got)
    assert all(0 <= e.node < 16 for e in got)
    assert [e.step for e in got] == sorted(e.step for e in got)
    assert mon.pending == 0


def test_fault_monitor_poll_is_incremental():
    mon = FaultMonitor.from_pairs([(2, 0), (5, 1)])
    assert mon.poll(1) == []
    assert [e.node for e in mon.poll(2)] == [0]
    assert mon.poll(2) == []                 # not redelivered
    assert [e.node for e in mon.poll(99)] == [1]


def test_device_pool_nodes():
    devs = list(range(8))                    # stand-in device objects
    pool = DevicePool(devices=devs, gpus_per_node=2)
    assert pool.n_nodes == 4
    pool.kill_node(1)
    assert pool.alive_devices() == [0, 1, 4, 5, 6, 7]
    assert pool.alive_count == 6 and pool.dead_nodes == (1,)
    with pytest.raises(ValueError):
        pool.kill_node(9)
    fab = pool.fabric()
    assert fab.nodes == 4 and fab.gpus_per_node == 2 and fab.pods == 1


# ---------------------------------------------------------------------------
# Checkpoint drain barrier + recovery telemetry
def test_checkpoint_drain_barrier(tmp_path):
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=3)
    drained, committed = [], []
    mgr.add_drain_observer(drained.append)
    mgr.add_completion_observer(committed.append)
    state = {"w": np.arange(4.0)}
    mgr.save(2, state, blocking=False)       # in-flight async save
    mgr.drain(4, state, extra={"pipeline": {"doc_cursor": 7, "carry": None}})
    # barrier flushed the async save AND committed the drain step
    assert mgr.all_steps() == [2, 4]
    assert drained == [4] and committed == [2, 4]
    _, extra, step = mgr.restore({"w": np.zeros(4)})
    assert step == 4 and extra["pipeline"]["doc_cursor"] == 7


def test_telemetry_records_recovery(cfg, shape, tmp_path):
    path = tmp_path / "telem.jsonl"
    telem = RunTelemetry(str(path), cfg, shape, n_chips=8)
    telem.step(0, {"loss": 1.0, "grad_norm": 0.1})
    rec = telem.recovery(4, time_to_recover_s=0.5, lost_steps=2,
                         chips_before=8, chips_after=6, policy="replan",
                         component="gpu", plan="auto/balanced")
    assert rec["lost_tokens"] == 2 * shape.tokens_per_step
    assert telem.n_chips == 6                # MFU now vs surviving chips
    summ = telem.recovery_summary()
    assert summ["recoveries"] == 1 and summ["total_lost_steps"] == 2
    assert summ["chips_final"] == 6
    telem.close()
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 2 and '"event": "recovery"' in lines[1]
    # step records unaffected
    assert telem.utilization_summary()["steps"] == 1


def test_telemetry_mfu_keyed_by_device_kind(cfg, shape):
    """MFU divides by the published peak of the device kind the run is
    on; a kind the peaks table lacks reports no MFU at all."""
    v5e = RunTelemetry(None, cfg, shape, device_kind="TPU v5 lite")
    assert v5e.step(0, {"loss": 1.0})["mfu"] > 0
    assert "mean_mfu" in v5e.utilization_summary()
    cpu = RunTelemetry(None, cfg, shape, device_kind="cpu")
    assert cpu.step(0, {"loss": 1.0})["mfu"] is None
    assert cpu.utilization_summary() == {"steps": 1}


def test_compilation_cache_dir(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache,
    which git ignores."""
    from repro.core import compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = compile_cache.CHECKOUT / ".jax_cache"
        assert compile_cache.enable_compilation_cache() == str(default)
        assert jax.config.jax_compilation_cache_dir == str(default)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    ignored = (compile_cache.CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_trainer_emits_recovery_telemetry(cfg, shape, tmp_path):
    telem = RunTelemetry(None, cfg, shape, n_chips=1)
    tr = Trainer(_run_cfg(cfg, shape, steps=6), ckpt_dir=str(tmp_path),
                 ckpt_every=2, telemetry=telem,
                 fault_monitor=FaultMonitor.from_pairs([(3, 1)]),
                 pool=DevicePool(gpus_per_node=1))
    rep = tr.run(6)
    assert len(telem.recovery_records) == 1
    assert telem.recovery_records[0]["policy"] == "restart"
    assert len(telem.records) == rep.steps_run


# ---------------------------------------------------------------------------
# launch.train CLI is a thin shim; launch.elastic deprecation shim
def test_train_cli_fault_flags():
    from repro.launch.train import build_parser, parse_fault_spec
    args = build_parser().parse_args(["--fault-at", "5:1,!9:2",
                                      "--recovery", "shrink",
                                      "--gpus-per-node", "2"])
    assert args.recovery == "shrink" and args.gpus_per_node == 2
    mon = parse_fault_spec(args.fault_at)
    evs = mon.poll(100)
    assert [(e.step, e.node, e.hard) for e in evs] == [(5, 1, False),
                                                       (9, 2, True)]


def test_elastic_shim_warns_and_delegates():
    import repro.launch.elastic as el
    from repro.train import runtime
    with pytest.warns(DeprecationWarning, match="repro.train.runtime"):
        fn = el.shrink_data_axis
    assert fn is runtime.shrink_data_axis
    with pytest.warns(DeprecationWarning):
        assert el.reshard_restore is runtime.reshard_restore
    with pytest.warns(DeprecationWarning):
        assert el.make_elastic_mesh is runtime.make_elastic_mesh
    with pytest.raises(AttributeError):
        el.not_a_name


def test_shrink_data_axis_semantics():
    from repro.train.runtime import shrink_data_axis
    assert shrink_data_axis(8, 2) == ((4, 2), ("data", "model"))
    assert shrink_data_axis(6, 2) == ((3, 2), ("data", "model"))
    # TP-group granularity: 7 devices with model=2 strands one
    assert shrink_data_axis(7, 2) == ((3, 2), ("data", "model"))
    with pytest.raises(ValueError):
        shrink_data_axis(1, 2)


# ---------------------------------------------------------------------------
# train-step satellites
def test_int8_ef_buffers_update_and_loss_decreases(cfg, shape):
    from repro.data import PackedPipeline
    from repro.models.model import build_model
    from repro.train.step import init_train_state, make_train_step
    rc = RunConfig(model=cfg, shape=shape,
                   parallel=ParallelConfig(microbatch=2),
                   optimizer=OptimizerConfig(
                       lr=3e-3, warmup_steps=0, total_steps=1000,
                       grad_compression="int8_ef"))
    model = build_model(cfg)
    state = init_train_state(model, rc, jax.random.key(0))
    assert state.ef is not None
    ef0 = [np.asarray(x) for x in jax.tree.leaves(state.ef)]
    step = jax.jit(make_train_step(model, rc))
    pipe = PackedPipeline(cfg, shape, seed=0)
    losses = []
    for _ in range(20):
        b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    ef1 = [np.asarray(x) for x in jax.tree.leaves(state.ef)]
    changed = sum(not np.array_equal(a, b) for a, b in zip(ef0, ef1))
    assert changed > 0, "error-feedback buffers never updated"
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_microbatch_aux_metrics_accumulated(cfg, shape):
    from repro.data import PackedPipeline
    from repro.models.model import build_model
    from repro.train.step import init_train_state, make_train_step
    model = build_model(cfg)
    pipe = PackedPipeline(cfg, shape, seed=0)
    b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}

    def metrics_for(nmicro):
        rc = RunConfig(model=cfg, shape=shape,
                       parallel=ParallelConfig(microbatch=nmicro),
                       optimizer=OptimizerConfig(lr=1e-3, warmup_steps=0,
                                                 total_steps=100))
        state = init_train_state(model, rc, jax.random.key(0))
        _, m = jax.jit(make_train_step(model, rc))(state, b)
        return m

    m1, m2 = metrics_for(0), metrics_for(2)
    # aux metrics used to be silently dropped on the microbatch path
    for key in ("xent", "aux_loss", "z_loss"):
        assert key in m2, f"{key} dropped by accumulation"
        np.testing.assert_allclose(float(m2[key]), float(m1[key]),
                                   rtol=2e-3, atol=1e-5)


def test_microbatches_positions_by_key_not_shape():
    from repro.train.step import _microbatches
    # a batch of exactly 3 rows must NOT be misread as M-RoPE sections
    mb = _microbatches({"tokens": jnp.arange(12).reshape(3, 4)}, 3)
    assert mb["tokens"].shape == (3, 1, 4)
    np.testing.assert_array_equal(np.asarray(mb["tokens"][1, 0]),
                                  np.arange(4, 8))
    # the M-RoPE positions leaf (sections, B, S) splits on its batch dim
    pos = jnp.arange(3 * 4 * 5).reshape(3, 4, 5)
    mp = _microbatches({"positions": pos}, 2)
    assert mp["positions"].shape == (2, 3, 2, 5)
    np.testing.assert_array_equal(np.asarray(mp["positions"][0]),
                                  np.asarray(pos[:, :2]))
    np.testing.assert_array_equal(np.asarray(mp["positions"][1]),
                                  np.asarray(pos[:, 2:]))


def test_vlm_microbatch_train_step_runs():
    """End-to-end: a leading-dim-3 VLM batch with M-RoPE positions goes
    through the accumulation path (regression for the shape heuristic)."""
    from repro.data import PackedPipeline
    from repro.models.model import build_model
    from repro.train.step import init_train_state, make_train_step
    vcfg = reduced_config("qwen2-vl-7b")
    vshape = ShapeConfig("t", 32, 4, StepKind.TRAIN)
    rc = RunConfig(model=vcfg, shape=vshape,
                   parallel=ParallelConfig(microbatch=2),
                   optimizer=OptimizerConfig(lr=1e-3, warmup_steps=0,
                                             total_steps=10))
    model = build_model(vcfg)
    state = init_train_state(model, rc, jax.random.key(0))
    pipe = PackedPipeline(vcfg, vshape, seed=0)
    b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
    assert b["positions"].shape[0] == 3
    _, m = jax.jit(make_train_step(model, rc))(state, b)
    assert np.isfinite(float(m["loss"]))
