"""Elastic training runtime (paper §8.7: drain the failed node, restart,
resume from checkpoint).

The defining operational dynamic of single-tenant LLM development is the
fault-tolerant-resume loop: a node fails, the job drains at a safe point,
the cluster re-plans around the loss, and training resumes from the last
checkpoint with the data cursor intact.  This module turns the previously
monolithic ``launch.train`` script into that loop:

  * :class:`Trainer` — owns the step loop as an event-driven state machine
    (INIT → RUNNING → DRAINING → REPLANNING → RESTORING → RUNNING) with
    pluggable :class:`TrainerCallback` observers (logging, telemetry,
    checkpoint events, fault watch).
  * :class:`FaultMonitor` — adapts :mod:`repro.sched.faults` schedules
    (Table 13 taxonomy) into runtime :class:`DeviceLossEvent`\\ s; only
    node-scope components (gpu / nvlink_pcie / nic_transceiver) kill a
    node — switch, storage and config faults are cluster-level events
    handled by :mod:`repro.sched`.
  * :class:`DevicePool` — groups this process's (fake) jax devices into
    failure-domain "nodes" so a node loss removes ``gpus_per_node``
    devices at once, the paper's node-granularity drain.
  * Recovery policies — ``"replan"`` re-runs the full auto-planner over
    the surviving chips (:func:`repro.parallel.plan.replan`, every axis
    back on the table); ``"shrink"`` is the legacy behavior that only
    shrinks the data axis while preserving TP groups
    (:func:`shrink_data_axis`).

Tracing: each step's phases are host spans in the JAX profiler's trace
(``repro.core.telemetry.span``), on the thread that calls ``run`` (none
in the prefetch thread): ``trainer.data`` (the wait on the prefetcher),
``trainer.put``, ``trainer.step`` (the dispatch), ``trainer.loss_sync``
(the wait for the step's loss), ``trainer.callbacks`` and
``trainer.checkpoint``.

Checkpoints are stored shard-agnostically (full logical arrays per leaf),
so restoring onto a different mesh is just load + device_put with the new
NamedShardings (:func:`reshard_restore`).  ``launch.elastic`` is now a
deprecation shim over the three elastic helpers that live here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import math
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint import CheckpointManager
from repro.core.config import RunConfig
from repro.core.fabric import FABRIC, FabricSpec
from repro.core.telemetry import RunTelemetry, span
from repro.data import PackedPipeline, Prefetcher
from repro.parallel.plan import (CollectiveSchedule, Layout, ParallelPlan,
                                 replan, score_layout)
from repro.parallel.sharding import make_mesh, spec_tree_for_params
from repro.sched.faults import FAULT_TAXONOMY
from repro.train.step import (abstract_train_state, init_train_state,
                              make_train_step, train_state_logical_axes)


# ---------------------------------------------------------------------------
# Elastic helpers (moved here from launch.elastic, which shims over us)
def shrink_data_axis(n_devices: int, model_parallel: int,
                     pod: Optional[int] = None) -> Tuple[Tuple[int, ...],
                                                         Tuple[str, ...]]:
    """Largest (pod?, data, model) mesh that fits the surviving devices.

    The model axis is preserved (TP groups must stay intact — losing one
    member of a TP group invalidates the whole group, so capacity shrinks
    in units of ``model_parallel`` devices, the paper's node-granularity
    drain generalized to TP-group granularity)."""
    groups = n_devices // model_parallel
    if groups < 1:
        raise ValueError("not enough devices for one model-parallel group")
    if pod and groups % pod == 0 and groups // pod > 1:
        return (pod, groups // pod, model_parallel), ("pod", "data", "model")
    return (groups, model_parallel), ("data", "model")


def make_elastic_mesh(model_parallel: int, devices=None,
                      pod: Optional[int] = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    shape, axes = shrink_data_axis(len(devices), model_parallel, pod)
    return make_mesh(shape, axes, devices)


def reshard_restore(mgr: CheckpointManager, abstract_state, axes_tree,
                    mesh: Mesh, step: Optional[int] = None):
    """Restore the latest checkpoint onto a (possibly different) mesh."""
    host_state, extra, step = mgr.restore(abstract_state, step)
    shardings = spec_tree_for_params(abstract_state, axes_tree, mesh)

    def put(x, sh):
        if sh is None:
            return jax.device_put(x)
        return jax.device_put(x, sh)

    from repro.parallel.sharding import LogicalAxes
    state = jax.tree.map(put, host_state, shardings,
                         is_leaf=lambda t: not isinstance(t, (dict, list,
                                                              tuple))
                         or isinstance(t, LogicalAxes))
    return state, extra, step


# ---------------------------------------------------------------------------
# Runtime states and events
class RunnerState(str, enum.Enum):
    INIT = "init"
    RUNNING = "running"
    DRAINING = "draining"          # fault seen; running to the next ckpt
    REPLANNING = "replanning"      # computing the post-fault layout
    RESTORING = "restoring"        # resharded checkpoint load
    DONE = "done"
    FAILED = "failed"


@dataclass
class DeviceLossEvent:
    """A node-granularity device loss delivered to the runtime."""
    step: int                      # first step at which the loss is visible
    node: int
    component: str = "gpu"         # Table 13 component name
    hard: bool = False             # True: state on the node is gone now
    #   (roll back to the last checkpoint); False: advance notice — drain
    #   at the next checkpoint boundary with zero lost steps (§8.5-style
    #   checkpoint preemption applied to faults with warning)
    t_hours: float = 0.0           # schedule time, when adapted from sched


_NODE_SCOPE = {c for c, _, scope in FAULT_TAXONOMY if scope == "node"}


class FaultMonitor:
    """Turns fault schedules into step-indexed device-loss events.

    ``poll(step)`` returns every not-yet-delivered event whose step has
    arrived; ``inject`` adds one at runtime (operator drain, tests)."""

    def __init__(self, events: Sequence[DeviceLossEvent] = ()):
        self._events: List[DeviceLossEvent] = sorted(events,
                                                     key=lambda e: e.step)
        self._delivered = 0

    @classmethod
    def from_pairs(cls, pairs: Sequence[Tuple[int, int]], *,
                   hard: bool = False, component: str = "gpu"
                   ) -> "FaultMonitor":
        """[(step, node), ...] — the deterministic test/bench interface."""
        return cls([DeviceLossEvent(step=s, node=n, hard=hard,
                                    component=component) for s, n in pairs])

    @classmethod
    def from_fault_schedule(cls, schedule: Sequence[Tuple[float, str]], *,
                            n_nodes: int, steps_per_hour: float,
                            seed: int = 0, hard: bool = True
                            ) -> "FaultMonitor":
        """Adapt a :func:`repro.sched.faults.draw_fault_schedule` draw
        ``[(t_hours, component), ...]`` onto a training run.

        Only node-scope components become device losses (Table 13: gpu,
        nvlink_pcie, nic_transceiver); the struck node is drawn
        deterministically from ``seed``.  Real hardware faults default to
        ``hard=True`` — no advance notice, steps since the last
        checkpoint are lost."""
        rng = np.random.default_rng(seed)
        events = []
        for t, comp in schedule:
            if comp not in _NODE_SCOPE:
                continue
            events.append(DeviceLossEvent(
                step=max(int(t * steps_per_hour), 0),
                node=int(rng.integers(n_nodes)), component=comp,
                hard=hard, t_hours=float(t)))
        return cls(events)

    def poll(self, step: int) -> List[DeviceLossEvent]:
        due = []
        while (self._delivered < len(self._events)
               and self._events[self._delivered].step <= step):
            due.append(self._events[self._delivered])
            self._delivered += 1
        return due

    def inject(self, step: int, node: int, *, component: str = "operator",
               hard: bool = False):
        ev = DeviceLossEvent(step=step, node=node, component=component,
                             hard=hard)
        i = self._delivered          # keep the undelivered tail step-sorted
        while i < len(self._events) and self._events[i].step <= ev.step:
            i += 1
        self._events.insert(i, ev)

    @property
    def pending(self) -> int:
        return len(self._events) - self._delivered


class DevicePool:
    """This process's jax devices grouped into failure-domain nodes."""

    def __init__(self, devices=None, gpus_per_node: int = 0):
        self.devices = list(devices) if devices is not None \
            else list(jax.devices())
        self.gpus_per_node = gpus_per_node or len(self.devices)
        self._dead_nodes: set = set()

    @property
    def n_nodes(self) -> int:
        return math.ceil(len(self.devices) / self.gpus_per_node)

    @property
    def dead_nodes(self) -> Tuple[int, ...]:
        return tuple(sorted(self._dead_nodes))

    def node_devices(self, node: int) -> List:
        lo = node * self.gpus_per_node
        return self.devices[lo:lo + self.gpus_per_node]

    def kill_node(self, node: int):
        if not 0 <= node < self.n_nodes:
            raise ValueError(f"node {node} outside pool of {self.n_nodes}")
        self._dead_nodes.add(node)

    def alive_devices(self) -> List:
        return [d for n in range(self.n_nodes) if n not in self._dead_nodes
                for d in self.node_devices(n)]

    @property
    def alive_count(self) -> int:
        return len(self.alive_devices())

    def fabric(self, base: FabricSpec = FABRIC) -> FabricSpec:
        """A FabricSpec scaled to this pool (for planner scoring)."""
        return dataclasses.replace(base, nodes=self.n_nodes,
                                   gpus_per_node=self.gpus_per_node, pods=1)


# ---------------------------------------------------------------------------
# Callbacks
class TrainerCallback:
    """Observer hooks for the runtime; all methods optional."""

    def on_state_change(self, trainer: "Trainer", old: Optional[RunnerState],
                        new: RunnerState):
        pass

    def on_step(self, trainer: "Trainer", step: int, metrics: Dict):
        pass

    def on_checkpoint(self, trainer: "Trainer", step: int):
        pass

    def on_fault(self, trainer: "Trainer", event: DeviceLossEvent):
        pass

    def on_recovery(self, trainer: "Trainer", record: "RecoveryRecord"):
        pass

    def close(self):
        pass


class LoggingCallback(TrainerCallback):
    def __init__(self, every: int = 5):
        self.every = every
        self._t0 = time.time()

    def on_state_change(self, trainer, old, new):
        if new != RunnerState.RUNNING or old in (None, RunnerState.INIT):
            print(f"[runtime] {old.value if old else '-'} -> {new.value}",
                  flush=True)

    def on_step(self, trainer, step, metrics):
        if step % self.every == 0 or step == trainer.total_steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):8.4f} "
                  f"gnorm {float(metrics.get('grad_norm', float('nan'))):8.3f} "
                  f"lr {float(metrics.get('lr', float('nan'))):.2e} "
                  f"({time.time() - self._t0:6.1f}s)", flush=True)

    def on_checkpoint(self, trainer, step):
        print(f"[ckpt] step {step} committed (safe preemption point)",
              flush=True)

    def on_fault(self, trainer, event):
        print(f"[fault] step {event.step}: {event.component} on node "
              f"{event.node} ({'hard' if event.hard else 'drain'})",
              flush=True)

    def on_recovery(self, trainer, rec):
        print(f"[recover] step {rec.resume_step}: {rec.chips_before}->"
              f"{rec.chips_after} chips via {rec.policy}, lost "
              f"{rec.lost_steps} steps, {rec.time_to_recover_s:.2f}s "
              f"({rec.plan_before} -> {rec.plan_after})", flush=True)


class TelemetryCallback(TrainerCallback):
    """Streams step + recovery records through :class:`RunTelemetry`."""

    def __init__(self, telemetry: RunTelemetry):
        self.telemetry = telemetry

    def on_step(self, trainer, step, metrics):
        self.telemetry.step(step, metrics)

    def on_recovery(self, trainer, rec):
        self.telemetry.recovery(
            rec.resume_step, time_to_recover_s=rec.time_to_recover_s,
            lost_steps=rec.lost_steps, chips_before=rec.chips_before,
            chips_after=rec.chips_after, policy=rec.policy,
            component=rec.component, plan=rec.plan_after)

    def close(self):
        self.telemetry.close()


@dataclass
class RecoveryRecord:
    """One completed fault → drain → re-plan → resume cycle."""
    resume_step: int
    node: int
    component: str
    hard: bool
    policy: str                   # replan | shrink | restart
    lost_steps: int               # steps rolled back (0 when drained)
    chips_before: int
    chips_after: int
    time_to_recover_s: float
    plan_before: str
    plan_after: str
    modeled_step_s_before: Optional[float] = None
    modeled_step_s_after: Optional[float] = None


@dataclass
class TrainReport:
    """What :meth:`Trainer.run` returns."""
    steps_run: int
    losses: List[float]
    recoveries: List[RecoveryRecord]
    state_history: List[RunnerState]
    final_state: RunnerState

    @property
    def improved(self) -> bool:
        return bool(self.losses) and self.losses[-1] < self.losses[0]


# ---------------------------------------------------------------------------
class Trainer:
    """Event-driven elastic training runtime.

    Owns model/state/step-function/pipeline/checkpoints and survives
    node loss: on a :class:`DeviceLossEvent` it drains at the next
    checkpoint boundary (or rolls back for hard faults), re-plans the
    parallelism layout over the surviving devices, reshards the
    checkpoint onto the new mesh, and resumes with the data-pipeline
    cursor intact.

        trainer = Trainer(run_cfg, plan=plan, ckpt_dir=..., ckpt_every=4,
                          fault_monitor=FaultMonitor.from_pairs([(5, 1)]),
                          recovery="replan")
        report = trainer.run()
    """

    RECOVERY_POLICIES = ("replan", "shrink")

    def __init__(self, run_cfg: RunConfig, *,
                 plan: Optional[ParallelPlan] = None,
                 callbacks: Sequence[TrainerCallback] = (),
                 ckpt_dir: str = "", ckpt_every: int = 10, keep: int = 2,
                 restore: bool = False,
                 fault_monitor: Optional[FaultMonitor] = None,
                 recovery: str = "replan",
                 pool: Optional[DevicePool] = None,
                 fabric: Optional[FabricSpec] = None,
                 telemetry: Optional[RunTelemetry] = None):
        if recovery not in self.RECOVERY_POLICIES:
            raise ValueError(f"recovery {recovery!r} not in "
                             f"{self.RECOVERY_POLICIES}")
        self.run_cfg = run_cfg
        self.cfg = run_cfg.model
        self.shape = run_cfg.shape
        self.plan = None if plan is None or plan.is_trivial else plan
        self.callbacks = list(callbacks)
        if telemetry is not None:
            self.callbacks.append(TelemetryCallback(telemetry))
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = max(ckpt_every, 1)
        self.keep = keep
        self.restore = restore
        self.monitor = fault_monitor
        self.recovery_policy = recovery
        self.pool = pool if pool is not None else DevicePool()
        self.fabric = fabric if fabric is not None else self.pool.fabric()

        self.state: Optional[RunnerState] = None
        self.state_history: List[RunnerState] = []
        self.recoveries: List[RecoveryRecord] = []
        self.total_steps = run_cfg.optimizer.total_steps
        self.start_step = 0
        self.mesh: Optional[Mesh] = None
        self.mgr: Optional[CheckpointManager] = None
        self._scope = contextlib.ExitStack()
        self._pending: List[DeviceLossEvent] = []
        self._pipe_state: Optional[Dict] = None
        self._it = None

    # -- state machine ---------------------------------------------------
    def _transition(self, new: RunnerState):
        old = self.state
        self.state = new
        self.state_history.append(new)
        for cb in self.callbacks:
            cb.on_state_change(self, old, new)

    # -- setup -----------------------------------------------------------
    def setup(self):
        from repro.models.model import build_model   # lazy: heavy import
        self._transition(RunnerState.INIT)
        self.model = build_model(self.cfg, remat=self.run_cfg.parallel.remat)
        self.train_state = init_train_state(self.model, self.run_cfg,
                                            jax.random.key(self.run_cfg.seed))
        self.pipe = PackedPipeline(self.cfg, self.shape,
                                   seed=self.run_cfg.seed)
        if self.ckpt_dir:
            self.mgr = CheckpointManager(self.ckpt_dir, keep=self.keep)
            self.mgr.add_completion_observer(self._on_ckpt_committed)
        self._activate_plan()
        self.train_state = self._shard_state(self.train_state)
        if self.restore and self.mgr and self.mgr.latest_step() is not None:
            self.train_state, extra, self.start_step = self._restore_latest()
            self._restore_pipeline(extra)
        return self

    def _on_ckpt_committed(self, step: int):
        for cb in self.callbacks:
            cb.on_checkpoint(self, step)

    def _activate_plan(self):
        """(Re)build the mesh from the surviving devices, re-enter the
        plan's sharding scope, and re-jit the train step."""
        self._scope.close()
        self._scope = contextlib.ExitStack()
        self.mesh = None
        self._mesh_devices: set = set()
        if self.plan is not None:
            devs = self.pool.alive_devices()
            if len(devs) < self.plan.chips:
                raise RuntimeError(
                    f"plan needs {self.plan.chips} devices, only "
                    f"{len(devs)} alive")
            devs = devs[:self.plan.chips]
            self.mesh = self.plan.mesh(devices=devs)
            self._mesh_devices = set(devs)
            self._scope.enter_context(self.plan.activate(self.mesh))
        # the state is donated: the step's new state reuses its buffers,
        # so device memory holds one copy of params + optimizer state
        self.step_fn = jax.jit(make_train_step(self.model, self.run_cfg),
                               donate_argnums=0)

    def _shard_state(self, state):
        if self.plan is None:
            return state
        return jax.device_put(state, self.plan.shardings(
            state, train_state_logical_axes(self.model, self.run_cfg),
            mesh=self.mesh))

    def _restore_latest(self):
        abstract = abstract_train_state(self.model, self.run_cfg)
        axes = train_state_logical_axes(self.model, self.run_cfg)
        if self.mesh is not None:
            return reshard_restore(self.mgr, abstract, axes, self.mesh)
        state, extra, step = self.mgr.restore(abstract)
        return jax.tree.map(jnp.asarray, state), extra, step

    # -- data ------------------------------------------------------------
    def _make_prefetcher(self):
        # The producer yields (batch, cursor-after-draw) pairs so the
        # checkpointed pipeline state always matches the batches actually
        # consumed — snapshotting pipe.state() at save time would be
        # ahead by the prefetch depth.
        pipe = self.pipe

        def producer():
            while True:
                b = pipe.next_batch()
                yield b, pipe.state()

        return Prefetcher(producer(), depth=2)

    def _restore_pipeline(self, extra: Dict):
        rebuild = self._it is not None
        if rebuild:
            self._it.close()
        # fresh instance: a zombie prefetch thread may still advance the
        # old pipeline object's cursor
        self.pipe = PackedPipeline(self.cfg, self.shape,
                                   seed=self.run_cfg.seed)
        if extra and extra.get("pipeline"):
            self.pipe.restore(extra["pipeline"])
            self._pipe_state = extra["pipeline"]
        if rebuild:
            self._it = self._make_prefetcher()

    # -- fault handling --------------------------------------------------
    def inject_fault(self, node: int, *, hard: bool = False,
                     component: str = "operator"):
        """Operator-initiated drain of a node (takes effect next step)."""
        ev = DeviceLossEvent(step=-1, node=node, component=component,
                             hard=hard)
        self._on_fault(ev)

    def _on_fault(self, ev: DeviceLossEvent):
        for cb in self.callbacks:
            cb.on_fault(self, ev)
        if self.mesh is None:
            # unsharded run: nodes are virtual, recovery is a pure
            # checkpoint-restart of the state machine
            self._pending.append(ev)
            return
        node_devs = set(self.pool.node_devices(ev.node))
        self.pool.kill_node(ev.node)
        if not (node_devs & self._mesh_devices):
            # hot-spare case: the struck node was not in the active mesh
            # (paper Table 13: multi-day vendor replacement covered by a
            # hot spare) — no drain needed
            return
        self._pending.append(ev)

    def _current_chips(self) -> int:
        return self.mesh.devices.size if self.mesh is not None else 1

    def _modeled_step_s(self, plan: Optional[ParallelPlan]
                        ) -> Optional[float]:
        if plan is None:
            return None
        if plan.score is not None:
            return plan.score.step_s
        try:
            shape = dict(zip(plan.axis_names, plan.mesh_shape))
            layout = Layout(pod=shape.get("pod", 1),
                            data=shape.get("data", 1),
                            model=shape.get("model", 1),
                            pipe=shape.get("pipe", 1))
            return score_layout(self.cfg, self.shape, layout,
                                fabric=self.fabric).step_s
        except Exception:                       # scoring is best-effort
            return None

    def _replan(self) -> Optional[ParallelPlan]:
        if self.plan is None:
            return None                         # single-device restart
        alive = self.pool.alive_count
        if self.recovery_policy == "shrink":
            mp = self.plan.axis_size("model")
            pod = self.plan.axis_size("pod")
            shape, axes = shrink_data_axis(alive, mp,
                                           pod if pod > 1 else None)
            return ParallelPlan(
                mesh_shape=shape, axis_names=axes, rules=self.plan.rules,
                collectives=CollectiveSchedule(
                    intra_axis="data" if "data" in axes else None,
                    inter_axis="pod" if "pod" in axes else None,
                    compress=self.plan.collectives.compress),
                fabric=self.plan.fabric, name="shrink")
        return replan(self.plan, self.cfg,
                      exclude_nodes=self.pool.dead_nodes, chips=alive,
                      shape=self.shape, fabric=self.fabric)

    def _recover(self, fail_step: int, events: List[DeviceLossEvent],
                 drained: bool) -> int:
        t0 = time.time()
        chips_before = self._current_chips()
        plan_before = self.plan
        ev = events[-1]
        resume_step = fail_step

        self._transition(RunnerState.REPLANNING)
        if self.plan is not None and self.mgr is None:
            self._transition(RunnerState.FAILED)
            raise RuntimeError("device loss without a checkpoint manager: "
                               "sharded state on the dead node is gone")
        self.plan = self._replan()

        self._transition(RunnerState.RESTORING)
        self._activate_plan()
        if self.mgr is not None:
            self.mgr.wait()                 # flush any in-flight async save
            ck = self.mgr.latest_step()
            if ck is None:
                self._transition(RunnerState.FAILED)
                raise RuntimeError("device loss before the first checkpoint")
            self.train_state, extra, resume_step = self._restore_latest()
            self._restore_pipeline(extra)
        # else: plan is None (single-device) and state is still in host
        # memory — a pure state-machine restart with nothing to reload
        lost_steps = 0 if drained else max(0, fail_step - resume_step)

        rec = RecoveryRecord(
            resume_step=resume_step, node=ev.node, component=ev.component,
            hard=ev.hard,
            policy=self.recovery_policy if plan_before is not None
            else "restart",
            lost_steps=lost_steps, chips_before=chips_before,
            chips_after=self._current_chips(),
            time_to_recover_s=time.time() - t0,
            plan_before=plan_before.name if plan_before else "trivial",
            plan_after=self.plan.name if self.plan else "trivial",
            modeled_step_s_before=self._modeled_step_s(plan_before),
            modeled_step_s_after=self._modeled_step_s(self.plan))
        self.recoveries.append(rec)
        for cb in self.callbacks:
            cb.on_recovery(self, rec)
        self._transition(RunnerState.RUNNING)
        return resume_step

    # -- the loop --------------------------------------------------------
    def run(self, steps: Optional[int] = None) -> TrainReport:
        if steps is not None:
            self.total_steps = steps
        if self.state is None:
            self.setup()
        self._transition(RunnerState.RUNNING)
        self._it = self._make_prefetcher()
        losses: List[float] = []
        step = self.start_step
        try:
            while step < self.total_steps:
                if self.monitor is not None:
                    for ev in self.monitor.poll(step):
                        self._on_fault(ev)
                if self._pending:
                    if any(e.hard for e in self._pending):
                        # state on the dead node is gone: roll back (a
                        # hard fault mid-drain abandons the drain too)
                        events, self._pending = self._pending, []
                        step = self._recover(step, events, drained=False)
                        continue
                    if self.state == RunnerState.RUNNING:
                        self._transition(RunnerState.DRAINING)

                with span("trainer.data"):
                    batch, pipe_state = next(self._it)
                with span("trainer.put"):
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                with span("trainer.step"):
                    self.train_state, metrics = self.step_fn(
                        self.train_state, batch)
                self._pipe_state = pipe_state
                with span("trainer.loss_sync"):
                    losses.append(float(metrics["loss"]))
                with span("trainer.callbacks"):
                    for cb in self.callbacks:
                        cb.on_step(self, step, metrics)

                boundary = (step + 1) % self.ckpt_every == 0
                done = step + 1 >= self.total_steps
                if self.state == RunnerState.DRAINING and (boundary or done):
                    # drain barrier: blocking checkpoint, then recover with
                    # zero lost steps
                    if self.mgr is not None:
                        with span("trainer.checkpoint"):
                            self.mgr.drain(
                                step + 1, self.train_state,
                                extra={"pipeline": self._pipe_state})
                    events, self._pending = self._pending, []
                    if done:
                        # nothing left to resume onto — the drain
                        # checkpoint is the final state
                        step += 1
                        continue
                    step = self._recover(step + 1, events, drained=True)
                    continue
                if self.mgr is not None and boundary:
                    with span("trainer.checkpoint"):
                        self.mgr.save(step + 1, self.train_state,
                                      extra={"pipeline": self._pipe_state},
                                      blocking=False)
                step += 1
        except Exception:
            if self.state != RunnerState.FAILED:
                self._transition(RunnerState.FAILED)
            raise
        finally:
            if self._it is not None:
                self._it.close()
            if self.mgr is not None:
                self.mgr.wait()
            if self.state == RunnerState.FAILED:
                self._scope.close()
        self._transition(RunnerState.DONE)
        self._scope.close()
        for cb in self.callbacks:
            cb.close()
        return TrainReport(steps_run=len(losses), losses=losses,
                           recoveries=self.recoveries,
                           state_history=list(self.state_history),
                           final_state=self.state)
