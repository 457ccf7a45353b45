"""Train cells: the program's ``Trainer`` with its input pipeline.

Set-up builds one ``Trainer`` whose state starts from weights the
harness makes from the seed, and drives it through its first three steps
with ``Trainer.run`` (the window's own call and feed).  What the check
needs is read as it goes, before the donated state is overwritten: each
step's loss, the first step's gradient as the optimizer got it (AdamW's
first moment after one step is (1 - beta1) x the clipped gradient), and
after the third step the change of every parameter leaf.  The window
then runs the same ``Trainer`` for ``--seconds``: a callback ends the run
at the first step boundary past the close.

Once the window has closed and the peak memory is read, the trainer is
freed and the float32 reference runs the same three steps on the same
batches from the same weights.  Compared, each by its worst member:
the step losses, the per-leaf norms of the first gradient and the
per-leaf norms of the change after three steps, a leaf's gap taken
against the larger of its reference norm and the median leaf's.  Leaves
whose reference gradient is under ``NOUGHT`` x the median leaf's move by
round-off alone under Adam and are left out of both norms' comparison.

A configuration whose ``train`` section names a ``plan`` (``model=4``)
runs sharded over the cell's chips: the trainer takes the resolved plan,
and the harness draws the initial weights and AdamW state straight into
the shardings the plan gives the trainer's state, since the unsharded
float32 state does not fit one chip.  Its reference spreads each leaf
over the same chips by a placement of its own (``arch.spread``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Dict, List, Optional
from unittest import mock

import numpy as np

from repro.train.runtime import TrainerCallback

from chipbench import traffic
from chipbench.bench import (Cell, arch_module, compile_counter, leaf_name,
                             program_config, span)

CHECK_STEPS = 3
NOUGHT = 1e-3


@dataclasses.dataclass
class TrainRecord:
    """What the per-layer readers see of a train run."""
    config: Dict
    peak: Dict
    chips: int
    t0: float
    t1: float
    steps: int
    batch: int
    seq: int
    trace: object = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def tokens(self) -> int:
        return self.steps * self.batch * self.seq


def _flat(tree) -> Dict[str, float]:
    import jax
    return {leaf_name(p): float(v)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


class Steps(TrainerCallback):
    """Trainer callback: stamps each step's end on the harness clock,
    keeps the host span of each step, reads the first step's gradient,
    and ends the run at the first step boundary past ``t_end``."""

    def __init__(self, arch):
        self.arch = arch
        self.stamps: List[float] = []
        self.t_end: Optional[float] = None
        self.first_grad: Optional[Dict[str, float]] = None
        self._span = None

    def open_span(self):
        self._span = span("train_step")
        self._span.__enter__()

    def close_span(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def on_step(self, trainer, step, metrics):
        now = time.perf_counter()
        self.stamps.append(now)
        if self.first_grad is None:
            b1 = trainer.run_cfg.optimizer.beta1
            m = _flat(self.arch.leaf_norms(trainer.train_state.opt.m))
            self.first_grad = {k: v / (1.0 - b1) for k, v in m.items()}
        self.close_span()
        if self.t_end is not None and now >= self.t_end:
            trainer.total_steps = step + 1
        else:
            self.open_span()

    def close(self):
        self.close_span()


def _opt_config(tr: Dict):
    from repro.core.config import OptimizerConfig
    # warmup 0 and min_lr_ratio 1: the learning rate is constant
    return OptimizerConfig(lr=tr["lr"], beta1=tr["beta1"], beta2=tr["beta2"],
                           eps=tr["eps"], weight_decay=tr["weight_decay"],
                           grad_clip=tr["grad_clip"], warmup_steps=0,
                           total_steps=1 << 30, min_lr_ratio=1.0)


def opt_tuple(tr: Dict) -> tuple:
    return (tr["lr"], tr["beta1"], tr["beta2"], tr["eps"],
            tr["weight_decay"], tr["grad_clip"])


def setup(cell: Cell, seed: int):
    """The trainer, its first CHECK_STEPS steps, and what the check reads
    of them: (trainer, steps callback, readings dict)."""
    import jax
    import jax.numpy as jnp
    from repro.core.config import (ParallelConfig, RunConfig, ShapeConfig,
                                   StepKind)
    from repro.optim import adamw_init
    import repro.train.runtime as runtime
    from repro.train.step import TrainState

    tr = cell.config["train"]
    cfg = program_config(cell.config)
    arch = arch_module(cell.config)
    s32 = traffic.seed32(seed)
    shape = ShapeConfig(cell.name, int(cell.mix["seq_len"]),
                        int(cell.mix["batch"]), StepKind.TRAIN)
    run_cfg = RunConfig(model=cfg, shape=shape,
                        parallel=ParallelConfig(remat=tr["remat"]),
                        optimizer=_opt_config(tr), param_dtype="float32",
                        seed=s32)
    plan = None
    if tr.get("plan"):
        from repro.parallel.plan import resolve_plan
        plan = resolve_plan(tr["plan"], cfg, chips=cell.chips, shape=shape)
    steps = Steps(arch)
    trainer = runtime.Trainer(run_cfg, plan=plan, callbacks=[steps])

    def init_state(model, run_cfg, key):
        abstract = model.abstract_params(jnp.float32)
        if plan is None:
            params = arch.make_params(abstract, s32, jnp.float32)
            return TrainState(params=params, opt=adamw_init(params), ef=None)
        sh = state_shardings(plan, model, run_cfg)
        params = arch.make_params(abstract, s32, jnp.float32,
                                  out_shardings=sh.params)
        opt = jax.jit(adamw_init, out_shardings=sh.opt)(params)
        return TrainState(params=params, opt=opt, ef=None)

    with mock.patch.object(runtime, "init_train_state", init_state):
        trainer.setup()

    fed: List[Dict[str, np.ndarray]] = []
    step_fn = trainer.step_fn

    def recording(state, batch):
        fed.append({k: np.asarray(v) for k, v in batch.items()})
        return step_fn(state, batch)

    trainer.step_fn = recording
    report = trainer.run(CHECK_STEPS)
    trainer.step_fn = step_fn
    params = trainer.train_state.params
    readings = {"losses": list(report.losses), "first_grad": steps.first_grad,
                "change": change_norms(
                    arch, params, trainer.model, s32, None if plan is None
                    else jax.tree.map(lambda a: a.sharding, params)),
                "batches": fed}
    return trainer, steps, readings


def state_shardings(plan, model, run_cfg):
    """The shardings the trainer gives its state under ``plan``, on the
    mesh it builds: the plan's mesh over the first ``plan.chips``
    devices."""
    import jax
    from repro.train.step import (abstract_train_state,
                                  train_state_logical_axes)
    mesh = plan.mesh(devices=jax.devices()[:plan.chips])
    return plan.shardings(abstract_train_state(model, run_cfg),
                          train_state_logical_axes(model, run_cfg), mesh=mesh)


def change_norms(arch, params, model, s32: int,
                 shardings=None) -> Dict[str, float]:
    """Per-leaf norm of params minus the seed's initial weights, drawn
    with ``shardings`` (None: on the default device)."""
    import jax.numpy as jnp
    p0 = arch.make_params(model.abstract_params(jnp.float32), s32,
                          jnp.float32, out_shardings=shardings)
    out = _flat(arch.gap_norms(params, p0))
    del p0
    return out


def window(cell: Cell, trainer, steps: Steps, seconds: float,
           trace_dir=None) -> tuple:
    """Run the trainer for ``seconds``; (TrainRecord, compile events)."""
    import jax
    counter = compile_counter()
    c0 = counter.events
    with span("ready"):           # the first annotation sets up the tracer
        pass
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    steps.stamps.clear()
    trainer.total_steps = 1 << 30
    win = span("window")
    win.__enter__()
    t0 = time.perf_counter()
    steps.t_end = t0 + seconds
    steps.open_span()
    # the step was traced under the plan's mesh and rules; the first run
    # left them at its end, and the window calls it as it was compiled
    with (trainer.plan.activate(trainer.mesh) if trainer.plan is not None
          else contextlib.nullcontext()):
        report = trainer.run()
    steps.close_span()
    win.__exit__(None, None, None)
    in_window = counter.events - c0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    rec = TrainRecord(cell.config, None, cell.chips, t0, steps.stamps[-1],
                      report.steps_run, int(cell.mix["batch"]),
                      int(cell.mix["seq_len"]))
    return rec, in_window


def free(trainer) -> None:
    trainer.train_state = None
    trainer.step_fn = None
    gc.collect()


def reference(arch, cell: Cell, model, seed: int, batches, *,
              low: bool = False) -> Dict:
    """The reference's readings of the same CHECK_STEPS steps, over the
    cell's chips."""
    import jax
    import jax.numpy as jnp
    s32 = traffic.seed32(seed)
    abstract = model.abstract_params(jnp.float32)
    shardings = (arch.spread(abstract, jax.devices()[:cell.chips])
                 if cell.chips > 1 else None)
    p0 = arch.make_params(abstract, s32, jnp.float32, out_shardings=shardings)
    losses, g1, p3 = arch.train_steps(p0, cell.config, batches[:CHECK_STEPS],
                                      opt_tuple(cell.config["train"]),
                                      low=low)
    return {"losses": losses, "first_grad": _flat(g1),
            "change": change_norms(arch, p3, model, s32, shardings)}


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Each number compared: the worst step loss gap, and the worst
    leaf's gap of first-gradient norm and of change norm."""
    g_ref = ref["first_grad"]
    med_g = float(np.median(list(g_ref.values())))
    kept = [k for k, v in g_ref.items() if v >= NOUGHT * med_g]

    def worst(a, b):
        med = float(np.median([b[k] for k in kept]))
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in kept)

    return {"loss_gap": max(abs(a - b) for a, b in
                            zip(prog["losses"], ref["losses"])),
            "grad_norm_gap": worst(prog["first_grad"], g_ref),
            "change_norm_gap": worst(prog["change"], ref["change"])}
