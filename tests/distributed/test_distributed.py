"""Multi-device semantics via subprocesses with fake CPU devices.

These spawn children with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
so the main pytest process keeps its single device (per the dry-run spec).
Each child prints ``OK`` on success.
"""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _run_child(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0 and "OK" in out.stdout, (
        out.stdout[-1500:], out.stderr[-3000:])
    return out.stdout


def test_pipeline_matches_unpipelined():
    _run_child(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.pipeline import make_pipelined_loss
from repro.parallel.sharding import make_mesh
L, D, M, mb = 8, 16, 8, 2
mesh = make_mesh((4,), ("pipe",))
ws = jnp.asarray(np.random.default_rng(0).standard_normal((L, D, D)) * 0.3,
                 jnp.float32)
def stage_fn(p, x):
    h, _ = jax.lax.scan(lambda h, w: (jnp.tanh(h @ w), None), x, p)
    return h
def loss_fn(h, _):
    return jnp.mean(h ** 2)
x = jnp.asarray(np.random.default_rng(1).standard_normal((M, mb, D)),
                jnp.float32)
for vp in (1, 2):
    ploss = make_pipelined_loss(mesh, stage_fn, loss_fn, num_micro=M, vp=vp)
    got = ploss(ws, x, jnp.zeros(()))
    ref = loss_fn(stage_fn(ws, x.reshape(M * mb, D)).reshape(M, mb, D), None)
    assert jnp.allclose(got, ref, atol=1e-6), (vp, got, ref)
    g1 = jax.grad(lambda w: ploss(w, x, jnp.zeros(())))(ws)
    g2 = jax.grad(lambda w: loss_fn(
        stage_fn(w, x.reshape(M * mb, D)).reshape(M, mb, D), None))(ws)
    assert jnp.abs(g1 - g2).max() < 1e-6
print("OK")
""")


def test_hierarchical_collectives_match_flat():
    _run_child(r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.collectives import hierarchical_psum, ring_all_reduce
from repro.parallel.sharding import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
sm = lambda fn: jax.shard_map(fn, mesh=mesh, in_specs=P(("pod", "data")),
                              out_specs=P(("pod", "data")), check_vma=False)
flat = sm(lambda v: jax.lax.psum(jax.lax.psum(v, "data"), "pod"))(x)
hier = sm(lambda v: hierarchical_psum(v, intra_axis="data",
                                      inter_axis="pod"))(x)
assert jnp.allclose(flat, hier)
m2 = make_mesh((8,), ("d",))
sm2 = lambda fn: jax.shard_map(fn, mesh=m2, in_specs=P("d"),
                               out_specs=P("d"), check_vma=False)
y = jnp.arange(128, dtype=jnp.float32).reshape(16, 8)
r = sm2(lambda v: ring_all_reduce(v, "d"))(y)
p = sm2(lambda v: jax.lax.psum(v, "d"))(y)
assert jnp.abs(r - p).max() < 1e-4
print("OK")
""")


def test_tp_sharded_loss_matches_single_device():
    """The TP/FSDP-sharded model loss (laid out by a ParallelPlan) equals
    the unsharded loss."""
    _run_child(r"""
import jax, jax.numpy as jnp
from repro.configs import reduced_config
from repro.core.config import ShapeConfig, StepKind
from repro.models.model import build_model, make_concrete_batch
from repro.parallel.plan import resolve_plan

cfg = reduced_config("qwen3-32b")
model = build_model(cfg, remat="none")
params = model.init(jax.random.key(0))
batch = make_concrete_batch(cfg, ShapeConfig("t", 64, 4, StepKind.TRAIN))
ref = float(model.loss(params, batch)[0])

plan = resolve_plan("data=2,model=4")
with plan.activate() as mesh:
    params_s = jax.device_put(
        params, plan.shardings(params, model.logical_axes(), mesh=mesh))
    got = float(jax.jit(lambda p, b: model.loss(p, b)[0])(params_s, batch))
assert abs(got - ref) < 2e-2, (got, ref)
print("OK")
""")


def test_tp_flash_kernel_grads_match_single_device():
    """Past the 2048-token threshold attention runs the Pallas flash
    kernel, which under a mesh runs per shard inside shard_map: the
    tensor-parallel loss and gradients equal the unsharded ones."""
    _run_child(r"""
import os
os.environ["REPRO_FORCE_PALLAS"] = "interpret"
import jax, jax.numpy as jnp
from repro.configs import reduced_config
from repro.core.config import ShapeConfig, StepKind
from repro.models.model import build_model, make_concrete_batch
from repro.parallel.plan import resolve_plan

cfg = reduced_config("qwen3-32b")
model = build_model(cfg, remat="none")
params = model.init(jax.random.key(0))
batch = make_concrete_batch(cfg, ShapeConfig("t", 4096, 1, StepKind.TRAIN))
grad = jax.jit(jax.value_and_grad(lambda p, b: model.loss(p, b)[0]))
ref, g_ref = grad(params, batch)

plan = resolve_plan("model=4")
with plan.activate() as mesh:
    params_s = jax.device_put(
        params, plan.shardings(params, model.logical_axes(), mesh=mesh))
    got, g = grad(params_s, batch)
assert abs(float(got) - float(ref)) < 2e-2, (got, ref)
for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
    assert float(jnp.abs(a - b).max()) <= 2e-2 * float(jnp.abs(b).max()) \
        + 1e-6, (a.shape,)
print("OK")
""", devices=4)


def test_auto_plan_is_executable():
    """plan_parallelism layouts actually build + run: shard a reduced
    model with the auto plan for this device count and jit a loss."""
    _run_child(r"""
import jax, jax.numpy as jnp
from repro.configs import reduced_config
from repro.core.config import ShapeConfig, StepKind
from repro.models.model import build_model, make_concrete_batch
from repro.parallel.plan import plan_parallelism

cfg = reduced_config("qwen3-32b")
shape = ShapeConfig("t", 64, 4, StepKind.TRAIN)
plan = plan_parallelism(cfg, chips=8, shape=shape)
assert plan.chips == 8 and plan.score is not None
assert plan.scorecard.chosen.layout == plan.score.layout
model = build_model(cfg, remat="none")
params = model.init(jax.random.key(0))
batch = make_concrete_batch(cfg, shape)
ref = float(model.loss(params, batch)[0])
with plan.activate() as mesh:
    params_s = jax.device_put(
        params, plan.shardings(params, model.logical_axes(), mesh=mesh))
    got = float(jax.jit(lambda p, b: model.loss(p, b)[0])(params_s, batch))
assert abs(got - ref) < 2e-2, (got, ref)
print("OK")
""")


def test_elastic_shrink_and_restore():
    """Lose 'nodes', rebuild a smaller mesh, restore the checkpoint onto
    it, keep training — the §8.7 fault-containment path (exercised via
    the launch.elastic deprecation shim on purpose)."""
    _run_child(r"""
import tempfile
import warnings
import jax, jax.numpy as jnp
from repro.checkpoint import CheckpointManager
from repro.configs import reduced_config
from repro.core.config import RunConfig, ShapeConfig, StepKind
with warnings.catch_warnings():
    warnings.simplefilter("ignore", DeprecationWarning)
    from repro.launch.elastic import make_elastic_mesh, reshard_restore, \
        shrink_data_axis
from repro.models.model import build_model, make_concrete_batch
from repro.parallel import sharding as shd
from repro.train.step import (abstract_train_state, init_train_state,
                              make_train_step, train_state_logical_axes)

cfg = reduced_config("gemma-2b")
shape = ShapeConfig("t", 32, 4, StepKind.TRAIN)
run_cfg = RunConfig(model=cfg, shape=shape)
model = build_model(cfg, remat="none")
state = init_train_state(model, run_cfg, jax.random.key(0))
step = make_train_step(model, run_cfg)
batch = make_concrete_batch(cfg, shape)

mgr = CheckpointManager(tempfile.mkdtemp())
mgr.save(1, state)

# full mesh: 8 devices (4 data x 2 model); "failure" leaves 6 => 3x2
assert shrink_data_axis(8, 2) == ((4, 2), ("data", "model"))
assert shrink_data_axis(6, 2) == ((3, 2), ("data", "model"))
mesh = make_elastic_mesh(2, devices=jax.devices()[:6])
assert dict(mesh.shape) == {"data": 3, "model": 2}

abstract = abstract_train_state(model, run_cfg)
axes = train_state_logical_axes(model, run_cfg)
with shd.use_sharding(mesh):
    restored, extra, s = reshard_restore(mgr, abstract, axes, mesh)
    with mesh:
        new_state, metrics = jax.jit(step)(restored, batch)
assert s == 1 and float(metrics["loss"]) > 0
print("OK")
""")


def test_reshard_restore_equivalence():
    """Train K steps on mesh A, kill a node, restore onto the re-planned
    mesh B: the resharded state is bitwise the checkpointed state, and
    continuing matches a never-interrupted run at the same step."""
    _run_child(r"""
import tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.configs import reduced_config
from repro.core.config import OptimizerConfig, RunConfig, ShapeConfig, \
    StepKind
from repro.data import PackedPipeline
from repro.models.model import build_model
from repro.parallel.plan import replan, resolve_plan
from repro.train.runtime import DevicePool, reshard_restore
from repro.train.step import (abstract_train_state, init_train_state,
                              make_train_step, train_state_logical_axes)

cfg = reduced_config("gemma-2b")
shape = ShapeConfig("t", 32, 8, StepKind.TRAIN)
run_cfg = RunConfig(model=cfg, shape=shape,
                    optimizer=OptimizerConfig(lr=3e-4, warmup_steps=2,
                                              total_steps=8))
model = build_model(cfg)
step = make_train_step(model, run_cfg)
axes = train_state_logical_axes(model, run_cfg)

def batches(n):
    pipe = PackedPipeline(cfg, shape, seed=0)
    return [{k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
            for _ in range(n)]

# uninterrupted reference: 6 steps on mesh A (data=4, model=2)
plan_a = resolve_plan("data=4,model=2")
ref_losses = []
with plan_a.activate() as mesh:
    state = init_train_state(model, run_cfg, jax.random.key(0))
    state = jax.device_put(state, plan_a.shardings(state, axes, mesh=mesh))
    sf = jax.jit(step)
    for b in batches(6):
        state, m = sf(state, b)
        ref_losses.append(float(m["loss"]))
ref_state_host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)

# interrupted run: checkpoint at step 4, then node 1 (of 4x2) dies
mgr = CheckpointManager(tempfile.mkdtemp())
with plan_a.activate() as mesh:
    state = init_train_state(model, run_cfg, jax.random.key(0))
    state = jax.device_put(state, plan_a.shardings(state, axes, mesh=mesh))
    sf = jax.jit(step)
    for b in batches(4):
        state, _ = sf(state, b)
    mgr.save(4, state)
ck_host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)

pool = DevicePool(gpus_per_node=2)
pool.kill_node(1)
plan_b = replan(plan_a, cfg, exclude_nodes=pool.dead_nodes,
                chips=pool.alive_count, shape=shape, fabric=pool.fabric())
assert plan_b.chips == 6
mesh_b = plan_b.mesh(devices=pool.alive_devices())
abstract = abstract_train_state(model, run_cfg)
with plan_b.activate(mesh_b):
    restored, extra, s = reshard_restore(mgr, abstract, axes, mesh_b)
    assert s == 4
    # resharding is exact: restored leaves == checkpointed leaves bitwise
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(ck_host)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)), b)
    sf_b = jax.jit(step)
    got_losses = []
    for b in batches(6)[4:]:
        restored, m = sf_b(restored, b)
        got_losses.append(float(m["loss"]))
# loss continuity across the mesh change (bf16 reduction-order tolerance)
np.testing.assert_allclose(got_losses, ref_losses[4:], atol=2e-2)
print("OK")
""")


def test_trainer_elastic_recovery_end_to_end():
    """The full runtime loop on fake devices: FaultMonitor event ->
    DRAINING at the ckpt boundary -> REPLANNING (8->6 chips) ->
    RESTORING (resharded) -> RUNNING, with loss continuity."""
    _run_child(r"""
import tempfile
import numpy as np
from repro.configs import reduced_config
from repro.core.config import OptimizerConfig, RunConfig, ShapeConfig, \
    StepKind
from repro.parallel.plan import resolve_plan
from repro.train.runtime import (DevicePool, FaultMonitor, RunnerState,
                                 Trainer)

cfg = reduced_config("gemma-2b")
shape = ShapeConfig("t", 32, 8, StepKind.TRAIN)
run_cfg = RunConfig(model=cfg, shape=shape,
                    optimizer=OptimizerConfig(lr=3e-4, warmup_steps=2,
                                              total_steps=10))

ref = Trainer(run_cfg, plan=resolve_plan("data=4,model=2"),
              ckpt_dir=tempfile.mkdtemp(), ckpt_every=4).run(10)

tr = Trainer(run_cfg, plan=resolve_plan("data=4,model=2"),
             ckpt_dir=tempfile.mkdtemp(), ckpt_every=4,
             fault_monitor=FaultMonitor.from_pairs([(5, 1)]),
             recovery="replan", pool=DevicePool(gpus_per_node=2))
rep = tr.run(10)
assert rep.final_state == RunnerState.DONE
assert [s.value for s in rep.state_history] == [
    "init", "running", "draining", "replanning", "restoring", "running",
    "done"]
rec = rep.recoveries[0]
assert rec.lost_steps == 0 and rec.resume_step == 8
assert (rec.chips_before, rec.chips_after) == (8, 6)
assert rec.plan_after.startswith("auto/")
np.testing.assert_allclose(rep.losses, ref.losses, atol=2e-2)
print("OK")
""")


def test_dryrun_single_cell_multipod():
    """The mandated multi-pod dry-run path (512 devices) for one cell,
    laid out by the named multi-pod ParallelPlan."""
    _run_child(r"""
import sys
from repro.launch.dryrun import run_cell
from repro.parallel.plan import resolve_plan
rep = run_cell("gemma-2b", "decode_32k",
               plan=resolve_plan("multi-pod"), verbose=False)
assert rep.chips == 512 and rep.mesh == "2x16x16"
assert rep.hlo_flops > 0 and rep.memory_s > 0
print("OK")
""", devices=512, timeout=900)
