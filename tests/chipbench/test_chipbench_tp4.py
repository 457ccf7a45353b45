"""The tensor-parallel train cell at a tiny size, on four CPU devices.

Each test runs in a child process of its own with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the pytest process
keeps its one device), under a time limit of its own, and prints ``OK``.
The children drive the harness without its look for a chip: the sharded
initialiser, the sharded reference, a whole run that comes out correct,
and runs with a fault planted underneath that come out incorrect.
"""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent

PRELUDE = r"""
import jax, jax.numpy as jnp, numpy as np
from chipbench import cells, faults, train
from chipbench.arch import dense_gqa as arch
from chipbench.bench import check, program_config
from chipbench_tiny import tiny_cell
assert len(jax.devices()) == 4
CELL = tiny_cell("qwen3-32b-train-tp4.pack4k")
DEVS = jax.devices()[:4]
SEED = 2**31 + 9151
"""


def _child(code: str, timeout: int = 300) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        str(p) for p in (ROOT, ROOT / "src", HERE))
    out = subprocess.run([sys.executable, "-c", PRELUDE + code], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0 and "OK" in out.stdout, (
        out.stdout[-1500:], out.stderr[-3000:])
    return out.stdout


def test_sharded_initialiser_draws_the_unsharded_weights():
    """Weights drawn into the plan's shardings (the program's state) and
    into the reference's own are the unsharded draw, bit for bit."""
    _child(r"""
from repro.core.config import RunConfig, ShapeConfig, StepKind
from repro.models.model import build_model
from repro.parallel.plan import resolve_plan
cfg = program_config(CELL.config)
model = build_model(cfg, remat="full")
shape = ShapeConfig("t", 64, 1, StepKind.TRAIN)
plan = resolve_plan(CELL.config["train"]["plan"], cfg, chips=4, shape=shape)
run_cfg = RunConfig(model=cfg, shape=shape, param_dtype="float32")
ab = model.abstract_params(jnp.float32)
one = arch.make_params(ab, 7, jnp.float32)
for sh in (train.state_shardings(plan, model, run_cfg).params,
           arch.spread(ab, DEVS)):
    got = arch.make_params(ab, 7, jnp.float32, out_shardings=sh)
    for a, b, s in zip(jax.tree.leaves(got), jax.tree.leaves(one),
                       jax.tree.leaves(sh)):
        assert a.sharding == s
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert any(len(s.device_set) == 4 and not s.is_fully_replicated
               for s in jax.tree.leaves(sh))
print("OK")
""")


def test_sharded_reference_matches_the_unsharded_reference():
    _child(r"""
from repro.models.model import build_model
model = build_model(program_config(CELL.config), remat="full")
ab = model.abstract_params(jnp.float32)
rng = np.random.default_rng(3)
V = CELL.config["vocab_size"]
batches = [{"tokens": rng.integers(2, V, (1, 64)).astype(np.int32),
            "labels": rng.integers(2, V, (1, 64)).astype(np.int32)}
           for _ in range(3)]
opt = train.opt_tuple(CELL.config["train"])
out = []
for sh in (None, arch.spread(ab, DEVS)):
    p0 = arch.make_params(ab, 11, jnp.float32, out_shardings=sh)
    losses, g1, p3 = arch.train_steps(p0, CELL.config, batches, opt)
    if sh is not None:
        assert all(a.sharding.is_equivalent_to(s, a.ndim) for a, s in
                   zip(jax.tree.leaves(p3), jax.tree.leaves(sh)))
    out.append((losses, g1, [np.asarray(a) for a in jax.tree.leaves(p3)]))
(l1, g1, p1), (l4, g4, p4) = out
np.testing.assert_allclose(l4, l1, rtol=1e-5)
np.testing.assert_allclose(jax.tree.leaves(g4), jax.tree.leaves(g1),
                           rtol=1e-4)
for a, b in zip(p4, p1):
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
print("OK")
""")


def test_tp4_run_is_correct_and_its_control_is_not():
    """A whole run on four devices: the state is drawn into the plan's
    shardings (AdamW's m and v with the weights'), nothing compiles in the
    window, the check passes, and the fp8 control fails it."""
    _child(r"""
from unittest import mock
seen = []
real = train.setup
def setup(cell, seed):
    trainer, steps, readings = real(cell, seed)
    st = trainer.train_state
    for p, m, v in zip(*(jax.tree.leaves(t) for t in
                         (st.params, st.opt.m, st.opt.v))):
        assert p.sharding == m.sharding == v.sharding
        seen.append(p.sharding)
    return trainer, steps, readings
with mock.patch.object(train, "setup", setup):
    m = cells.measure(CELL, DEVS, SEED, 1.0)
assert any(not s.is_fully_replicated for s in seen)
assert m.compiles == 0 and m.attempted > 0
assert m.e2e["train_tokens_per_s"] > 0
values, control = cells.compared(m, control=True)
assert all(check(CELL, k, v).ok for k, v in values.items()), values
assert not all(check(CELL, k, v).ok for k, v in control.items()), control
print("OK")
""")


@pytest.mark.parametrize("fault", ["exchange", "keep"])
def test_a_planted_fault_comes_out_incorrect(fault):
    """``exchange``: each chip keeps its own partial sum of the FFN's
    row-parallel product, a fault that exists only across devices;
    ``keep``: the step returns its state unchanged."""
    _child(r"""
import json
lines = []
with faults.FAULTS[%r]():
    cells.run(CELL, DEVS, seed=SEED, seconds=1.0, out=lines.append)
out = json.loads(lines[-1])
assert out["correct"] is False, out["checks"]
print("OK")
""" % fault)
