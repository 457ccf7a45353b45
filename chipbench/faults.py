"""Faults planted in the program under test, to show that a cell's check
catches them: read on the chip by ``calibrate.py check --fault`` and at
a tiny size by the CPU tests.  Each is a context manager that patches the
program while a cell is set up and run, and restores it on exit.

* ``kept_state``: the train step returns the state it was given.
* ``no_exchange``: under a mesh with a ``model`` axis, the FFN's last
  product (rows of w2 over the model axis) is left without its sum over
  the shards, the exchange that tensor parallelism adds; each chip goes
  on with its own partial sum.
"""
from __future__ import annotations

import contextlib
from unittest import mock


@contextlib.contextmanager
def kept_state():
    import repro.train.runtime as runtime
    real = runtime.make_train_step

    def frozen(model, run_cfg):
        step = real(model, run_cfg)

        def keep(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return keep

    with mock.patch.object(runtime, "make_train_step", frozen):
        yield


@contextlib.contextmanager
def no_exchange():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.config import Activation
    from repro.models import layers
    from repro.parallel import sharding
    real = layers.mlp

    def mlp(p, x, cfg):
        mesh = sharding.current_mesh()
        if mesh is None or mesh.shape.get("model", 1) == 1:
            return real(p, x, cfg)
        if cfg.activation != Activation.SWIGLU:
            raise NotImplementedError("no_exchange plants a SwiGLU FFN")
        # the gate and up products as layers.mlp computes them; then each
        # shard multiplies its own columns of h by its own rows of w2 and
        # keeps that partial product, where the program sums them
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w1"].astype(x.dtype)))
        h = h * jnp.einsum("bsd,df->bsf", x, p["w3"].astype(x.dtype))
        h = sharding.constrain(h, "batch", None, "act_mlp")
        y = jax.shard_map(
            lambda h, w: jnp.einsum("bsf,fd->bsd", h, w.astype(h.dtype)),
            mesh=mesh, in_specs=(P(None, None, "model"), P("model", None)),
            out_specs=P(), check_vma=False)(h, p["w2"])
        return sharding.constrain(y, "batch", None, "act_embed")

    with mock.patch.object(layers, "mlp", mlp):
        yield


FAULTS = {"keep": kept_state, "exchange": no_exchange}
