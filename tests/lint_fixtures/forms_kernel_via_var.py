"""Binding-form regression: the kernel reaches ``pallas_call`` through
a local variable, not a direct name.  RL007 must still fire."""
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS", "") in ("interpret", "1")


def _acc_kernel(x_ref, o_ref):
    o_ref[...] += x_ref[...]         # RL007: no first-step init


def running_sum(x):
    rows, cols = x.shape
    assert rows % 2 == 0
    half = rows // 2
    body = _acc_kernel               # indirection the resolver must chase
    return pl.pallas_call(
        body,
        grid=(2,),
        in_specs=[pl.BlockSpec((half, cols), lambda si: (si, 0))],
        out_specs=pl.BlockSpec((half, cols), lambda si: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((half, cols), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(x)
