"""RL007 clean twin: the canonical first-step guarded init before the
accumulating store."""
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS", "") in ("interpret", "1")


def _acc_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += x_ref[...]


def running_sum(x):
    rows, cols = x.shape
    assert rows % 2 == 0
    half = rows // 2
    return pl.pallas_call(
        _acc_kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec((half, cols), lambda si: (si, 0))],
        out_specs=pl.BlockSpec((half, cols), lambda si: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((half, cols), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(x)


def run():
    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    return running_sum(x)


def expected():
    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    return x[:4] + x[4:]
