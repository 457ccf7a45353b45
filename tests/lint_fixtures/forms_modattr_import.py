"""Binding-form regression: ``import jax.experimental.pallas as pl``
(plain module import with asname, not ``from ... import``).  The
semantic rules must still resolve the call site — proven by the RL007
bug being found through it."""
import os

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _interpret() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS", "") in ("interpret", "1")


def _acc_kernel(x_ref, o_ref):
    o_ref[...] += x_ref[...]         # RL007: no first-step init


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
