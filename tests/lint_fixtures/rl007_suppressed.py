"""RL007 suppressed: the uninitialized += behind a pragma."""
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _acc_kernel(x_ref, o_ref):
    o_ref[...] += x_ref[...]  # repro-lint: disable=RL007


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
    )(x)
