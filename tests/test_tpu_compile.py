"""Main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs: each test lowers a kernel with shapes placed on a
*described* v5e chip (``jax.experimental.topologies``) and compiles it
with the TPU compiler installed beside JAX.  That compiler refuses what
interpret mode accepts — blocks off the (8, 128) tiling, more scoped
VMEM than a kernel may use, a kernel the partitioner cannot split — so
these tests guard the chip path on a CPU-only machine.

Widths: qwen3-32b attention (head_dim 128, 64 q heads, 8 kv heads) and
the mixtral-8x22b expert FFN (d_model 6144, d_ff 16384, 8 experts).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under several
test workers only the one given this file should.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import (flash_decode_paged,
                                        flash_decode_paged_quant,
                                        flash_decode_pallas)
from repro.kernels.moe_gemm import moe_gemm_pallas

H, K, D_HEAD = 64, 8, 128           # qwen3-32b attention
T = 4096                            # train / cache length
B_DEC, BS = 8, 128                  # decode rows, paged block size
NB, MAXB = B_DEC * T // BS, T // BS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip, so keep these out of it
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis()


@pytest.mark.parametrize("B", [1, 2])
def test_flash_attention_forward(one_chip, B):
    qkv = ((B, T, H, D_HEAD), jnp.bfloat16)
    pos = ((B, T), jnp.int32)
    _compile(one_chip,
             lambda q, k, v, qp, kp: flash_attention_pallas(q, k, v, qp, kp),
             qkv, qkv, qkv, pos, pos)


def test_flash_attention_backward(one_chip):
    """The train path differentiates through the kernel's custom VJP."""
    qkv = ((1, T, H, D_HEAD), jnp.bfloat16)
    pos = ((1, T), jnp.int32)

    def loss(q, k, v, qp, kp):
        out = flash_attention_pallas(q, k, v, qp, kp)
        return out.astype(jnp.float32).sum()
    _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
             qkv, qkv, qkv, pos, pos)


def test_flash_decode_contiguous(one_chip):
    kv = ((B_DEC, T, K, D_HEAD), jnp.bfloat16)
    mem = _compile(one_chip,
                   lambda q, k, v, qp, kp: flash_decode_pallas(q, k, v, qp,
                                                               kp),
                   ((B_DEC, 1, H, D_HEAD), jnp.bfloat16), kv, kv,
                   ((B_DEC,), jnp.int32), ((B_DEC, T), jnp.int32))
    # K/V tiles are read in their stored layout: no relayout copy
    assert mem.temp_size_in_bytes < B_DEC * T * K * D_HEAD * 2 // 8


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_flash_decode_paged(one_chip, kv_dtype):
    q = ((B_DEC, 1, H, D_HEAD), jnp.bfloat16)
    pool = ((NB, BS, K, D_HEAD), kv_dtype)
    common = [((B_DEC,), jnp.int32), ((NB, BS), jnp.int32),
              ((B_DEC, MAXB), jnp.int32)]
    if kv_dtype == jnp.int8:
        scale = ((NB, BS, K), jnp.float32)
        mem = _compile(one_chip, flash_decode_paged_quant, q, pool, pool,
                       *common, scale, scale)
    else:
        mem = _compile(one_chip, flash_decode_paged, q, pool, pool, *common)
    assert mem.temp_size_in_bytes < NB * BS * K * D_HEAD // 8


# an op that moves a whole cache: `%x = bf16[dims]{layout} copy(...)`
_CACHE_OP = re.compile(
    r"= \w+\[([\d,]+)\]\S* (pad|dynamic-slice|dynamic-update-slice|copy)\(")


def _moves_kv(line, B, T_row):
    """Whether an HLO line pads, slices, writes back or copies one
    layer's K/V (B, >= T_row, K, d) or the stack of them."""
    m = _CACHE_OP.search(line)
    if m is None:
        return False
    dims = [int(x) for x in m.group(1).split(",")]
    while len(dims) > 4 and dims[0] == 1:
        dims.pop(0)
    return (len(dims) in (4, 5) and tuple(dims[-2:]) == (K, D_HEAD)
            and dims[-4] == B and dims[-3] >= T_row)


@pytest.mark.parametrize("B,T_row", [(64, 1280), (19, 5120)],
                         ids=["batch", "chat"])
def test_generate_step_updates_cache_in_place(one_chip, monkeypatch, B,
                                              T_row):
    """The serve decode tick at qwen3-32b widths (2 layers, a small
    vocabulary) with the cache donated, as ``Engine`` compiles it: no
    layer's K/V is sliced out, padded, written back or copied, the
    program aliases the whole cache from input to output, and its
    temporaries stay under one layer's K."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models.model import build_model
    from repro.serving.engine import make_generate_step
    monkeypatch.setattr(ops, "_use_pallas", lambda: "tpu")
    cfg = dataclasses.replace(get_config("qwen3-32b"), num_layers=2,
                              vocab_size=1024)
    assert (cfg.num_kv_heads, cfg.head_dim) == (K, D_HEAD)
    model = build_model(cfg, remat="none")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place, model.abstract_params(jnp.bfloat16))
    cache = jax.tree.map(place, model.cache_spec(B, T_row))
    rows = [place(jax.ShapeDtypeStruct((B,), dt)) for dt in (
        jnp.int32, jnp.int32, jnp.uint32, jnp.int32, jnp.float32,
        jnp.int32, jnp.float32)]
    compiled = jax.jit(make_generate_step(model), donate_argnums=(1,)).lower(
        params, cache, *rows).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    moved = [line.strip()[:160] for line in hlo.splitlines()
             if _moves_kv(line, B, T_row)]
    assert not moved, moved[:4]
    mem = compiled.memory_analysis()
    kvp = sum(cache[n].size * cache[n].dtype.itemsize
              for n in ("k", "v", "pos"))
    assert mem.alias_size_in_bytes >= kvp
    assert mem.temp_size_in_bytes < B * T_row * K * D_HEAD * 2


def test_moe_gemm_mixtral_widths(one_chip):
    """Its weight tiles need more than the 16 MiB of VMEM v5e scopes by
    default; the kernel asks for what it needs."""
    E, C, D, F = 8, 256, 6144, 16384
    _compile(one_chip,
             lambda x, c, w1, w3, w2: moe_gemm_pallas(x, c, w1, w3, w2),
             ((1, E, C, D), jnp.bfloat16), ((1, E), jnp.int32),
             ((E, D, F), jnp.bfloat16), ((E, D, F), jnp.bfloat16),
             ((E, F, D), jnp.bfloat16))
