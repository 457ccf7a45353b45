"""Grouped-expert GEMM kernel vs the dense einsum formulation.

The Pallas kernel (interpret mode on CPU) must reproduce the einsum
path in f32 — the dispatch zero-pads dropped/empty capacity slots, and
act(0)·0 @ w2 == 0 in both formulations, so the only divergence is the
order in which XLA's CPU dot sums the D-long contractions (a batched
einsum and a per-block dot block their loops differently): a few f32
ulps.  Gradients run the same einsums and stay bit-exact.  bf16 inputs
accumulate in f32 inside the kernel and get a rounding tolerance.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.kernels import ops
from repro.kernels.moe_gemm import moe_gemm_pallas
from repro.kernels.ref import moe_gemm_ref, resolve_moe_act
from repro.models import moe as M


def _blocks(seed, B, E, C, D, F, dtype=np.float32, shuffle=False):
    """Random capacity blocks shaped like the sort-based dispatch output:
    the first counts[b, e] rows real, the rest exact zeros.  With
    ``shuffle`` the fill order is permuted per block — the kernel must
    not care where in the valid prefix a token came from."""
    rng = np.random.default_rng(seed)
    xe = np.zeros((B, E, C, D), dtype)
    counts = rng.integers(0, C + 1, size=(B, E)).astype(np.int32)
    for b in range(B):
        for e in range(E):
            n = counts[b, e]
            rows = rng.standard_normal((n, D)).astype(dtype)
            if shuffle and n > 1:
                rows = rows[rng.permutation(n)]
            xe[b, e, :n] = rows
    w1 = (rng.standard_normal((E, D, F)) * 0.05).astype(dtype)
    w3 = (rng.standard_normal((E, D, F)) * 0.05).astype(dtype)
    w2 = (rng.standard_normal((E, F, D)) * 0.05).astype(dtype)
    return (jnp.asarray(xe), jnp.asarray(counts), jnp.asarray(w1),
            jnp.asarray(w3), jnp.asarray(w2))


# Reduced Mixtral / DBRX expert geometries (E, C, D, F) — C chosen to
# exercise both the multi-row-block (C % 128 == 0 at C=128 via bm=C)
# and odd-capacity fallback block sizing.
GEOMETRIES = [
    pytest.param(8, 64, 64, 96, id="mixtral-ish"),
    pytest.param(16, 32, 64, 128, id="dbrx-ish"),
]


@pytest.mark.parametrize("E,C,D,F", GEOMETRIES)
@pytest.mark.parametrize("shuffle", [False, True],
                         ids=["ordered", "shuffled"])
def test_kernel_bitexact_f32(E, C, D, F, shuffle):
    xe, counts, w1, w3, w2 = _blocks(0, 2, E, C, D, F, shuffle=shuffle)
    y_k = moe_gemm_pallas(xe, counts, w1, w3, w2, interpret=True)
    y_r = moe_gemm_ref(xe, counts, w1, w3, w2)
    # f32 summation-order bound: D * eps32 relative to the row scale
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=0, atol=D * 2 ** -23 * float(
                                   np.abs(np.asarray(y_r)).max()))
    # empty capacity rows are exact zeros on both paths
    pad = np.arange(C)[None, None, :] >= np.asarray(counts)[..., None]
    assert (np.asarray(y_k)[pad] == 0).all()


@pytest.mark.parametrize("E,C,D,F", GEOMETRIES)
def test_kernel_bf16_tolerance(E, C, D, F):
    xe, counts, w1, w3, w2 = _blocks(1, 2, E, C, D, F, dtype=np.float32)
    cast = lambda a: a.astype(jnp.bfloat16)
    y_k = moe_gemm_pallas(cast(xe), counts, cast(w1), cast(w3), cast(w2),
                          interpret=True)
    y_r = moe_gemm_ref(cast(xe), counts, cast(w1), cast(w3), cast(w2))
    np.testing.assert_allclose(
        np.asarray(y_k, np.float32), np.asarray(y_r, np.float32),
        atol=2e-2, rtol=2e-2)
    assert y_k.dtype == jnp.bfloat16


def test_kernel_gelu_tanh_act():
    xe, counts, w1, w3, w2 = _blocks(2, 1, 4, 32, 48, 64)
    y_k = moe_gemm_pallas(xe, counts, w1, w3, w2, act="gelu_tanh",
                          interpret=True)
    y_r = moe_gemm_ref(xe, counts, w1, w3, w2, act="gelu_tanh")
    # tanh lowers with ULP-level differences inside the Pallas
    # interpreter vs eager XLA; silu stays bit-exact (see above)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-7)


def test_kernel_grads_match_einsum():
    """custom-VJP backward (jax.vjp of the einsum recompute) must equal
    differentiating the einsum directly."""
    xe, counts, w1, w3, w2 = _blocks(3, 2, 4, 32, 48, 64)

    def l_kernel(x, a, b, c):
        return (moe_gemm_pallas(x, counts, a, b, c, interpret=True) ** 2
                ).mean()

    def l_ref(x, a, b, c):
        return (moe_gemm_ref(x, counts, a, b, c) ** 2).mean()

    gk = jax.grad(l_kernel, argnums=(0, 1, 2, 3))(xe, w1, w3, w2)
    gr = jax.grad(l_ref, argnums=(0, 1, 2, 3))(xe, w1, w3, w2)
    for a, b in zip(gk, gr):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_kernel_empty_blocks_skip_to_zero():
    """Blocks the router never filled (counts == 0) must come out as
    exact zeros via the skip path, not garbage from uninitialized acc."""
    xe, counts, w1, w3, w2 = _blocks(4, 2, 4, 32, 48, 64)
    counts = counts.at[0, 1].set(0)
    xe = xe.at[0, 1].set(0.0)
    y = moe_gemm_pallas(xe, counts, w1, w3, w2, interpret=True)
    assert (np.asarray(y[0, 1]) == 0.0).all()


def test_kernel_rejects_bad_shapes_and_acts():
    xe, counts, w1, w3, w2 = _blocks(5, 1, 4, 32, 48, 64)
    with pytest.raises(ValueError):
        moe_gemm_pallas(xe, counts, w1[:2], w3, w2, interpret=True)
    with pytest.raises(ValueError):
        resolve_moe_act("relu")
    with pytest.raises(NotImplementedError):
        # C=32 not divisible by an explicit 24-row block
        moe_gemm_pallas(xe, counts, w1, w3, w2, block_rows=24,
                        interpret=True)


def test_ops_dispatch_falls_back_on_indivisible(monkeypatch):
    """ops.moe_gemm picks its path before the call: with no mesh the
    kernel takes every capacity — a prime one is padded to whole 8-row
    sublane tiles inside the kernel wrapper — and a kernel that fails
    raises instead of being swapped for the jnp twin."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    xe, counts, w1, w3, w2 = _blocks(6, 1, 4, 37, 48, 64)
    y = ops.moe_gemm(xe, counts, w1, w3, w2)
    y_r = moe_gemm_ref(xe, counts, w1, w3, w2)
    assert y.shape == xe.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), rtol=0,
                               atol=48 * 2 ** -23 * float(
                                   np.abs(np.asarray(y_r)).max()))

    import repro.kernels.moe_gemm as MG

    def broken(*a, **k):
        raise NotImplementedError("kernel failure")
    monkeypatch.setattr(MG, "moe_gemm_pallas", broken)
    with pytest.raises(NotImplementedError, match="kernel failure"):
        ops.moe_gemm(xe, counts, w1, w3, w2)


def test_moe_layer_interpret_matches_default(monkeypatch):
    """End-to-end: moe_sorted_capacity under REPRO_FORCE_PALLAS=interpret
    (kernel path) must match the plain CPU run (einsum fallback)."""
    cfg = reduced_config("mixtral-8x22b")
    from repro.models.param import init_tree
    p = init_tree(jax.random.key(0), M.moe_defs(cfg))
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model),
                          jnp.float32)

    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    y_plain, aux_plain = M.moe_sorted_capacity(p, x, cfg)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    y_kern, aux_kern = M.moe_sorted_capacity(p, x, cfg)

    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_plain),
                               atol=1e-6)
    assert float(aux_kern["aux_loss"]) == pytest.approx(
        float(aux_plain["aux_loss"]))
    assert float(aux_kern["dropped_frac"]) == pytest.approx(
        float(aux_plain["dropped_frac"]))


def test_moe_layer_grads_interpret(monkeypatch):
    """Training differentiates through the kernel's custom VJP."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    cfg = reduced_config("mixtral-8x22b")
    from repro.models.param import init_tree
    p = init_tree(jax.random.key(0), M.moe_defs(cfg))
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model),
                          jnp.float32)

    def loss(p):
        y, aux = M.moe_sorted_capacity(p, x, cfg)
        return (y ** 2).mean() + 0.01 * aux["aux_loss"]

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["w1"]).max()) > 0
    assert float(jnp.abs(g["w2"]).max()) > 0
    assert float(jnp.abs(g["router"]).max()) > 0
