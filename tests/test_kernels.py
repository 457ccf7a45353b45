"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mxp_gemm import mxp_gemm_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.ref import (attention_oracle, flash_attention_ref,
                               mxp_gemm_ref, rmsnorm_ref, ssd_scan_ref)


def _qkv(B, S, H, d, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    mk = lambda k: jax.random.normal(k, (B, S, H, d), jnp.float32).astype(
        dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,S,H,d", [(1, 64, 2, 32), (2, 128, 4, 64),
                                     (2, 256, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 32),
                                           (False, None)])
def test_flash_pallas_vs_oracle(B, S, H, d, dtype, causal, window):
    q, k, v = _qkv(B, S, H, d, dtype)
    qp = jnp.broadcast_to(jnp.arange(S), (B, S))
    got = flash_attention_pallas(q, k, v, qp, qp, causal=causal,
                                 window=window, block_q=32, block_k=32,
                                 interpret=True)
    want = attention_oracle(q, k, v, qp, qp, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_pallas_empty_slots_masked():
    """Ring-buffer decode semantics: k_pos == -1 slots contribute nothing."""
    B, S, H, d = 1, 32, 2, 16
    q, k, v = _qkv(B, S, H, d, jnp.float32)
    qp = jnp.broadcast_to(jnp.arange(S), (B, S))
    kp = jnp.where(jnp.arange(S) < 16, jnp.arange(S), -1)[None]
    got = flash_attention_pallas(q, k, v, qp, jnp.broadcast_to(kp, (B, S)),
                                 block_q=16, block_k=16, interpret=True)
    want = attention_oracle(q[:, :, :, :], k, v, qp,
                            jnp.broadcast_to(kp, (B, S)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("softcap", [10.0, 30.0])
@pytest.mark.parametrize("window", [None, 32])
def test_flash_pallas_softcap_vs_oracle(softcap, window):
    """Gemma-style tanh score cap runs IN the Pallas kernel now (no more
    silent ref fallback for softcap configs)."""
    B, S, H, d = 2, 128, 2, 32
    q, k, v = _qkv(B, S, H, d, jnp.float32, seed=5)
    qp = jnp.broadcast_to(jnp.arange(S), (B, S))
    got = flash_attention_pallas(q, k, v, qp, qp, softcap=softcap,
                                 window=window, block_q=32, block_k=32,
                                 interpret=True)
    want = attention_oracle(q, k, v, qp, qp, softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_ops_flash_attention_softcap_uses_pallas(monkeypatch):
    """ops.flash_attention must not drop to the ref path anymore when a
    softcap is set and Pallas is forced."""
    from repro.kernels import flash_attention as fa_mod
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    called = {}
    orig = fa_mod.flash_attention_pallas

    def spy(*a, **kw):
        called["pallas"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(fa_mod, "flash_attention_pallas", spy)
    B, S, H, d = 1, 64, 2, 16
    q, k, v = _qkv(B, S, H, d, jnp.float32, seed=6)
    qp = jnp.broadcast_to(jnp.arange(S), (B, S))
    got = ops.flash_attention(q, k, v, qp, qp, softcap=15.0)
    assert called.get("pallas"), "softcap call fell back to the ref path"
    want = attention_oracle(q, k, v, qp, qp, softcap=15.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ops_flash_attention_shape_check(monkeypatch):
    """The kernel-or-twin choice is an explicit shape check made before
    the call: lengths the 512 blocks do not tile take the jnp twin."""
    from repro.kernels import flash_attention as fa_mod
    from repro.kernels import ops
    assert fa_mod.flash_attention_fits(64, 64)          # one whole block
    assert fa_mod.flash_attention_fits(1024, 4096)
    assert not fa_mod.flash_attention_fits(520, 520)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    called = []
    monkeypatch.setattr(fa_mod, "flash_attention_pallas",
                        lambda *a, **kw: called.append(1))
    B, S, H, d = 1, 520, 1, 16
    q, k, v = _qkv(B, S, H, d, jnp.float32, seed=7)
    qp = jnp.broadcast_to(jnp.arange(S), (B, S))
    got = ops.flash_attention(q, k, v, qp, qp, chunk=520)
    assert not called
    want = attention_oracle(q, k, v, qp, qp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("softcap", [None, 20.0])
def test_flash_pallas_grads_vs_ref(softcap):
    """The kernel's custom VJP (kernel forward, recompute backward) gives
    the gradients of the jnp twin."""
    B, S, H, d = 2, 64, 2, 16
    q, k, v = _qkv(B, S, H, d, jnp.float32, seed=8)
    qp = jnp.broadcast_to(jnp.arange(S), (B, S))
    kp = qp.at[1, 40:].set(-1)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    pallas = lambda q, k, v: flash_attention_pallas(
        q, k, v, qp, kp, softcap=softcap, block_q=16, block_k=16,
        interpret=True)
    ref = lambda q, k, v: flash_attention_ref(q, k, v, qp, kp,
                                              softcap=softcap, chunk=16)
    got = jax.grad(loss(pallas), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=TOL[jnp.float32],
                                   rtol=TOL[jnp.float32])


@pytest.mark.parametrize("softcap", [None, 20.0])
@pytest.mark.parametrize("window", [None, 24])
def test_flash_ref_grads_vs_oracle(softcap, window):
    B, S, H, d = 2, 64, 2, 16
    q, k, v = _qkv(B, S, H, d, jnp.float32, seed=3)
    qp = jnp.broadcast_to(jnp.arange(S), (B, S))

    def f(q, k, v):
        return flash_attention_ref(q, k, v, qp, qp, causal=True,
                                   window=window, softcap=softcap,
                                   chunk=16).sum()

    def g(q, k, v):
        return attention_oracle(q, k, v, qp, qp, causal=True, window=window,
                                softcap=softcap).sum()

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("shape", [(4, 32, 64), (2, 8, 128), (256, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas(shape, dtype):
    x = jax.random.normal(jax.random.key(0), shape, jnp.float32).astype(dtype)
    sc = 1 + 0.1 * jax.random.normal(jax.random.key(1), (shape[-1],))
    rows = int(np.prod(shape[:-1]))
    got = rmsnorm_pallas(x, sc, block_rows=min(rows, 64), interpret=True)
    want = rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype])


@pytest.mark.parametrize("M,K,N,blk", [(128, 256, 128, 128),
                                       (256, 128, 64, 64),
                                       (64, 512, 128, 128)])
def test_mxp_gemm_pallas_vs_ref(M, K, N, blk):
    a = jax.random.normal(jax.random.key(0), (M, K))
    b = jax.random.normal(jax.random.key(1), (K, N))
    got = mxp_gemm_pallas(a, b, block=blk, block_m=min(M, 128),
                          block_n=min(N, 128), interpret=True)
    want = mxp_gemm_ref(a, b, block=blk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_mxp_gemm_quantization_error_bounded():
    """Emulated e4m3 keeps relative GEMM error at the few-percent level —
    the regime iterative refinement is designed for."""
    a = jax.random.normal(jax.random.key(0), (256, 256))
    b = jax.random.normal(jax.random.key(1), (256, 256))
    exact = a @ b
    approx = mxp_gemm_ref(a, b, block=128)
    rel = float(jnp.abs(approx - exact).max() / jnp.abs(exact).max())
    assert 1e-4 < rel < 0.15, rel


@pytest.mark.parametrize("B,S,H,P,N,chunk", [(2, 64, 3, 16, 8, 16),
                                             (1, 128, 2, 32, 16, 32),
                                             (2, 96, 1, 16, 8, 32)])
def test_ssd_pallas_vs_sequential_ref(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, N)) * 0.5
    c = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y1, st1 = ssd_scan_pallas(x, dt, a, b, c, chunk=chunk, interpret=True)
    y2, st2 = ssd_scan_ref(x, dt, a, b, c, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=2e-4)


def test_ssd_model_impl_matches_kernel():
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N = 2, 64, 2, 16, 8
    ks = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, N)) * 0.5
    c = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y1, st1 = ssd_scan_pallas(x, dt, a, b, c, chunk=16, interpret=True)
    y2, st2 = ssd_chunked(x, dt, a, b, c, 16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=2e-4)


def test_ssd_decode_matches_prefill():
    """Step recurrence must continue exactly from the chunked state."""
    from repro.models.ssm import ssd_chunked, ssd_decode_step
    B, S, H, P, N = 1, 24, 2, 8, 4        # prefill 24, full pass 32 (chunk 8)
    T = 32
    ks = jax.random.split(jax.random.key(9), 5)
    x = jax.random.normal(ks[0], (B, T, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, T, N)) * 0.5
    c = jax.random.normal(ks[4], (B, T, N)) * 0.5
    y_full, _ = ssd_chunked(x, dt, a, b, c, 8)
    _, st = ssd_chunked(x[:, :S], dt[:, :S], a, b[:, :S], c[:, :S], 8)
    y_step, _ = ssd_decode_step(st, x[:, S], dt[:, S], a, b[:, S], c[:, S])
    np.testing.assert_allclose(np.asarray(y_step),
                               np.asarray(y_full[:, S]), atol=1e-4)
