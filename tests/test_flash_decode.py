"""Grouped split-KV flash-decode kernel validation.

Interpret-mode Pallas vs the pure-jnp twin (``ref.flash_decode_ref``)
and the naive oracle, across GQA/MQA/MHA groupings, ring-buffer
wraparound, mixed per-slot lengths (SlotPool serving), sliding windows,
tanh softcap, and split-KV reduction — plus the property that the
grouped kernel equals the retired repeat-then-flash path exactly.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                      # clean env: deterministic fallback
    from _hyp_fallback import given, settings, strategies as st

from repro.kernels.flash_decode import (contiguous_block, flash_decode_pallas,
                                        flash_decode_pallas_quant)
from repro.kernels.ref import (attention_oracle, flash_attention_ref,
                               flash_decode_ref)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _decode_inputs(B, T, H, K, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, K, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, K, d), jnp.float32).astype(dtype)
    return q, k, v


def _oracle(q, k, v, q_pos, k_pos, **kw):
    """Repeat grouped K/V to full head count, run the naive oracle."""
    G = q.shape[2] // k.shape[2]
    return attention_oracle(q, jnp.repeat(k, G, axis=2),
                            jnp.repeat(v, G, axis=2), q_pos, k_pos, **kw)


def _check(q, k, v, q_pos, k_pos, *, block_k=512, dtype=jnp.float32, **kw):
    got = flash_decode_pallas(q, k, v, q_pos, k_pos, block_k=block_k,
                              interpret=True, **kw)
    twin = flash_decode_ref(q, k, v, q_pos, k_pos, **kw)
    want = _oracle(q, k, v, q_pos, k_pos, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(twin, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("H,K", [(4, 4), (8, 1), (8, 2), (16, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_groupings_vs_oracle(H, K, dtype):
    """MHA (G=1), MQA (K=1) and two GQA groupings match the oracle."""
    B, T, d = 2, 128, 32
    q, k, v = _decode_inputs(B, T, H, K, d, dtype)
    qp = jnp.full((B, 1), T, jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    _check(q, k, v, qp, kp, dtype=dtype)


@pytest.mark.parametrize("block_k", [16, 32, 64, 128])
def test_split_kv_reduction_invariant(block_k):
    """The LSE epilogue makes the result independent of the split count."""
    B, T, H, K, d = 2, 128, 8, 2, 32
    q, k, v = _decode_inputs(B, T, H, K, d)
    qp = jnp.full((B, 1), T, jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    one = flash_decode_pallas(q, k, v, qp, kp, block_k=T, interpret=True)
    split = flash_decode_pallas(q, k, v, qp, kp, block_k=block_k,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(split), np.asarray(one),
                               atol=2e-6, rtol=2e-6)


def test_ring_buffer_wraparound():
    """Ring cache past capacity: slot s holds position p with p % T == s,
    the newest T positions — decode must attend exactly to those."""
    B, T, H, K, d = 2, 32, 8, 2, 16
    total = 52                                  # wrapped 20 slots past cap
    q, k, v = _decode_inputs(B, T, H, K, d, seed=3)
    slots = jnp.arange(T)
    kp = jnp.where(slots < total % T, slots + (total // T) * T,
                   slots + (total // T - 1) * T)
    assert int(kp.min()) == total - T and int(kp.max()) == total - 1
    kp = jnp.broadcast_to(kp, (B, T))
    qp = jnp.full((B, 1), total, jnp.int32)
    _check(q, k, v, qp, kp, block_k=16)


def test_mixed_per_slot_lengths_and_empty_slots():
    """SlotPool serving: co-batched rows at different lengths, -1 pads."""
    B, T, H, K, d = 3, 32, 8, 2, 16
    lengths = [5, 17, 32]
    q, k, v = _decode_inputs(B, T, H, K, d, seed=5)
    kp = jnp.stack([jnp.where(jnp.arange(T) < L, jnp.arange(T), -1)
                    for L in lengths])
    qp = jnp.asarray(lengths, jnp.int32)[:, None]
    _check(q, k, v, qp, kp, block_k=16)
    # each row must equal its own single-sequence decode (no cross-talk)
    got = flash_decode_pallas(q, k, v, qp, kp, block_k=16, interpret=True)
    for i, L in enumerate(lengths):
        solo = flash_decode_pallas(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                   qp[i:i + 1], kp[i:i + 1], block_k=16,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(solo[0]),
                                   atol=2e-6)


def test_per_row_q_pos_1d_form():
    """Both backends accept the documented (B,) per-row q_pos shape."""
    B, T, H, K, d = 3, 32, 8, 2, 16
    q, k, v = _decode_inputs(B, T, H, K, d, seed=6)
    qp1 = jnp.asarray([7, 19, 32], jnp.int32)               # (B,)
    kp = jnp.stack([jnp.where(jnp.arange(T) < L, jnp.arange(T), -1)
                    for L in [7, 19, 32]])
    want = _oracle(q, k, v, qp1[:, None], kp)
    got_k = flash_decode_pallas(q, k, v, qp1, kp, block_k=16,
                                interpret=True)
    got_r = flash_decode_ref(q, k, v, qp1, kp)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_r), np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("window", [8, 24])
def test_sliding_window(window):
    B, T, H, K, d = 2, 64, 8, 2, 16
    q, k, v = _decode_inputs(B, T, H, K, d, seed=7)
    qp = jnp.full((B, 1), T, jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    _check(q, k, v, qp, kp, window=window, block_k=16)


def test_traced_window_scalar():
    """Per-layer scanned windows arrive as traced scalars (gemma3)."""
    B, T, H, K, d = 1, 64, 4, 2, 16
    q, k, v = _decode_inputs(B, T, H, K, d, seed=8)
    qp = jnp.full((B, 1), T, jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))

    f = jax.jit(lambda w: flash_decode_pallas(
        q, k, v, qp, kp, window=w, block_k=16, interpret=True))
    got = f(jnp.asarray(16, jnp.int32))
    want = _oracle(q, k, v, qp, kp, window=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("softcap", [20.0, 50.0])
def test_softcap(softcap):
    B, T, H, K, d = 2, 64, 8, 2, 16
    q, k, v = _decode_inputs(B, T, H, K, d, seed=9)
    qp = jnp.full((B, 1), T, jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    _check(q, k, v, qp, kp, softcap=softcap, block_k=32)


@pytest.mark.parametrize("T,block_k", [(40, 16), (33, 32), (7, 512),
                                       (1280, 512), (1000, 512)])
def test_cache_len_not_divisible_by_block_k(T, block_k):
    """Regression: T % block_k != 0 used to raise NotImplementedError;
    the block now divides the row (1280 -> 256), or the last block is
    ragged and masked in-kernel (40 / 16, 33 / 32, 1000 / 512), never
    padded by a copy of the cache."""
    B, H, K, d = 2, 8, 2, 16
    q, k, v = _decode_inputs(B, T, H, K, d, seed=21)
    qp = jnp.full((B, 1), T, jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    _check(q, k, v, qp, kp, block_k=block_k)


@pytest.mark.parametrize("T,block_k,want", [
    (1280, 512, (256, False)), (5120, 512, (512, False)),
    (1000, 512, (512, True)), (7, 512, (7, False)),
    (64, 16, (16, False)), (40, 16, (16, True))])
def test_contiguous_block_divides_or_masks(T, block_k, want):
    """A lane-multiple divisor of the row up to block_k, else a ragged
    last block; a row that fits is one block."""
    assert contiguous_block(T, block_k) == want


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("T", [64, 40])
def test_stacked_cache_each_layer_vs_ref(T, quant):
    """The kernel reads layer l of a stacked (L, B, T, K, d) cache in
    place, the layer a traced scalar, and equals the jnp twin on that
    layer's slice for every l (layers filled to different lengths;
    T = 40 leaves a ragged last block)."""
    from repro.kernels import quant as Q
    Lr, B, H, K, d = 3, 2, 8, 2, 16
    ks = jax.random.split(jax.random.key(31), 3)
    q = jax.random.normal(ks[0], (B, 1, H, d))
    k = jax.random.normal(ks[1], (Lr, B, T, K, d))
    v = jax.random.normal(ks[2], (Lr, B, T, K, d))
    lengths = jnp.asarray([T - 3, 9])
    t = jnp.arange(T)
    kp = jnp.stack([jnp.where(t < (lengths - l)[:, None], t, -1)
                    for l in range(Lr)])                     # (L, B, T)
    qp = lengths
    if quant:
        (kq, kscale), (vq, vscale) = (Q.quantize_kv(a, "int8")
                                      for a in (k, v))
        run = jax.jit(lambda l: flash_decode_pallas_quant(
            q, kq, vq, qp, kp, kscale, vscale, block_k=16,
            interpret=True, layer=l))
        twin = lambda l: Q.flash_decode_quant_ref(
            q, kq[l], vq[l], qp, kp[l], kscale[l], vscale[l])
    else:
        run = jax.jit(lambda l: flash_decode_pallas(
            q, k, v, qp, kp, block_k=16, interpret=True, layer=l))
        twin = lambda l: flash_decode_ref(q, k[l], v[l], qp, kp[l])
    for l in range(Lr):
        np.testing.assert_allclose(np.asarray(run(jnp.int32(l))),
                                   np.asarray(twin(l)), atol=2e-5,
                                   rtol=2e-5)


def test_fully_masked_row_returns_zeros():
    """A slot with no live key (fresh ring) must emit zeros, not NaNs or
    a garbage mean-of-v (dead splits carry l == 0 into the epilogue)."""
    B, T, H, K, d = 2, 32, 8, 2, 16
    q, k, v = _decode_inputs(B, T, H, K, d, seed=11)
    kp = jnp.stack([jnp.full((T,), -1, jnp.int32),          # row 0: empty
                    jnp.where(jnp.arange(T) < 4, jnp.arange(T), -1)])
    qp = jnp.asarray([[0], [4]], jnp.int32)
    out = flash_decode_pallas(q, k, v, qp, kp, block_k=16, interpret=True)
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_allclose(
        np.asarray(out[1:]), np.asarray(_oracle(q, k, v, qp, kp)[1:]),
        atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 3),                       # batch
       st.sampled_from([(4, 4), (4, 1), (8, 2), (8, 4)]),  # (H, K)
       st.sampled_from([16, 32, 64]),           # head_dim
       st.sampled_from([32, 64]),               # cache len
       st.integers(0, 2 ** 16))                 # seed
def test_grouped_equals_repeat_then_flash(B, hk, d, T, seed):
    """Property: the grouped decode twin is EXACTLY the retired
    repeat-then-flash path, modulo f32 reduction order."""
    H, K = hk
    q, k, v = _decode_inputs(B, T, H, K, d, seed=seed)
    L = 1 + seed % T                             # partial fill
    kp = jnp.broadcast_to(
        jnp.where(jnp.arange(T) < L, jnp.arange(T), -1), (B, T))
    qp = jnp.full((B, 1), L, jnp.int32)
    G = H // K
    got = flash_decode_ref(q, k, v, qp, kp)
    want = flash_attention_ref(q, jnp.repeat(k, G, axis=2),
                               jnp.repeat(v, G, axis=2), qp, kp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_ops_dispatch_decode_and_grouped_expand(monkeypatch):
    """ops.flash_attention: S==1 grouped K/V dispatches to the decode
    kernel under REPRO_FORCE_PALLAS=interpret and to the jnp twin on
    plain CPU; multi-token grouped K/V expands shard-locally."""
    from repro.kernels import ops
    B, T, H, K, d = 2, 64, 8, 2, 16
    q, k, v = _decode_inputs(B, T, H, K, d, seed=13)
    qp = jnp.full((B, 1), T, jnp.int32)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    want = _oracle(q, k, v, qp, kp)

    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    cpu = ops.flash_attention(q, k, v, qp, kp)
    np.testing.assert_allclose(np.asarray(cpu), np.asarray(want), atol=2e-5)

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    pallas = ops.flash_attention(q, k, v, qp, kp)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(want),
                               atol=2e-5)

    # multi-token (prefill-style) call with grouped K/V: expand + flash
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    S = 8
    ks = jax.random.split(jax.random.key(17), 3)
    qm = jax.random.normal(ks[0], (B, S, H, d))
    km = jax.random.normal(ks[1], (B, S, K, d))
    vm = jax.random.normal(ks[2], (B, S, K, d))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    got = ops.flash_attention(qm, km, vm, pos, pos)
    G = H // K
    want_m = attention_oracle(qm, jnp.repeat(km, G, axis=2),
                              jnp.repeat(vm, G, axis=2), pos, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_m),
                               atol=2e-5)


def test_decode_step_matches_prefill_logits():
    """Model-level integration: one decode_step through the grouped path
    reproduces the full-sequence forward's next-token logits (GQA)."""
    from repro.configs import reduced_config
    from repro.models.lm import DecoderModel

    cfg = reduced_config("qwen3-32b")            # GQA: 4 heads over 2 kv
    model = DecoderModel(cfg)
    params = model.init(jax.random.key(0))
    B, S, Sp = 2, 12, 32                         # bucketed prefill: the
    toks = jax.random.randint(jax.random.key(1), (B, S + 1), 0,
                              cfg.vocab_size)
    # cache needs spare slots past the prompt (a full prefill cache is a
    # ring at capacity — the next write would evict token 0), so prefill
    # right-padded with -1 positions exactly like Engine._join
    toks_p = jnp.zeros((B, Sp), jnp.int32).at[:, :S].set(toks[:, :S])
    pos = jnp.where(jnp.arange(Sp) < S, jnp.arange(Sp), -1)
    logits_p, cache = jax.jit(model.prefill)(
        params, {"tokens": toks_p,
                 "positions": jnp.broadcast_to(pos, (B, Sp)),
                 "length": jnp.full((B,), S, jnp.int32)})
    logits_d, _ = jax.jit(model.decode_step)(
        params, {"tokens": toks[:, S:],
                 "positions": jnp.full((B, 1), S, jnp.int32),
                 "pos_row": jnp.full((B,), S, jnp.int32)}, cache)
    full, _ = jax.jit(model.prefill)(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(full),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("mode", ["interpret", "jnp"])
@pytest.mark.parametrize("T,lengths", [
    (1280, (5, 0, 17)),           # ragged rows and an empty slot
    (1000, (9, 3, 12)),           # no lane-multiple divisor: ragged block
    (1280, (11, 11, 11)),         # one length: the scalar-pos write
], ids=["ragged", "tail", "uniform"])
def test_decode_in_place_matches_recompute(T, lengths, mode, monkeypatch):
    """Three greedy decode steps over the stacked cache, updated in place,
    give each row's next-token logits of a full forward over its
    sequence (the tolerance of the test above) and the same greedy
    tokens; through the Pallas kernel (interpret) and the jnp twin."""
    from repro.configs import reduced_config
    from repro.models.lm import DecoderModel

    cfg = reduced_config("qwen3-32b")            # GQA: 4 heads over 2 kv
    model = DecoderModel(cfg)
    params = model.init(jax.random.key(0))
    B, Sp = len(lengths), 32
    uniform = len(set(lengths)) == 1
    rng = np.random.default_rng(T + sum(lengths))
    seqs = [list(rng.integers(2, cfg.vocab_size, n + 1)) for n in lengths]

    def padded(rows):
        toks = np.zeros((B, Sp), np.int32)
        pos = np.full((B, Sp), -1, np.int32)
        for b, r in enumerate(rows):
            toks[b, :len(r)] = r
            pos[b, :len(r)] = np.arange(len(r))
        return {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos),
                "length": jnp.asarray([max(len(r), 1) for r in rows],
                                      jnp.int32)}

    forward = jax.jit(model.prefill)
    _, c = forward(params, padded([s[:-1] for s in seqs]))
    cache = model.init_cache(B, T)
    for name in ("k", "v", "pos"):
        cache[name] = cache[name].at[:, :, :Sp].set(c[name])
    cache["len"] = jnp.asarray(lengths[0], jnp.int32)
    step = jax.jit(model.decode_step)
    for _ in range(3):
        cur = jnp.asarray([len(s) - 1 for s in seqs], jnp.int32)
        batch = {"tokens": jnp.asarray([[s[-1]] for s in seqs], jnp.int32)}
        if not uniform:
            batch["positions"], batch["pos_row"] = cur[:, None], cur
        with monkeypatch.context() as m:
            if mode == "interpret":
                m.setenv("REPRO_FORCE_PALLAS", "interpret")
            else:
                m.delenv("REPRO_FORCE_PALLAS", raising=False)
            logits, cache = step(params, batch, cache)
        want, _ = forward(params, padded(seqs))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   atol=2e-2, rtol=2e-2)
        got_tok = np.asarray(logits).argmax(-1)
        np.testing.assert_array_equal(got_tok, np.asarray(want).argmax(-1))
        for s, t in zip(seqs, got_tok):
            s.append(int(t))
    for b, s in enumerate(seqs):     # every layer holds the row's columns
        n = len(s) - 1
        np.testing.assert_array_equal(
            np.asarray(cache["pos"][:, b, :n]),
            np.broadcast_to(np.arange(n), (cfg.num_layers, n)))
