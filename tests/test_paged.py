"""Paged KV cache validation: kernel, block pool, and engine layers.

Kernel: ``flash_decode_paged`` through a SHUFFLED (non-identity) block
table must match the contiguous grouped split-KV kernel bit-for-bit in
f32 — with block_size == block_k both run identical per-split
arithmetic and the same log-sum-exp combine.  BlockPool: refcounted
prefix sharing, copy-on-write tail boundary, LRU reclaim, reservation
admission, and the 1000-cycle leak regression.  Engine: paged decode
reproduces contiguous goldens token-for-token, shared prefixes skip
re-prefilling without cross-talk, cancel returns blocks, capacity caps
retire cleanly, and non-dense archs are rejected.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                      # clean env: deterministic fallback
    from _hyp_fallback import given, settings, strategies as st

from repro.configs import reduced_config
from repro.kernels.flash_decode import flash_decode_pallas, flash_decode_paged
from repro.kernels.ref import (attention_oracle, flash_decode_paged_ref,
                               flash_decode_ref)
from repro.models.model import build_model
from repro.serving import BlockPool, Engine, SamplingParams

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# kernel: paged gather == contiguous
def _page_cache(k, v, kp, BS, seed, extra_blocks=3):
    """Scatter a contiguous (B, T, K, d) cache into a block pool through
    a SHUFFLED table — block j of row b lands at a random pool slot."""
    B, T, K, d = k.shape
    assert T % BS == 0
    nb = T // BS
    NB = B * nb + extra_blocks
    rng = np.random.default_rng(seed)
    perm = rng.permutation(NB)[:B * nb]
    if np.array_equal(perm, np.arange(B * nb)):
        perm = (perm + 1) % NB          # a draw can be the identity
    perm = perm.reshape(B, nb)
    k_pool = np.zeros((NB, BS, K, d), np.float32)
    v_pool = np.zeros((NB, BS, K, d), np.float32)
    kp_pool = np.full((NB, BS), -1, np.int32)
    kc, vc, kpc = (np.asarray(x, np.float32) for x in (k, v, kp[..., None]))
    for b in range(B):
        for j in range(nb):
            blk = perm[b, j]
            k_pool[blk] = kc[b, j * BS:(j + 1) * BS]
            v_pool[blk] = vc[b, j * BS:(j + 1) * BS]
            kp_pool[blk] = np.asarray(kp, np.int32)[b, j * BS:(j + 1) * BS]
    bt = perm.astype(np.int32)
    return (jnp.asarray(k_pool).astype(k.dtype),
            jnp.asarray(v_pool).astype(v.dtype),
            jnp.asarray(kp_pool), jnp.asarray(bt))


def _inputs(B, T, H, K, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, H, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, K, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, K, d), jnp.float32).astype(dtype)
    return q, k, v


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3),                        # batch
       st.sampled_from([(4, 4), (8, 1), (8, 2)]),  # (H, K): MHA/MQA/GQA
       st.sampled_from([16, 32]),                # head_dim
       st.sampled_from([32, 64]),                # cache tokens
       st.integers(0, 2 ** 16))                  # seed
def test_paged_equals_contiguous_bitexact(B, hk, d, T, seed):
    """Property: paged decode through a shuffled block table is BITWISE
    equal to the contiguous kernel in f32 (block_size == block_k)."""
    H, K = hk
    BS = 16
    q, k, v = _inputs(B, T, H, K, d, seed=seed)
    L = 1 + seed % T                              # partial fill per row
    kp = jnp.broadcast_to(
        jnp.where(jnp.arange(T) < L, jnp.arange(T), -1), (B, T))
    qp = jnp.full((B, 1), L, jnp.int32)
    k_pool, v_pool, kp_pool, bt = _page_cache(k, v, kp, BS, seed)
    assert not np.array_equal(np.asarray(bt).ravel(),
                              np.arange(bt.size))     # genuinely shuffled
    contig = flash_decode_pallas(q, k, v, qp, kp, block_k=BS,
                                 interpret=True)
    paged = flash_decode_paged(q, k_pool, v_pool, qp, kp_pool, bt,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(contig))
    # the jnp twin pair agrees bitwise too (gather then identical math)
    np.testing.assert_array_equal(
        np.asarray(flash_decode_paged_ref(q, k_pool, v_pool, qp, kp_pool,
                                          bt)),
        np.asarray(flash_decode_ref(q, k, v, qp, kp)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_vs_oracle_dtypes(dtype):
    """Paged kernel + twin match the naive oracle within dtype tolerance
    (bf16 within the contiguous kernel's existing tolerances)."""
    B, T, H, K, d, BS = 2, 64, 8, 2, 32, 16
    q, k, v = _inputs(B, T, H, K, d, dtype, seed=2)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    qp = jnp.full((B, 1), T, jnp.int32)
    k_pool, v_pool, kp_pool, bt = _page_cache(k, v, kp, BS, seed=2)
    G = H // K
    want = attention_oracle(q, jnp.repeat(k, G, axis=2),
                            jnp.repeat(v, G, axis=2), qp, kp)
    got = flash_decode_paged(q, k_pool, v_pool, qp, kp_pool, bt,
                             interpret=True)
    twin = flash_decode_paged_ref(q, k_pool, v_pool, qp, kp_pool, bt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(twin, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [8, 24])
def test_paged_sliding_window(window):
    B, T, H, K, d, BS = 2, 64, 8, 2, 16, 16
    q, k, v = _inputs(B, T, H, K, d, seed=7)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    qp = jnp.full((B, 1), T, jnp.int32)
    k_pool, v_pool, kp_pool, bt = _page_cache(k, v, kp, BS, seed=7)
    contig = flash_decode_pallas(q, k, v, qp, kp, window=window,
                                 block_k=BS, interpret=True)
    paged = flash_decode_paged(q, k_pool, v_pool, qp, kp_pool, bt,
                               window=window, interpret=True)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(contig))


def test_paged_unmapped_blocks_and_no_cross_talk():
    """Rows with -1 (unmapped) table entries and mixed lengths: each row
    equals its own solo contiguous decode; a fully-unmapped row is 0."""
    B, T, H, K, d, BS = 3, 64, 8, 2, 16, 16
    q, k, v = _inputs(B, T, H, K, d, seed=5)
    lengths = [5, 33, 0]
    kp = jnp.stack([jnp.where(jnp.arange(T) < L, jnp.arange(T), -1)
                    for L in lengths])
    qp = jnp.asarray(lengths, jnp.int32)[:, None]
    k_pool, v_pool, kp_pool, bt = _page_cache(k, v, kp, BS, seed=5)
    # unmap the blocks past each row's length (the pool never allocated
    # them) — and poison the pool slots they pointed at
    bt = np.asarray(bt).copy()
    for b, L in enumerate(lengths):
        nb = -(-L // BS)
        bt[b, nb:] = -1
    bt = jnp.asarray(bt)
    got = flash_decode_paged(q, k_pool, v_pool, qp, kp_pool, bt,
                             interpret=True)
    for b, L in enumerate(lengths):
        solo = flash_decode_pallas(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                   qp[b:b + 1], kp[b:b + 1], block_k=BS,
                                   interpret=True)
        np.testing.assert_array_equal(np.asarray(got[b]),
                                      np.asarray(solo[0]))
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_array_equal(np.asarray(got[2]), 0.0)


def test_ops_dispatch_paged(monkeypatch):
    """ops.flash_decode_paged: jnp twin on CPU, Pallas kernel under
    REPRO_FORCE_PALLAS=interpret — same numbers either way."""
    from repro.kernels import ops
    B, T, H, K, d, BS = 2, 32, 8, 2, 16, 16
    q, k, v = _inputs(B, T, H, K, d, seed=13)
    kp = jnp.broadcast_to(jnp.arange(T), (B, T))
    qp = jnp.full((B, 1), T, jnp.int32)
    k_pool, v_pool, kp_pool, bt = _page_cache(k, v, kp, BS, seed=13)
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    cpu = ops.flash_decode_paged(q, k_pool, v_pool, qp, kp_pool, bt)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    pal = ops.flash_decode_paged(q, k_pool, v_pool, qp, kp_pool, bt)
    np.testing.assert_allclose(np.asarray(cpu), np.asarray(pal), atol=2e-6)


# ---------------------------------------------------------------------------
# BlockPool unit behaviour
def _toks(rng, n, lo=2, hi=500):
    return rng.integers(lo, hi, n).astype(np.int32)


def test_blockpool_mapping_and_reservation():
    pool = BlockPool(2, num_blocks=8, block_size=4, max_blocks_per_slot=4)
    rng = np.random.default_rng(0)
    p = _toks(rng, 6)                       # 2 blocks of prompt
    cached = pool.acquire_blocks(0, rid=1, prompt=p, max_new=5)
    assert cached == 0                      # cold index: no hits
    assert pool.allocated_blocks(0) == 2
    # ceil((6+5)/4)=3 blocks total -> 1 growth block reserved, unmapped
    assert pool._total_reserved == 1
    assert pool.available_blocks() == 8 - 2 - 1
    # decode up to the block boundary: ensure_block maps the third block
    # and settles the reservation
    pool.lengths[0] = 8
    assert pool.ensure_block(0)
    assert pool.allocated_blocks(0) == 3 and pool._total_reserved == 0
    # the table caps at max_blocks_per_slot
    pool.lengths[0] = 16
    assert not pool.ensure_block(0)
    pool.release(0)
    assert pool.free_blocks == 8 and pool.num_active == 0


def test_blockpool_prefix_sharing_refcounts_and_cow():
    BS = 4
    pool = BlockPool(3, num_blocks=12, block_size=BS, max_blocks_per_slot=4)
    rng = np.random.default_rng(1)
    prompt = _toks(rng, 10)                 # 2 full blocks + partial tail
    pool.acquire_blocks(0, rid=1, prompt=prompt, max_new=1)
    pool.register_prefix(0, prompt)
    # COW boundary: only the FULL blocks are published
    assert len(pool._index) == 2
    tail_blk = int(pool.block_tables[0, 2])
    assert tail_blk >= 0 and tail_blk not in pool._block_hash

    # same prompt again: both full blocks hit, mapped shared
    cached = pool.acquire_blocks(1, rid=2, prompt=prompt, max_new=1)
    assert cached == 2 * BS
    assert pool.prefix_hits == 1 and pool.prefix_hit_tokens == 2 * BS
    for j in range(2):
        shared = int(pool.block_tables[0, j])
        assert int(pool.block_tables[1, j]) == shared
        assert pool.refcount[shared] == 2
    assert int(pool.block_tables[1, 2]) != tail_blk   # private tails

    # a prompt equal in block 0 but not block 1 hits exactly one block
    p2 = prompt.copy()
    p2[BS] += 1
    assert pool.probe_prefix(p2) == 1
    # probe is capped so at least one suffix token remains: a prompt of
    # exactly 2 blocks may hit at most 1 even though both are indexed
    assert pool.probe_prefix(prompt[:2 * BS]) == 1

    # release the publisher: shared blocks stay live via slot 1
    pool.release(0)
    for j in range(2):
        assert pool.refcount[int(pool.block_tables[1, j])] == 1
    # release the last holder: indexed blocks become CACHED, not free
    pool.release(1)
    assert pool.cached_blocks == 2
    assert pool.free_blocks == 12 - 2
    assert pool.probe_prefix(prompt) == 2   # still fully hittable


def test_blockpool_lru_reclaim_and_exhaustion():
    BS = 4
    pool = BlockPool(1, num_blocks=4, block_size=BS, max_blocks_per_slot=4)
    rng = np.random.default_rng(2)
    a, b = _toks(rng, 8), _toks(rng, 8)
    pool.acquire_blocks(0, rid=1, prompt=a, max_new=0)
    pool.register_prefix(0, a)
    pool.release(0)
    pool.acquire_blocks(0, rid=2, prompt=b, max_new=0)
    pool.register_prefix(0, b)
    pool.release(0)
    assert pool.free_blocks == 0 and pool.cached_blocks == 4
    # a third distinct prompt must evict the LRU entries (prompt a's)
    c = _toks(rng, 8)
    pool.acquire_blocks(0, rid=3, prompt=c, max_new=0)
    assert pool.probe_prefix(a) == 0        # a was evicted ...
    assert pool.probe_prefix(b) == 1        # ... b survived (cap at 1)
    # pinned blocks are NOT reclaimable: demanding more must raise
    with pytest.raises(RuntimeError, match="exhausted"):
        for _ in range(5):
            pool._alloc()


def test_blockpool_admission_accounting():
    pool = BlockPool(4, num_blocks=4, block_size=4, max_blocks_per_slot=4)
    rng = np.random.default_rng(3)
    p = _toks(rng, 8)
    assert pool.can_admit(p, max_new=8)     # needs 4 blocks == pool
    pool.acquire_blocks(0, rid=1, prompt=p, max_new=8)
    # 2 mapped + 2 reserved: nothing left although 2 blocks are free
    assert pool.free_blocks == 2
    assert not pool.can_admit(_toks(rng, 4), max_new=1)
    pool.release(0)
    assert pool.can_admit(_toks(rng, 4), max_new=1)


def test_blockpool_leak_regression_1000_cycles():
    """1000 acquire/release cycles over varied prompts (some shared,
    some evicting) conserve every block: free + cached == num_blocks and
    no refcount survives."""
    BS = 4
    pool = BlockPool(4, num_blocks=16, block_size=BS,
                     max_blocks_per_slot=4)
    rng = np.random.default_rng(4)
    prompts = [_toks(rng, int(rng.integers(1, 13))) for _ in range(17)]
    for i in range(1000):
        slot = int(rng.integers(4))
        if pool.owner[slot] is not None:
            pool.release(slot)
        p = prompts[int(rng.integers(len(prompts)))]
        if not pool.can_admit(p, max_new=3):
            continue
        pool.acquire_blocks(slot, rid=i, prompt=p, max_new=3)
        if rng.random() < 0.5:
            pool.register_prefix(slot, p)
        if rng.random() < 0.5:
            pool.lengths[slot] = min(len(p) + 3, 16)
            pool.ensure_block(slot)
    for slot in range(4):
        if pool.owner[slot] is not None:
            pool.release(slot)
    assert pool.free_blocks + pool.cached_blocks == 16
    assert pool._total_reserved == 0
    live = {blk for blk, _ in pool._index.values()}
    for blk in range(16):
        assert pool.refcount[blk] == 0
        assert (blk in live) == (blk in pool._block_hash)


# ---------------------------------------------------------------------------
# engine integration
@pytest.fixture(scope="module")
def gemma():
    cfg = reduced_config("gemma-2b")
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prompt(rng, n, vocab=500):
    return rng.integers(2, vocab, n).astype(np.int32)


@pytest.mark.parametrize("sampling", [
    SamplingParams(max_new_tokens=6),
    SamplingParams(temperature=0.8, top_k=20, seed=7, max_new_tokens=6)])
def test_engine_paged_matches_contiguous_tokens(gemma, sampling):
    """Greedy AND seeded-sampling decodes agree token-for-token between
    the paged and contiguous engines on a mixed-length batch."""
    cfg, model, params = gemma
    rng = np.random.default_rng(42)
    prompts = [_prompt(rng, n) for n in (5, 23, 12, 7, 31, 4)]

    contig = Engine(model, params, slots=3, prefill_len=32, cache_len=48)
    paged = Engine(model, params, slots=3, prefill_len=32, cache_len=48,
                   block_size=16)
    a = [r.tokens for r in contig.generate(prompts, sampling, max_ticks=99)]
    b = [r.tokens for r in paged.generate(prompts, sampling, max_ticks=99)]
    assert a == b
    # every block came back: nothing pinned after the batch drains
    assert (paged.pool.free_blocks + paged.pool.cached_blocks
            == paged.pool.num_blocks)


def test_engine_shared_prefix_hits_and_no_cross_talk(gemma):
    """Requests sharing a system prompt skip re-prefilling the shared
    blocks yet decode exactly like solo runs (correct RoPE positions —
    any off-by-one in suffix positions changes the tokens)."""
    cfg, model, params = gemma
    rng = np.random.default_rng(3)
    sys_prompt = _prompt(rng, 16)                 # 2 full 8-token blocks
    prompts = [np.concatenate([sys_prompt, _prompt(rng, n)])
               for n in (5, 9, 3, 7)]

    def solo(p):
        e = Engine(model, params, slots=1, prefill_len=32, cache_len=48)
        return e.generate([p], max_ticks=60)[0].tokens

    golden = [solo(p) for p in prompts]
    e = Engine(model, params, slots=2, prefill_len=32, cache_len=48,
               block_size=8)
    res = e.generate(prompts, max_ticks=120)
    assert [r.tokens for r in res] == golden
    st_ = e.pool.prefix_stats()
    assert st_["hits"] == 3 and st_["hit_tokens"] == 3 * 16
    # the hit requests prefilled only their suffixes
    hit_metrics = [r.metrics for r in res[1:]]
    assert all(m.prefix_cached_tokens == 16 for m in hit_metrics)
    assert all(m.prefilled_tokens == m.prompt_tokens - 16
               for m in hit_metrics)


def test_engine_admission_blocks_on_blocks_not_slots(gemma):
    """A pool smaller than slots x cache_len admits by free BLOCKS: with
    room for one request at a time the rest queue — and still finish."""
    cfg, model, params = gemma
    rng = np.random.default_rng(5)
    prompts = [_prompt(rng, 12) for _ in range(3)]
    e = Engine(model, params, slots=4, prefill_len=16, cache_len=32,
               block_size=16, num_blocks=2, prefix_cache=False)
    for p in prompts:
        # ceil((12 + 8) / 16) = 2 blocks: exactly one request fits
        e.submit(p, SamplingParams(max_new_tokens=8))
    e.step()
    assert e.pool.num_active == 1 and len(e.queue) == 2   # block-gated
    done = e.run(max_ticks=120)
    assert len(done) == 3
    assert all(len(r.tokens) == 8 for r in done.values())


def test_engine_cancel_returns_blocks_leak_regression(gemma):
    """Satellite: acquire/cancel cycles (queued, mid-decode, and shared-
    prefix holders) restore the free-block count to baseline."""
    cfg, model, params = gemma
    rng = np.random.default_rng(7)
    e = Engine(model, params, slots=2, prefill_len=16, cache_len=32,
               block_size=8)
    baseline = e.pool.num_blocks
    sys_prompt = _prompt(rng, 8)                  # 1 shareable block
    for i in range(40):
        p = np.concatenate([sys_prompt, _prompt(rng, 1 + i % 6)])
        ra = e.submit(p, SamplingParams(max_new_tokens=8))
        rb = e.submit(_prompt(rng, 4), SamplingParams(max_new_tokens=8))
        if i % 3 == 0:
            e.cancel(rb)                          # still queued
            e.step()
            e.cancel(ra)                          # mid-decode
        else:
            e.step()
            e.cancel(ra)
            e.cancel(rb)
        e.run(max_ticks=30)                       # drain leftovers
        assert e.pool.num_active == 0
        assert e.pool.free_blocks + e.pool.cached_blocks == baseline
        assert e.pool._total_reserved == 0
    assert (e.pool.refcount == 0).all()
    # cancelled requests still get cache-memory accounting stamped
    cancelled = [r for r in e.finished.values()
                 if r.done_reason == "cancelled" and r.tokens]
    assert cancelled
    assert all(r.metrics.kv_allocated_bytes >= r.metrics.kv_used_bytes > 0
               for r in cancelled)


def test_engine_paged_capacity_retires_as_length(gemma):
    """Paged slots do NOT ring-wrap (a shared block may hold another
    request's history): hitting cache_len retires with reason=length."""
    cfg, model, params = gemma
    rng = np.random.default_rng(9)
    e = Engine(model, params, slots=1, prefill_len=32, cache_len=32,
               block_size=16)
    res = e.generate([_prompt(rng, 30)],
                     SamplingParams(max_new_tokens=50), max_ticks=60)[0]
    assert res.done_reason == "length"
    assert len(res.tokens) == 32 - 30 + 1       # tok0 + decode to the cap
    assert e.pool.free_blocks + e.pool.cached_blocks == e.pool.num_blocks


def test_engine_paged_rejects_non_dense_archs():
    """SSM / sliding-window caches have no paged layout: fail loudly at
    construction, not with silent corruption mid-decode."""
    for arch in ("mamba2-1.3b", "mixtral-8x22b"):
        cfg = reduced_config(arch)
        model = build_model(cfg, remat="none")
        params = model.init(jax.random.key(0))
        with pytest.raises(NotImplementedError, match="[Pp]aged"):
            Engine(model, params, slots=1, prefill_len=16, cache_len=32,
                   block_size=16)


def test_engine_paged_kv_accounting_and_stats(gemma):
    """Per-request allocated-vs-used KV bytes and pool stats surface
    through metrics / stats() / telemetry summary."""
    cfg, model, params = gemma
    rng = np.random.default_rng(11)
    e = Engine(model, params, slots=2, prefill_len=16, cache_len=64,
               block_size=16)
    res = e.generate([_prompt(rng, 5), _prompt(rng, 12)],
                     SamplingParams(max_new_tokens=3), max_ticks=40)
    bpt = e.kv_bytes_per_token
    assert bpt == cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    for r in res:
        m = r.metrics
        # the FINAL sampled token is never written back to the cache
        used = (m.prompt_tokens + len(r.tokens) - 1) * bpt
        assert m.kv_used_bytes == used
        assert m.kv_allocated_bytes % (e.block_size * bpt) == 0
        assert used <= m.kv_allocated_bytes < used + e.block_size * bpt
        assert m.prefilled_tokens == m.prompt_tokens
    s = e.stats()
    assert s["block_size"] == 16 and s["num_blocks"] == 8
    assert 0 < s["kv_utilization"] <= 1.0
    assert s["kv_used_mb"] <= s["kv_allocated_mb"]
    assert s["prefix"]["misses"] == 2


def test_engine_paged_under_interpret(gemma, monkeypatch):
    """The Pallas paged kernel body actually executes in the engine
    decode path under REPRO_FORCE_PALLAS=interpret and reproduces the
    CPU twin's greedy tokens."""
    cfg, model, params = gemma
    rng = np.random.default_rng(13)
    prompts = [_prompt(rng, 7), _prompt(rng, 12)]

    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    e1 = Engine(model, params, slots=2, prefill_len=16, cache_len=32,
                block_size=16)
    want = [r.tokens for r in e1.generate(prompts, max_ticks=40)]

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "interpret")
    e2 = Engine(model, params, slots=2, prefill_len=16, cache_len=32,
                block_size=16)
    got = [r.tokens for r in e2.generate(prompts, max_ticks=40)]
    assert got == want
