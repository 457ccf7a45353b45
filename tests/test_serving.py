"""Serving-engine tests: sampling, request lifecycle, per-slot
correctness (the ``slot_len.max()`` regression), slot recycling,
deprecation shims, CLI flags, and the load-benchmark trace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models.model import build_model
from repro.serving import (ContinuousBatcher, Engine, Request, RequestState,
                           SamplingParams, SlotPool, sample_tokens)


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced_config("gemma-2b")
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prompt(rng, n, vocab=500):
    return rng.integers(2, vocab, n).astype(np.int32)


# ---------------------------------------------------------------------------
# sampling
def test_sampling_determinism_and_filters():
    B, V = 4, 50
    flat = jnp.zeros((B, V))
    seeds = jnp.asarray([7, 7, 8, 8], jnp.uint32)

    def draw(step):
        return np.asarray(sample_tokens(
            flat, seeds, jnp.full((B,), step, jnp.int32),
            jnp.ones(B), jnp.zeros(B, jnp.int32), jnp.ones(B)))

    a, b = draw(0), draw(0)
    np.testing.assert_array_equal(a, b)          # same seed+step => same
    assert a[0] == a[1] and a[2] == a[3]         # per-row seed, not per-slot
    assert (a[0] != a[2]) or (draw(1)[0] != draw(1)[2])
    steps = np.stack([draw(s) for s in range(6)])
    assert len(set(steps[:, 0].tolist())) > 1    # stream varies over steps

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(B, V)))
    argmax = np.asarray(jnp.argmax(logits, -1))
    greedy = np.asarray(sample_tokens(
        logits, seeds, jnp.zeros(B, jnp.int32), jnp.zeros(B),
        jnp.zeros(B, jnp.int32), jnp.ones(B)))
    np.testing.assert_array_equal(greedy, argmax)      # temperature 0

    top1 = np.asarray(sample_tokens(
        logits, seeds, jnp.zeros(B, jnp.int32), jnp.ones(B),
        jnp.ones(B, jnp.int32), jnp.ones(B)))
    np.testing.assert_array_equal(top1, argmax)        # top_k=1

    tiny_p = np.asarray(sample_tokens(
        logits, seeds, jnp.zeros(B, jnp.int32), jnp.ones(B),
        jnp.zeros(B, jnp.int32), jnp.full(B, 1e-6)))
    np.testing.assert_array_equal(tiny_p, argmax)      # nucleus -> top-1


def test_sampling_top_k_support():
    """top_k=2 never samples outside the two largest logits."""
    V = 20
    logits = jnp.asarray(np.arange(V, dtype=np.float32))[None]
    allowed = {V - 1, V - 2}
    for step in range(30):
        t = sample_tokens(logits, jnp.asarray([3], jnp.uint32),
                          jnp.asarray([step], jnp.int32), jnp.ones(1) * 2.0,
                          jnp.asarray([2], jnp.int32), jnp.ones(1))
        assert int(t[0]) in allowed
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)


# ---------------------------------------------------------------------------
# per-slot correctness (the slot_len.max() regression)
def test_mixed_length_batch_matches_single_run(gemma):
    """Two co-batched requests of different lengths must decode exactly as
    they do alone.  The old ContinuousBatcher advanced the pooled cache at
    ``slot_len.max()``: the short slot's RoPE positions and KV write
    columns were those of the LONGEST slot, which leaves holes in the
    cache position rows and shifts every rotary angle — both asserted
    exactly here, so this test fails against that behaviour."""
    cfg, model, params = gemma
    rng = np.random.default_rng(42)
    pa, pb = _prompt(rng, 6), _prompt(rng, 14)

    def alone(p):
        e = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
        res = e.generate([p], max_ticks=50)[0]
        return res.tokens, (np.asarray(e.cache["pos"])[:, 0],
                            np.asarray(e.cache["k"], np.float32)[:, 0])

    toks_a, (pos_a, k_a) = alone(pa)
    toks_b, (pos_b, k_b) = alone(pb)

    e = Engine(model, params, slots=2, prefill_len=16, cache_len=48)
    res = e.generate([pa, pb], max_ticks=50)
    assert res[0].tokens == toks_a
    assert res[1].tokens == toks_b
    # cache columns are written at each slot's OWN length: position rows
    # are gap-free prefixes identical to the batch=1 reference ...
    np.testing.assert_array_equal(np.asarray(e.cache["pos"])[:, 0], pos_a)
    np.testing.assert_array_equal(np.asarray(e.cache["pos"])[:, 1], pos_b)
    # ... and the RoPE'd keys match the reference (a max-length decode
    # rotates the short slot's keys by the wrong angle, an O(1) error)
    np.testing.assert_allclose(np.asarray(e.cache["k"], np.float32)[:, 0],
                               k_a, atol=1e-6)
    np.testing.assert_allclose(np.asarray(e.cache["k"], np.float32)[:, 1],
                               k_b, atol=1e-6)


def test_windowed_arch_mixed_lengths():
    """Ring-buffer caches (sliding-window archs) also write per-row."""
    cfg = reduced_config("mixtral-8x22b")
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(7)
    pa, pb = _prompt(rng, 5, cfg.vocab_size), _prompt(rng, 12, cfg.vocab_size)

    def alone(p):
        e = Engine(model, params, slots=1, prefill_len=16, cache_len=32)
        return e.generate([p], max_ticks=50)[0].tokens

    e = Engine(model, params, slots=2, prefill_len=16, cache_len=32)
    res = e.generate([pa, pb], max_ticks=50)
    assert res[0].tokens == alone(pa)
    assert res[1].tokens == alone(pb)


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "zamba2-7b", "gemma3-4b",
                                  "qwen2-vl-7b"])
def test_mixed_lengths_all_families(arch):
    """Per-slot decode is exact for every cache layout: SSM state,
    hybrid shared-attention KV, gemma3 local:global rings, VLM M-RoPE."""
    cfg = reduced_config(arch)
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    pa = _prompt(rng, 5, cfg.vocab_size)
    pb = _prompt(rng, 11, cfg.vocab_size)

    def alone(p):
        e = Engine(model, params, slots=1, prefill_len=16, cache_len=32)
        return e.generate([p], max_ticks=60)[0].tokens

    e = Engine(model, params, slots=2, prefill_len=16, cache_len=32)
    res = e.generate([pa, pb], max_ticks=60)
    assert res[0].tokens == alone(pa)
    assert res[1].tokens == alone(pb)


@pytest.mark.parametrize("arch", ["qwen3-32b", "gemma3-4b"])
def test_engine_donated_cache_matches_undonated(arch):
    """Ticks with the decode cache donated, requests admitted between
    them (``scatter_prefill`` onto the last tick's output while its
    donated input is gone): no deleted buffer is read, the tokens equal
    those of an engine whose step does not donate, and the compiled
    step aliases at least the whole cache (none without donation).
    gemma3-4b keeps per-layer windows in ring caches."""
    from repro.serving.engine import make_generate_step
    cfg = reduced_config(arch)
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(3))
    rng = np.random.default_rng(5)
    reqs = [(_prompt(rng, n, cfg.vocab_size), m)
            for n, m in ((5, 3), (14, 7), (9, 4), (3, 6), (11, 5))]

    def serve(donate):
        e = Engine(model, params, slots=2, prefill_len=16, cache_len=48)
        if not donate:
            e._generate = jax.jit(make_generate_step(model))
        rids = [e.submit(p, SamplingParams(max_new_tokens=m))
                for p, m in reqs]
        before = None
        while e.queue or e.pool.num_active:
            before = e.cache
            e.step()
        return e, [e.finished[r].tokens for r in rids], before

    e, toks, before = serve(donate=True)
    assert before["k" if "k" in before else "k_loc"].is_deleted()
    plain, want, _ = serve(donate=False)
    assert toks == want
    assert e.decode_cache_aliased_bytes >= e.decode_cache_bytes > 0
    assert plain.decode_cache_aliased_bytes == 0


def test_slot_reuse_recycled_slot_does_not_leak(gemma):
    """A short request finishing frees its slot; the next queued request
    joins it and must see NONE of the previous occupant's history."""
    cfg, model, params = gemma
    rng = np.random.default_rng(3)
    pa = _prompt(rng, 14)          # long occupant, finishes first
    pb = _prompt(rng, 6)           # joins the recycled slot

    e1 = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    golden = e1.generate([pb], max_ticks=60)[0]
    ref_pos = np.asarray(e1.cache["pos"])[:, 0]

    e = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    e.submit(pa, SamplingParams(max_new_tokens=4))
    e.submit(pb)
    done = e.run(max_ticks=120)
    assert done[1].tokens == golden.tokens
    # the recycled slot's cache row was fully overwritten at join: its
    # position row matches a fresh single-request run bit-for-bit (any
    # leak of A's history would leave extra valid (>=0) positions)
    np.testing.assert_array_equal(np.asarray(e.cache["pos"])[:, 0], ref_pos)
    assert e.pool.owner[0] is None and e.pool.lengths[0] == 0


# ---------------------------------------------------------------------------
# request lifecycle
def test_lifecycle_states_metrics_and_streaming(gemma):
    cfg, model, params = gemma
    rng = np.random.default_rng(5)
    streamed = []
    e = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    r0 = e.submit(_prompt(rng, 8), SamplingParams(max_new_tokens=3),
                  on_token=lambda rid, tok, last: streamed.append(
                      (rid, tok, last)))
    r1 = e.submit(_prompt(rng, 8), SamplingParams(max_new_tokens=2))
    assert e.requests[r0].state == RequestState.QUEUED
    assert e.requests[r1].state == RequestState.QUEUED

    e.step()    # r0 joins (prefill) and decodes once; r1 still queued
    assert e.requests[r0].state == RequestState.DECODE
    assert e.requests[r1].state == RequestState.QUEUED

    done = e.run(max_ticks=60)
    assert {r0, r1} == set(done)
    for res in done.values():
        assert res.state == RequestState.FINISHED
        assert res.done_reason in ("length", "eos")
        m = res.metrics
        assert m.queue_wait is not None and m.queue_wait >= 0
        assert m.ttft is not None and m.ttft >= 0
        assert m.tpot is not None and m.tpot >= 0
        assert m.output_tokens == len(res.tokens)
    assert done[r0].metrics.queue_wait <= done[r1].metrics.queue_wait
    # streaming callback saw every token of r0, in order, last flagged
    assert [t for _, t, _ in streamed] == done[r0].tokens
    assert [last for _, _, last in streamed] == [False, False, True]
    assert len(done[r0].tokens) == 3 and len(done[r1].tokens) == 2

    s = e.stats()
    assert s["finished"] == 2 and s["output_tokens"] == 5
    assert s["ttft_p50_ms"] >= 0 and s["tpot_p99_ms"] >= 0


def test_eos_and_cancel(gemma):
    cfg, model, params = gemma
    rng = np.random.default_rng(9)
    p = _prompt(rng, 8)
    e = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    probe = e.generate([p], SamplingParams(max_new_tokens=4))[0]
    eos = probe.tokens[1]          # a token this model will emit

    e2 = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    res = e2.generate([p], SamplingParams(max_new_tokens=10,
                                          eos_token=int(eos)))[0]
    assert res.done_reason == "eos"
    assert len(res.tokens) < 10 and res.tokens[-1] == eos

    # cancel: one queued, one active
    e3 = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    ra = e3.submit(p, SamplingParams(max_new_tokens=50))
    rb = e3.submit(_prompt(rng, 6), SamplingParams(max_new_tokens=2))
    e3.step()
    assert e3.cancel(rb)           # still queued
    assert e3.finished[rb].state == RequestState.CANCELLED
    assert e3.finished[rb].done_reason == "cancelled"
    assert e3.cancel(ra)           # mid-decode: frees the slot
    assert e3.pool.num_active == 0
    assert not e3.cancel(ra)       # idempotent on terminal state
    rc = e3.submit(_prompt(rng, 6), SamplingParams(max_new_tokens=2))
    done = e3.run(max_ticks=30)
    assert done[rc].state == RequestState.FINISHED


def test_step_contract_instant_finish_drains_queue(gemma):
    """Requests that finish on their very first token (max_new=1) free
    their slot inside the join; `while engine.step()` must still drain
    the whole queue rather than stranding it behind a False return."""
    cfg, model, params = gemma
    rng = np.random.default_rng(17)
    e = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    for _ in range(3):
        e.submit(_prompt(rng, 6), SamplingParams(max_new_tokens=1))
    while e.step():
        pass
    assert len(e.finished) == 3
    assert all(len(r.tokens) == 1 for r in e.finished.values())
    # single-token outputs have no inter-token interval: tpot is None,
    # so it must not drag the percentile aggregation toward zero
    assert all(r.metrics.tpot is None for r in e.finished.values())
    assert np.isnan(e.stats()["tpot_p50_ms"])


def test_generate_reports_tick_exhaustion(gemma):
    cfg, model, params = gemma
    rng = np.random.default_rng(19)
    e = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    with pytest.raises(RuntimeError, match="unfinished"):
        e.generate([_prompt(rng, 6), _prompt(rng, 6)],
                   SamplingParams(max_new_tokens=30), max_ticks=3)


def test_prefill_chunk_warns_when_unsupported():
    cfg = reduced_config("mamba2-1.3b")
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(0))
    with pytest.warns(UserWarning, match="prefill_chunk"):
        e = Engine(model, params, slots=1, prefill_len=16, cache_len=32,
                   prefill_chunk=8)
    assert e.prefill_chunk is None


def test_reentrant_cancel_from_stream_callback(gemma):
    """An on_token callback cancelling ANOTHER request mid-tick (client
    disconnect) must not corrupt slot bookkeeping, double-finalize, or
    advance the freed slot."""
    cfg, model, params = gemma
    rng = np.random.default_rng(23)
    e = Engine(model, params, slots=2, prefill_len=16, cache_len=48)
    victim = {}

    def cb(rid, tok, last):
        v = victim.get("rid")
        if v is not None and not e.requests[v].state.is_terminal:
            e.cancel(v)

    ra = e.submit(_prompt(rng, 6), SamplingParams(max_new_tokens=4),
                  on_token=cb)
    victim["rid"] = e.submit(_prompt(rng, 8), SamplingParams(max_new_tokens=4))
    done = e.run(max_ticks=30)
    assert done[ra].state == RequestState.FINISHED
    assert done[victim["rid"]].state == RequestState.CANCELLED
    assert e.stats()["requests"] == 2          # one telemetry record each
    assert e.pool.num_active == 0
    assert all(v == 0 for v in e.pool.lengths)

    # self-cancel on one's own token must not be overwritten by FINISHED
    e2 = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    rid = e2.submit(_prompt(rng, 6), SamplingParams(max_new_tokens=3),
                    on_token=lambda r, tok, last: e2.cancel(r))
    done2 = e2.run(max_ticks=20)
    assert done2[rid].state == RequestState.CANCELLED
    assert len(done2[rid].tokens) == 1
    assert e2.stats()["requests"] == 1


def test_prompt_truncation_warns_and_reap_drains(gemma):
    cfg, model, params = gemma
    rng = np.random.default_rng(31)
    e = Engine(model, params, slots=1, prefill_len=8, cache_len=32)
    with pytest.warns(UserWarning, match="exceeds prefill_len"):
        e.submit(_prompt(rng, 20), SamplingParams(max_new_tokens=2))
    e.run(max_ticks=20)
    reaped = e.reap()
    assert len(reaped) == 1 and e.finished == {} and e.requests == {}
    assert e.stats()["requests"] == 1      # telemetry records survive reap


def test_negative_seed_does_not_crash(gemma):
    cfg, model, params = gemma
    rng = np.random.default_rng(29)
    e = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    res = e.generate([_prompt(rng, 6)],
                     SamplingParams(temperature=0.8, seed=-1,
                                    max_new_tokens=3))[0]
    assert len(res.tokens) == 3


def test_seeded_sampling_reproducible_across_engines(gemma):
    cfg, model, params = gemma
    rng = np.random.default_rng(11)
    prompts = [_prompt(rng, 6), _prompt(rng, 10)]
    sp = SamplingParams(temperature=0.9, top_k=30, top_p=0.95, seed=123,
                        max_new_tokens=5)

    def roll():
        e = Engine(model, params, slots=2, prefill_len=16, cache_len=48)
        return [r.tokens for r in e.generate(prompts, sp)]

    assert roll() == roll()


def test_padded_prefill_bucket_matches_exact(gemma):
    """prefill_chunk right-pads prompts to bucket lengths; -1 pad
    positions are masked so the result is identical to exact-length."""
    cfg, model, params = gemma
    rng = np.random.default_rng(13)
    prompts = [_prompt(rng, 5), _prompt(rng, 9)]

    exact = Engine(model, params, slots=2, prefill_len=16, cache_len=48)
    bucketed = Engine(model, params, slots=2, prefill_len=16, cache_len=48,
                      prefill_chunk=8)
    assert bucketed._bucket_len(5) == 8 and bucketed._bucket_len(9) == 16
    a = [r.tokens for r in exact.generate(prompts)]
    b = [r.tokens for r in bucketed.generate(prompts)]
    assert a == b


# ---------------------------------------------------------------------------
# shims + prefill return contract
def test_batcher_shim_works_with_deprecation(gemma):
    cfg, model, params = gemma
    rng = np.random.default_rng(0)
    with pytest.warns(DeprecationWarning, match="ContinuousBatcher"):
        b = ContinuousBatcher(model, params, slots=2, prefill_len=16,
                              cache_len=64)
    reqs = [Request(rid=rid, prompt=_prompt(rng, 16), max_new=4)
            for rid in range(5)]
    for r in reqs:
        b.submit(r)
    done = b.run()
    assert sorted(done) == [0, 1, 2, 3, 4]
    assert all(0 < len(v) <= 4 for v in done.values())
    assert reqs[0].generated == done[0]      # legacy field still filled


def test_prefill_return_contract(gemma):
    """model.prefill returns (B, V) logits — never pre-argmaxed tokens —
    and the legacy make_prefill_step shim argmaxes exactly once.
    (Regression for the old _join ``tok.ndim > 1`` dance, which indexed
    into whatever came back and silently mishandled scalar returns.)"""
    from repro.serving import make_prefill_step
    cfg, model, params = gemma
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(_prompt(rng, 8))[None]}
    logits, cache = model.prefill(params, batch)
    assert logits.ndim == 2 and logits.shape == (1, cfg.padded_vocab)
    tok, _ = make_prefill_step(model)(params, batch)
    assert tok.shape == (1,) and tok.dtype == jnp.int32
    assert int(tok[0]) == int(jnp.argmax(logits, -1)[0])
    # greedy engine first token agrees with the raw-logits argmax
    e = Engine(model, params, slots=1, prefill_len=16, cache_len=48)
    res = e.generate([np.asarray(batch["tokens"][0])])[0]
    assert res.tokens[0] == int(jnp.argmax(logits, -1)[0])


def test_engine_rejects_encdec():
    cfg = reduced_config("seamless-m4t-medium")
    model = build_model(cfg, remat="none")
    with pytest.raises(NotImplementedError):
        Engine(model, params=None)


# ---------------------------------------------------------------------------
# slot pool unit behaviour
def test_slotpool_bookkeeping():
    pool = SlotPool(3)
    assert pool.free_slots() == [0, 1, 2] and pool.num_active == 0
    pool.acquire(1, rid=42, prompt_len=7)
    assert pool.free_slots() == [0, 2] and pool.num_active == 1
    assert pool.positions().tolist() == [0, 7, 0]
    pool.advance(1)
    assert pool.lengths[1] == 8
    with pytest.raises(AssertionError):
        pool.acquire(1, rid=43, prompt_len=3)
    pool.release(1)
    assert pool.free_slots() == [0, 1, 2]
    assert pool.positions().tolist() == [0, 0, 0]


def test_serving_telemetry_summary(tmp_path):
    from repro.core.telemetry import ServingTelemetry, percentile

    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert percentile([5.0], 99) == 5.0
    assert np.isnan(percentile([], 50))

    class _M:
        def as_dict(self):
            return {"prompt_tokens": 4, "output_tokens": 3,
                    "queue_wait_s": 0.01, "ttft_s": 0.05, "tpot_s": 0.002}

    class _R:
        def __init__(self, rid, state):
            self.rid, self.metrics = rid, _M()
            self.state = RequestState(state)
            self.done_reason = "length" if state == "finished" else "cancelled"

    path = tmp_path / "serving.jsonl"
    tel = ServingTelemetry(str(path))
    for i in range(3):
        tel.record_request(_R(i, "finished"))
    tel.record_request(_R(3, "cancelled"))
    s = tel.summary()
    assert s["requests"] == 4 and s["finished"] == 3 and s["cancelled"] == 1
    assert s["ttft_p50_ms"] == pytest.approx(50.0)
    assert s["tpot_p99_ms"] == pytest.approx(2.0)
    tel.close()
    assert len(path.read_text().strip().splitlines()) == 4


# ---------------------------------------------------------------------------
# CLI + load benchmark
def test_serve_cli_reduced_flag_both_paths():
    from repro.launch.serve import build_parser
    ap = build_parser()
    assert ap.parse_args([]).reduced is True
    assert ap.parse_args(["--reduced"]).reduced is True
    assert ap.parse_args(["--no-reduced"]).reduced is False


def test_serving_load_trace_and_smoke(gemma):
    from benchmarks.serving_load import make_trace, run_one
    trace = make_trace(20, rate=100.0, prefill_len=32, vocab=500,
                       max_new_cap=8, seed=0)
    arr = [t.arrival_s for t in trace]
    assert arr == sorted(arr) and arr[0] > 0
    assert all(4 <= len(t.prompt) <= 32 for t in trace)
    assert all(1 <= t.max_new <= 8 for t in trace)

    cfg, model, params = gemma
    s = run_one(model, params, trace[:5], slots=2, prefill_len=32,
                cache_len=64, prefill_chunk=16, seed=0)
    assert s["finished"] == 5
    assert s["output_tokens"] >= 5 and s["tok_per_s"] > 0
    assert s["ttft_p99_ms"] >= s["ttft_p50_ms"]
