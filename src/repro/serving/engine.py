"""Request-lifecycle inference engine (continuous batching, slot pool).

``Engine`` is the serving facade: submit ``InferenceRequest``s (QUEUED),
they join a fixed pool of cache slots via prefill (PREFILL), decode one
token per tick for every active slot (DECODE), and finish on EOS /
max_new_tokens (FINISHED) or ``cancel`` (CANCELLED).  Streaming token
callbacks fire as tokens are sampled; per-request queue-wait / TTFT /
TPOT land in a ``repro.core.telemetry.ServingTelemetry``.

Compilation discipline: the decode hot path is ONE jitted
``generate_step`` whose signature is all-array — tokens, per-slot
positions, and the packed per-slot sampling params (temperature / top-k
/ top-p / seed / step).  Changing a request's sampling config therefore
never retriggers compilation.  Inside that step the single-token
attention dispatches to the grouped split-KV flash-decode kernel
(``repro.kernels.flash_decode``; jnp twin on CPU): K/V stay at the
native kv-head count and every live cache byte is read once per tick —
the memory-bound optimum — with per-slot ring positions and -1 empty
slots masked in-kernel.  Prefill compiles once per prompt-length
bucket (``prefill_chunk`` rounds lengths up; pure-global-attention archs
only — ring buffers and SSM state cannot mask pad tokens).

The decode step takes the cache donated: the contiguous cache is
updated in place (``DecoderModel.decode_step``), and
``decode_cache_aliased_bytes`` against ``decode_cache_bytes`` shows
that the compiled step aliases it.  Every reader of ``self.cache``
between ticks (admission's ``scatter_prefill``, the ``len`` set before
a tick) reads the step's latest output, never a donated buffer.

Tracing: each tick's phases are host spans in the JAX profiler's trace
(``repro.core.telemetry.span``), on the thread that calls ``step``:
``engine.admit`` per admission (args ``rid``, ``tokens``) holding
``engine.prefill`` and ``engine.first_token_sync``, then
``engine.prepare``, ``engine.decode`` (the dispatch),
``engine.decode_sync`` (the wait for the tick's tokens) and
``engine.emit``.  They add no device work and no sync.

Legacy API: ``make_prefill_step`` / ``make_decode_step`` are the
original greedy step factories, kept as deprecated shims (the dry-run
cells still lower them); ``repro.serving.batcher.ContinuousBatcher``
wraps ``Engine`` behind the old driver interface.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import Family
from repro.core.telemetry import ServingTelemetry, span
from repro.models.lm import window_layout
from repro.serving.request import (GenerationResult, InferenceRequest,
                                   RequestState, TokenCallback)
from repro.serving.sampling import GREEDY, SamplingParams, sample_tokens
from repro.serving.paged import BlockPool
from repro.serving.slots import SlotPool


def make_generate_step(model):
    """One decode tick for every slot + per-slot sampling, in one jit.

    All per-slot state enters as arrays (B,):
      tokens      last sampled token per slot
      positions   true per-slot sequence length (the row's next write
                  position — fixes the pooled ``slot_len.max()`` bug)
      seeds/steps per-request PRNG stream (fold_in(PRNGKey(seed), step))
      temperature/top_k/top_p   sampling filters (0 temp = greedy)
    """
    cfg = model.cfg

    def generate_step(params, cache, tokens, positions, seeds, steps,
                      temperature, top_k, top_p, block_tables=None):
        B = tokens.shape[0]
        if cfg.m_rope_sections is not None:
            pos = jnp.broadcast_to(positions[None, :, None], (3, B, 1))
        else:
            pos = positions[:, None]
        batch = {"tokens": tokens[:, None],
                 "positions": pos.astype(jnp.int32),
                 "pos_row": positions.astype(jnp.int32)}
        if block_tables is not None:
            # paged serving: route cache reads/writes through the
            # per-slot block tables into the global block pool
            batch["block_tables"] = block_tables.astype(jnp.int32)
        logits, new_cache = model.decode_step(params, batch, cache)
        next_tok = sample_tokens(logits, seeds, steps, temperature,
                                 top_k, top_p)
        return next_tok, new_cache

    return generate_step


def make_prefill_step(model):
    """Deprecated: greedy prefill step (use ``Engine`` / ``model.prefill``).

    Kept for the dry-run cells and existing callers; returns
    (argmax token (B,), cache) — the logits-based API lives on
    ``model.prefill`` and ``Engine``."""
    def prefill_step(params, batch) -> Tuple[jax.Array, Dict]:
        logits, cache = model.prefill(params, batch)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache
    return prefill_step


def make_decode_step(model):
    """Deprecated: greedy decode step (use ``Engine`` / ``generate_step``)."""
    def decode_step(params, cache, batch) -> Tuple[jax.Array, Dict]:
        logits, new_cache = model.decode_step(params, batch, cache)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache
    return decode_step


class Engine:
    """Continuous-batching inference engine over a fixed slot pool."""

    def __init__(self, model, params, *, slots: int = 4,
                 prefill_len: int = 64, cache_len: int = 256,
                 prefill_chunk: Optional[int] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 kv_dtype: Optional[str] = None,
                 telemetry: Optional[ServingTelemetry] = None,
                 plan=None, clock=time.monotonic):
        cfg = model.cfg
        # quantized-KV opt-in: explicit kv_dtype overrides the model's
        # (set BEFORE jitting so every traced step sees the same cache
        # layout); None inherits whatever the model was built with
        if kv_dtype is not None:
            model.kv_dtype = kv_dtype
        self.kv_dtype = getattr(model, "kv_dtype", "bf16")
        if cfg.family in (Family.ENCDEC, Family.AUDIO):
            raise NotImplementedError(
                "Engine serves decoder-only families; encoder-decoder "
                "serving needs src_embeds plumbing (use launch.dryrun cells)")
        if prefill_len > cache_len:
            raise ValueError(f"prefill_len {prefill_len} exceeds "
                             f"cache_len {cache_len}")
        self.model = model
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.prefill_len = prefill_len
        self.cache_len = cache_len
        self.clock = clock
        self.telemetry = telemetry if telemetry is not None \
            else ServingTelemetry()
        # Bucketed (right-padded) prefill is only sound where cache
        # positions fully encode validity: pure-global attention.  Ring
        # buffers would retain pads over real keys; SSM state integrates
        # pad tokens into the recurrence.
        can_pad = (cfg.uses_attention
                   and cfg.family not in (Family.SSM, Family.HYBRID)
                   and window_layout(cfg, cache_len) is None)
        if prefill_chunk and not can_pad:
            warnings.warn(
                f"prefill_chunk={prefill_chunk} ignored for {cfg.name}: "
                "bucketed prefill needs pure-global attention (ring "
                "buffers / SSM state cannot mask pad tokens)",
                UserWarning, stacklevel=2)
        self.prefill_chunk = prefill_chunk if can_pad else None

        # Parallelism plan (repro.parallel.plan): shard the weights over the
        # plan's mesh and trace the jitted steps under its ambient
        # mesh+rules so with_sharding_constraint hints resolve.
        self._plan = plan if (plan is not None
                              and not plan.is_trivial) else None
        if self._plan is not None:
            self._mesh = self._plan.mesh()
            self.params = params = jax.device_put(
                params, self._plan.shardings(params, model.logical_axes(),
                                             mesh=self._mesh))

        self._prefill = jax.jit(model.prefill)
        # the cache is donated: the decode step updates it in place and
        # hands it back, so a tick holds one cache, not two
        self._generate = jax.jit(make_generate_step(model),
                                 donate_argnums=(1,))
        self._generate_compiles = 0
        self._sample1 = jax.jit(sample_tokens)

        # Paged KV: the cache becomes a GLOBAL pool of block_size-token
        # blocks addressed through per-slot block tables; admission
        # blocks on free blocks, not free slots, and shared prompt
        # prefixes map existing blocks instead of re-prefilling.
        self.paged = block_size is not None
        if self.paged:
            self.block_size = int(block_size)
            self.max_blocks = -(-cache_len // self.block_size)
            # default pool: HBM parity with the contiguous layout
            # (slots × cache_len tokens, rounded up to whole blocks)
            self.num_blocks = (int(num_blocks) if num_blocks is not None
                               else slots * self.max_blocks)
            # raises NotImplementedError for non-dense-global archs
            self.cache = model.init_cache(
                slots, cache_len, paged=(self.num_blocks, self.block_size))
            self.pool: SlotPool = BlockPool(
                slots, num_blocks=self.num_blocks,
                block_size=self.block_size,
                max_blocks_per_slot=self.max_blocks,
                prefix_cache=prefix_cache,
                kv_dtype=self.kv_dtype)
            self._prefix_prefill = jax.jit(model.prefix_prefill)
        else:
            self.block_size = self.num_blocks = None
            self.cache = model.init_cache(slots, cache_len)
            self.pool = SlotPool(slots)
        # bytes of the cache, and those the compiled decode step aliases
        # from its input to its output (None until it first compiles):
        # at least the cache's own when it is updated in place
        self.decode_cache_bytes = sum(a.nbytes for a in self.cache.values())
        self.decode_cache_aliased_bytes: Optional[int] = None
        self.queue: List[InferenceRequest] = []
        self.requests: Dict[int, InferenceRequest] = {}
        self.finished: Dict[int, GenerationResult] = {}
        self._slot_req: List[Optional[InferenceRequest]] = [None] * slots
        self.last_tok = np.zeros(slots, np.int32)
        self._temp = np.zeros(slots, np.float32)
        self._top_k = np.zeros(slots, np.int32)
        self._top_p = np.ones(slots, np.float32)
        self._seeds = np.zeros(slots, np.uint32)
        self._steps = np.zeros(slots, np.int32)
        self._next_rid = 0
        self.ticks = 0

    # -- request intake ----------------------------------------------------
    def submit(self, prompt: Union[np.ndarray, Sequence[int],
                                   InferenceRequest],
               sampling: Optional[SamplingParams] = None, *,
               rid: Optional[int] = None,
               on_token: Optional[TokenCallback] = None) -> int:
        """Enqueue a request (QUEUED). Returns its rid."""
        if isinstance(prompt, InferenceRequest):
            req = prompt
        else:
            arr = np.asarray(prompt, np.int32).reshape(-1)
            if arr.size == 0:
                raise ValueError("empty prompt")
            req = InferenceRequest(
                rid=self._next_rid if rid is None else rid,
                prompt=arr, sampling=sampling or GREEDY, on_token=on_token)
        if req.rid in self.requests:
            raise ValueError(f"duplicate rid {req.rid}")
        if len(req.prompt) > self.prefill_len:
            warnings.warn(
                f"rid {req.rid}: prompt ({len(req.prompt)} tokens) exceeds "
                f"prefill_len ({self.prefill_len}); only the first "
                f"{self.prefill_len} tokens will be prefilled",
                UserWarning, stacklevel=2)
        self._next_rid = max(self._next_rid, req.rid + 1)
        req.state = RequestState.QUEUED
        req.metrics.t_submit = self.clock()
        req.metrics.prompt_tokens = int(len(req.prompt))
        self.requests[req.rid] = req
        self.queue.append(req)
        return req.rid

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or running request. Returns True if it was live."""
        req = self.requests.get(rid)
        if req is None or req.state.is_terminal:
            return False
        if req.state == RequestState.QUEUED:
            self.queue.remove(req)
        else:
            for slot, r in enumerate(self._slot_req):
                if r is req:
                    self._account(slot, req)
                    self._release(slot)   # returns blocks / decrefs prefix
                    break
        self._finalize(req, RequestState.CANCELLED)
        return True

    # -- lifecycle internals ----------------------------------------------
    def _scope(self):
        """Ambient mesh+rules while tracing/running jitted steps."""
        if self._plan is not None:
            return self._plan.activate(self._mesh)
        return contextlib.nullcontext()

    def _bucket_len(self, S: int) -> int:
        if self.prefill_chunk:
            c = self.prefill_chunk
            return min(self.prefill_len, -(-S // c) * c)
        return S

    def _join(self, slot: int, req: InferenceRequest):
        """Admit one request: prefill it, sample its first token, arm
        its slot (the ``engine.admit`` span; ``t_admitted`` closes it)."""
        req.state = RequestState.PREFILL
        req.metrics.t_prefill_start = self.clock()
        S = int(min(len(req.prompt), self.prefill_len))
        with span("engine.admit", rid=req.rid, tokens=S):
            if self.paged:
                self._join_paged(slot, req, S)
            else:
                self._join_contiguous(slot, req, S)
        req.metrics.t_admitted = self.clock()

    def _join_contiguous(self, slot: int, req: InferenceRequest, S: int):
        """Prefill at batch=1, scatter into the slot's cache row."""
        with span("engine.prefill"):
            Sp = self._bucket_len(S)
            toks = np.zeros(Sp, np.int32)
            toks[:S] = req.prompt[:S]
            pos = np.arange(Sp, dtype=np.int32)
            pos[S:] = -1                  # pads: masked keys, no-op RoPE
            batch: Dict[str, Any] = {"tokens": jnp.asarray(toks)[None]}
            if self.cfg.m_rope_sections is not None:
                batch["positions"] = jnp.broadcast_to(
                    jnp.asarray(pos)[None, None], (3, 1, Sp))
            elif Sp != S:
                batch["positions"] = jnp.asarray(pos)[None]
            if Sp != S:
                batch["length"] = jnp.asarray([S], jnp.int32)
            with self._scope():
                logits, cache1 = self._prefill(self.params, batch)
            self.cache = self.pool.scatter_prefill(self.cache, cache1, slot)
        self.pool.acquire(slot, req.rid, S)
        req.metrics.prefilled_tokens = S
        self._finish_join(slot, req, logits)

    def _join_paged(self, slot: int, req: InferenceRequest, S: int):
        """Paged join: map blocks (prefix hits shared), prefill only the
        suffix THROUGH the pool, publish the new full blocks."""
        prompt = np.asarray(req.prompt[:S], np.int32)
        cached = self.pool.acquire_blocks(slot, req.rid, prompt,
                                          req.sampling.max_new_tokens)
        Ssuf = S - cached
        with span("engine.prefill"):
            Sp = self._bucket_len(Ssuf)
            toks = np.zeros(Sp, np.int32)
            toks[:Ssuf] = prompt[cached:]
            pos = np.arange(Sp, dtype=np.int32) + cached
            pos[Ssuf:] = -1               # pads: dropped writes, dead keys
            batch: Dict[str, Any] = {
                "tokens": jnp.asarray(toks)[None],
                "positions": jnp.asarray(pos)[None],
                "length": jnp.asarray([Ssuf], jnp.int32),
                "block_tables": jnp.asarray(
                    self.pool.block_tables[slot:slot + 1]),
            }
            with self._scope():
                logits, self.cache = self._prefix_prefill(
                    self.params, batch, self.cache)
        self.pool.register_prefix(slot, prompt)
        req.metrics.prefix_cached_tokens = cached
        req.metrics.prefilled_tokens = Ssuf
        self._finish_join(slot, req, logits)

    def _finish_join(self, slot: int, req: InferenceRequest, logits):
        """Shared join tail: sample token 0, arm the slot's decode state."""
        sp = req.sampling
        with span("engine.first_token_sync"):
            first = self._sample1(
                logits,
                jnp.asarray([sp.seed & 0xFFFFFFFF], jnp.uint32),
                jnp.asarray([0], jnp.int32),
                jnp.asarray([sp.temperature], jnp.float32),
                jnp.asarray([sp.top_k], jnp.int32),
                jnp.asarray([sp.top_p], jnp.float32))
            tok = int(first[0])
        self._slot_req[slot] = req
        self.last_tok[slot] = tok
        self._temp[slot] = sp.temperature
        self._top_k[slot] = sp.top_k
        self._top_p[slot] = sp.top_p
        self._seeds[slot] = np.uint32(sp.seed & 0xFFFFFFFF)
        self._steps[slot] = 1
        req.state = RequestState.DECODE
        req.metrics.t_first_token = self.clock()
        last = self._is_last(req, tok) or self._at_capacity(slot)
        req.emit(tok, last)
        # the callback may have cancelled this request (reentrant
        # cancel): only retire the slot if it still holds it
        if last and self._slot_req[slot] is req:
            self._retire(slot)

    def _is_last(self, req: InferenceRequest, tok: int) -> bool:
        sp = req.sampling
        n_after = len(req.generated) + 1
        return (sp.eos_token is not None and tok == sp.eos_token) \
            or n_after >= sp.max_new_tokens

    def _at_capacity(self, slot: int) -> bool:
        """Paged slots retire at cache_len (no ring wraparound: evicting
        a pool block could drop another request's shared history)."""
        return self.paged and self.pool.lengths[slot] >= self.cache_len

    @property
    def kv_bytes_per_token(self) -> int:
        """Dense K+V bytes one cached token costs (per layer pair;
        positions excluded; approximate for hybrid archs).  Byte-true
        for the engine's kv_dtype: quantized caches charge the narrow
        payload plus the 4-byte f32 scale per (token, head) vector, so
        admission and kv_utilization reflect the real HBM footprint."""
        cfg = self.cfg
        if not cfg.uses_attention:
            return 0
        if self.kv_dtype == "bf16":
            return cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2
        from repro.kernels.quant import kv_bytes_per_vector
        return (cfg.num_layers * 2 * cfg.num_kv_heads
                * kv_bytes_per_vector(cfg.head_dim, self.kv_dtype))

    def _account(self, slot: int, req: InferenceRequest):
        """Stamp allocated-vs-used KV bytes before the slot is released
        (the fragmentation signal the load benchmark reports)."""
        bpt = self.kv_bytes_per_token
        req.metrics.kv_used_bytes = int(
            min(int(self.pool.lengths[slot]), self.cache_len)) * bpt
        if self.paged:
            req.metrics.kv_allocated_bytes = (
                self.pool.allocated_blocks(slot) * self.block_size * bpt)
        else:
            req.metrics.kv_allocated_bytes = self.cache_len * bpt

    def _release(self, slot: int):
        self.pool.release(slot)
        self._slot_req[slot] = None
        self._temp[slot] = 0.0
        self._steps[slot] = 0

    def _retire(self, slot: int):
        req = self._slot_req[slot]
        self._account(slot, req)
        self._release(slot)
        self._finalize(req, RequestState.FINISHED)

    def _finalize(self, req: InferenceRequest,
                  state: RequestState) -> GenerationResult:
        req.state = state
        req.metrics.t_finish = self.clock()
        res = GenerationResult(rid=req.rid, tokens=list(req.generated),
                               state=state, done_reason=req.done_reason,
                               metrics=req.metrics)
        self.finished[req.rid] = res
        self.telemetry.record_request(res)
        return res

    # -- scheduling tick ---------------------------------------------------
    def step(self) -> bool:
        """One tick: admit queued requests into free slots, decode once.

        Returns False when there is nothing to do."""
        admitted = 0
        while self.queue:
            # re-list free slots each join: a request whose first token
            # already finishes it (eos / max_new=1) frees its slot inside
            # _join, and the next queued request must be able to take it
            free = self.pool.free_slots()
            if not free:
                break
            if self.paged:
                # admission blocks on free BLOCKS, not free slots: the
                # head request must fit its prompt + reserved growth in
                # the pool (FIFO — no head-of-line reordering)
                head = self.queue[0]
                S = int(min(len(head.prompt), self.prefill_len))
                if not self.pool.can_admit(
                        np.asarray(head.prompt[:S], np.int32),
                        head.sampling.max_new_tokens):
                    break
            self._join(free[0], self.queue.pop(0))
            admitted += 1
        if self.pool.num_active == 0:
            return admitted > 0
        with span("engine.prepare"):
            if self.paged:
                # map the block holding each active row's next write
                # position before the tick (draws on admission
                # reservations)
                for slot in range(self.slots):
                    if self._slot_req[slot] is not None:
                        self.pool.ensure_block(slot)
            self.cache["len"] = jnp.asarray(int(self.pool.lengths.max()),
                                            jnp.int32)
            extra = ({"block_tables": jnp.asarray(self.pool.block_tables)}
                     if self.paged else {})
            with self._scope():
                args = [jnp.asarray(a) for a in (
                    self.last_tok, self.pool.positions(), self._seeds,
                    self._steps, self._temp, self._top_k, self._top_p)]
        with span("engine.decode"), self._scope():
            donated = self.cache
            tok, self.cache = self._generate(self.params, donated, *args,
                                             **extra)
            if self._generate._cache_size() != self._generate_compiles:
                self._record_aliasing(donated, args, extra)
        with span("engine.decode_sync"):
            tok_host = np.asarray(jax.block_until_ready(tok))
        with span("engine.emit"):
            self.last_tok = tok_host.copy()
            self.ticks += 1
            for slot in range(self.slots):
                # read live, not a snapshot: an on_token callback earlier
                # in this loop may have cancel()ed a later slot's request
                req = self._slot_req[slot]
                if req is None or req.state.is_terminal:
                    continue
                t = int(tok_host[slot])
                self.pool.advance(slot)
                self._steps[slot] += 1
                last = self._is_last(req, t) or self._at_capacity(slot)
                req.emit(t, last)
                if last and self._slot_req[slot] is req:
                    self._retire(slot)
        return True

    def _record_aliasing(self, cache, args, extra):
        """Once per compile of the decode step: the bytes the compiled
        program aliases from input to output.  The lowering is the one
        the call just compiled (its donated, now deleted, cache gives
        only shapes), so nothing compiles again."""
        self._generate_compiles = self._generate._cache_size()
        mem = self._generate.lower(self.params, cache, *args,
                                   **extra).compile().memory_analysis()
        if mem is not None:
            self.decode_cache_aliased_bytes = int(mem.alias_size_in_bytes)

    def run(self, max_ticks: int = 1000) -> Dict[int, GenerationResult]:
        """Drive ticks until idle (or max_ticks). Returns finished results."""
        ticks = 0
        while (self.queue or self.pool.num_active) and ticks < max_ticks:
            self.step()
            ticks += 1
        return dict(self.finished)

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling: Optional[SamplingParams] = None,
                 max_ticks: int = 10_000) -> List[GenerationResult]:
        """Batch convenience: submit all, run to completion, return in order."""
        rids = [self.submit(np.asarray(p, np.int32), sampling)
                for p in prompts]
        self.run(max_ticks)
        missing = [r for r in rids if r not in self.finished]
        if missing:
            raise RuntimeError(
                f"generate: {len(missing)} request(s) unfinished after "
                f"{max_ticks} ticks (rids {missing[:5]}...); raise max_ticks")
        return [self.finished[r] for r in rids]

    def reap(self) -> Dict[int, GenerationResult]:
        """Drain terminal results and their request records.

        Long-lived engines call this periodically to bound memory:
        ``finished``/``requests`` entries are dropped (telemetry records
        stay — they back ``stats()`` and stream to JSONL when a path was
        given)."""
        out = dict(self.finished)
        self.finished.clear()
        for rid in out:
            self.requests.pop(rid, None)
        return out

    def stats(self) -> Dict:
        """Aggregate serving metrics (p50/p99 TTFT, TPOT, queue wait)."""
        out = self.telemetry.summary()
        out["kv_dtype"] = self.kv_dtype
        if self.paged:
            out["block_size"] = self.block_size
            out["num_blocks"] = self.num_blocks
            out["free_blocks"] = self.pool.free_blocks
            out["prefix"] = self.pool.prefix_stats()
        return out
