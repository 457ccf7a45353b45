"""Core transformer layers: norms, rotary embeddings, attention, MLPs.

Everything is functional: ``*_defs(cfg)`` returns PDefs, ``fn(params, x, ...)``
applies.  Attention supports GQA/MQA, qk-norm, sliding windows, M-RoPE,
KV caches (full and ring-buffer) and a memory-efficient chunked
(flash-style, online-softmax) path used for long sequences — the pure-jnp
twin of ``repro.kernels.flash_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import Activation, ModelConfig
from repro.models.param import PDef
from repro.parallel.sharding import constrain

NEG_INF = -1e30  # large-negative that is safe in bf16 after exp()


# ---------------------------------------------------------------------------
# RMSNorm
def rmsnorm_defs(dim: int) -> Dict:
    return {"scale": PDef((dim,), ("norm",), "ones")}


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with f32 statistics but a bf16 data path.

    §Perf iteration A2: computing ``x32 * rsqrt * scale32`` makes every
    cotangent on the residual stream f32, and XLA then runs the TP
    boundary all-reduces in f32 (measured: 620 GB/device on qwen3-32b
    train_4k).  Keeping the *multiply* in the input dtype (stats still
    f32) halves collective and norm-region HBM bytes."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(dtype)
    return x * inv * scale.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (classic + M-RoPE)
def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    freqs = _rope_freqs(x.shape[-1], theta)                     # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, half)
    angles = angles[..., None, :]                               # (..., S, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_m_rope(x: jax.Array, positions_thw: jax.Array, theta: float,
                 sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions_thw: (3, ..., S) — temporal / height / width position streams.
    ``sections`` split the head_dim/2 frequency bands; each band takes its
    angle from the corresponding stream (text tokens carry t==h==w).
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = _rope_freqs(x.shape[-1], theta)                     # (half,)
    # (3, ..., S, half)
    angles = positions_thw[..., None].astype(jnp.float32) * freqs
    # band ownership: frequency index i belongs to stream sec_ids[i]
    sec_ids = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                         total_repeat_length=half)              # (half,)
    onehot = jax.nn.one_hot(sec_ids, 3, dtype=jnp.float32)      # (half, 3)
    angles = jnp.einsum("t...h,ht->...h", angles, onehot)       # (..., S, half)
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
class KVCache(NamedTuple):
    """Per-layer-stack KV cache.

    k, v:       (L, B, S_cache, K, hd)
    positions:  (L, B, S_cache) int32 — absolute position held in each slot
                (-1 = empty).  Supports both full and ring-buffer layouts.
    """
    k: jax.Array
    v: jax.Array
    positions: jax.Array


def attention_defs(cfg: ModelConfig, d_model: Optional[int] = None) -> Dict:
    D = d_model or cfg.d_model
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    defs = {
        "wq": PDef((D, H, hd), ("qkv_embed", "heads", "head_dim")),
        "wk": PDef((D, K, hd), ("qkv_embed", "kv_heads", "head_dim")),
        "wv": PDef((D, K, hd), ("qkv_embed", "kv_heads", "head_dim")),
        "wo": PDef((H, hd, D), ("heads", "head_dim", "qkv_embed")),
    }
    if cfg.qk_norm:
        defs["q_norm"] = PDef((hd,), ("norm",), "ones")
        defs["k_norm"] = PDef((hd,), ("norm",), "ones")
    return defs


def _mask(q_pos, k_pos, *, causal: bool, window: Optional[Any]):
    """q_pos: (..., S); k_pos: (..., T) -> bool (..., S, T).

    ``window`` may be None, an int, or a traced scalar (scanned per-layer
    window sizes for local:global patterns — global layers pass a huge
    window so the same scan body serves both)."""
    valid = (k_pos >= 0)[..., None, :]
    if causal:
        valid &= q_pos[..., :, None] >= k_pos[..., None, :]
    if window is not None:
        dist = q_pos[..., :, None] - k_pos[..., None, :]
        valid &= dist < window
    return valid


def _expand_kv(k: jax.Array, groups: int) -> jax.Array:
    """GQA -> per-shard MHA: repeat kv heads to the full head count.

    Multi-token train/prefill paths ONLY.  Head h reads kv head
    h // groups (matches q's k*G+g grouping).  With kv_heads replicated
    over `model` (rule fallback) and q heads sharded, the repeat is
    shard-local — zero resharding, unlike the 5-D (K, G) einsum which
    forced involuntary-remat copies (29 GB temps measured).  The cached
    decode path never calls this anymore: it reads K/V grouped through
    the split-KV flash-decode dispatch (groups× fewer HBM bytes)."""
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def _attend_dense(q, k, v, mask, softcap):
    """q: (B,S,H,hd) k,v: (B,T,H,hd) mask: (B,S,T) -> (B,S,H,hd)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bshd,bthd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", p, v)


def _attend_chunked(q, k, v, q_pos, k_pos, *, causal, window, softcap,
                    chunk: int):
    """Online-softmax attention, lax.scan over KV chunks.

    Never materializes the (S, T) score matrix — the pure-jnp twin of the
    Pallas flash kernel, used when T is large.
    q: (B,S,H,hd); k,v: (B,T,H,hd); q_pos: (B,S) or (S,); k_pos: (B,T)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    chunk = min(chunk, T)
    n = T // chunk
    assert T % chunk == 0, (T, chunk)
    scale = 1.0 / math.sqrt(hd)
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos, (B, T))
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos, (B, S))

    kc = jnp.moveaxis(k.reshape(B, n, chunk, H, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n, chunk, H, hd), 1, 0)
    pc = jnp.moveaxis(k_pos.reshape(B, n, chunk), 1, 0)

    def body(carry, xs):
        m, l, acc = carry
        kci, vci, pci = xs
        s = jnp.einsum("bshd,bthd->bhst", q, kci,
                       preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        msk = _mask(q_pos, pci, causal=causal, window=window)  # (B,S,t)
        s = jnp.where(msk[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p.astype(q.dtype), vci,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B,S,H,hd)


def attention(
    p: Dict,
    x: jax.Array,                       # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: jax.Array,               # (B, S) or (S,) [or (3,B,S) M-RoPE]
    causal: bool = True,
    window: Optional[Any] = None,       # None | int | traced scalar
    cache_kv: Optional[Tuple] = None,   # (k, v, k_positions) for decode/cross
    cache_layer: Optional[Any] = None,  # layer of a stacked contiguous cache
    kv_x: Optional[jax.Array] = None,   # cross-attention source
    use_rope: bool = True,              # False for cross-attention
    chunked_threshold: int = 2048,
    chunk: int = 1024,
) -> jax.Array:
    """General attention. Returns (B, S, D).

    Modes:
      * self-attention train/prefill: cache_kv=None, kv_x=None
      * cross-attention:              kv_x = encoder memory
      * decode:                       cache_kv = (k_cache, v_cache, k_pos)
                                      (projected new kv already merged by
                                      the caller's cache update)
      * paged decode/prefill:         cache_kv = (k_pool, v_pool, kp_pool,
                                      block_tables) — K/V gathered from the
                                      global block pool through per-row
                                      block tables (serving paged KV)
      * quantized decode:             cache_kv = (k, v, k_pos, k_scale,
                                      v_scale) contiguous or (k_pool,
                                      v_pool, kp_pool, block_tables,
                                      ks_pool, vs_pool) paged — K/V stored
                                      int8/fp8 with f32 per-(token, head)
                                      scales; S == 1 dequantizes inside
                                      the flash-decode kernels, S > 1
                                      dequantizes before attending
      * in-place decode:              a contiguous cache_kv (plain or
                                      quantized) stacked over layers,
                                      (L, B, T, ...), with cache_layer
                                      the layer to read — the decode
                                      kernel reads it without a copy
    """
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    B, S, _ = x.shape
    paged = cache_kv is not None and len(cache_kv) in (4, 6)
    quant = cache_kv is not None and len(cache_kv) in (5, 6)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q = constrain(q, "batch", None, "act_heads", None)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)

    if cache_kv is None:
        src = x if kv_x is None else kv_x
        k = jnp.einsum("btd,dhk->bthk", src, p["wk"].astype(x.dtype))
        v = jnp.einsum("btd,dhk->bthk", src, p["wv"].astype(x.dtype))
        if cfg.qk_norm:
            k = rms_norm(k, p["k_norm"], cfg.rms_eps)
        T = k.shape[1]
        if kv_x is None:
            k_pos = positions if positions.ndim <= 2 else positions[0]
        else:
            k_pos = jnp.arange(T)
        if cfg.m_rope_sections is not None and kv_x is None and use_rope:
            assert positions.ndim == 3, "M-RoPE needs (3, B, S) positions"
            q = apply_m_rope(q, positions, cfg.rope_theta, cfg.m_rope_sections)
            k = apply_m_rope(k, positions, cfg.rope_theta, cfg.m_rope_sections)
            q_pos = positions[0]
        elif kv_x is None and use_rope and cfg.rope_theta > 0:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, k_pos, cfg.rope_theta)
            q_pos = positions
        else:
            q_pos = positions if positions.ndim <= 2 else positions[0]
    else:
        k_scale = v_scale = None
        if paged:
            if quant:
                k_pool, v_pool, kp_pool, btab, k_scale, v_scale = cache_kv
            else:
                k_pool, v_pool, kp_pool, btab = cache_kv
            k = v = k_pos = None
            T = btab.shape[1] * k_pool.shape[1]
        else:
            if quant:
                k, v, k_pos, k_scale, v_scale = cache_kv
            else:
                k, v, k_pos = cache_kv
            T = k.shape[-3]
        if not use_rope:
            q_pos = positions if positions.ndim <= 2 else positions[0]
        elif cfg.m_rope_sections is not None:
            q = apply_m_rope(q, positions, cfg.rope_theta, cfg.m_rope_sections)
            q_pos = positions[0]
        elif cfg.rope_theta > 0:
            q = apply_rope(q, positions, cfg.rope_theta)
            q_pos = positions
        else:
            q_pos = positions

    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos, (B, S))
    if paged:
        k_pos_b = None
    elif k_pos.ndim == 1:
        k_pos_b = jnp.broadcast_to(k_pos, (B, T))
    else:
        k_pos_b = k_pos

    if paged:
        # K/V stay in the shared block pool; per-row block tables route
        # the gather.  S == 1 (serving decode) goes straight through the
        # paged split-KV kernel — the table lookup happens INSIDE the
        # Pallas grid via scalar prefetch, so no contiguous copy of the
        # cache is ever materialized.  Multi-token suffix prefill (cold
        # path, once per admitted request) gathers a contiguous view.
        from repro.kernels.ops import flash_attention, flash_decode_paged
        if S == 1:
            out = flash_decode_paged(q, k_pool, v_pool, q_pos, kp_pool,
                                     btab, causal=causal, window=window,
                                     softcap=cfg.logit_softcap,
                                     k_scale=k_scale, v_scale=v_scale)
        else:
            kg, vg, kpg = gather_paged_kv(k_pool, v_pool, kp_pool, btab)
            if quant:
                # suffix prefill (cold path): gather the scale pools along
                # the same tables and widen before the multi-token kernel
                from repro.kernels.quant import dequantize_kv
                safe = jnp.maximum(btab.astype(jnp.int32), 0)
                kg = dequantize_kv(kg, k_scale[safe].reshape(B, -1, K))
                vg = dequantize_kv(vg, v_scale[safe].reshape(B, -1, K))
                kg, vg = kg.astype(q.dtype), vg.astype(q.dtype)
            out = flash_attention(q, kg, vg, q_pos, kpg, causal=causal,
                                  window=window, softcap=cfg.logit_softcap,
                                  chunk=chunk)
    elif cache_kv is not None:
        # Decode/cross with a populated cache: K/V stay GROUPED at the
        # native kv-head count — no repeat materialization.  For S == 1
        # (the serving decode hot path) ops.flash_attention dispatches
        # to the grouped split-KV flash-decode kernel, which reads each
        # cache byte from HBM exactly once (groups× fewer bytes than
        # the retired repeat-then-attend path).
        from repro.kernels.ops import flash_attention
        stack = (None,) * (k.ndim - 4)
        k = constrain(k, *stack, "batch", None, "cache_kv", None)
        v = constrain(v, *stack, "batch", None, "cache_kv", None)
        if quant and S > 1:
            # multi-token path dequantizes up front (decode S == 1 keeps
            # the narrow bytes all the way into the kernel)
            from repro.kernels.quant import dequantize_kv
            k = dequantize_kv(k, k_scale).astype(q.dtype)
            v = dequantize_kv(v, v_scale).astype(q.dtype)
            k_scale = v_scale = None
        out = flash_attention(q, k, v, q_pos, k_pos_b, causal=causal,
                              window=window, softcap=cfg.logit_softcap,
                              chunk=chunk, k_scale=k_scale, v_scale=v_scale,
                              layer=cache_layer)
    else:
        # GQA -> per-shard MHA (see _expand_kv) keeps head sharding
        # aligned on the multi-token train/prefill paths.
        k = _expand_kv(k, G)
        v = _expand_kv(v, G)
        k = constrain(k, "batch", None, "act_heads", None)
        v = constrain(v, "batch", None, "act_heads", None)

        if T > chunked_threshold:
            # flash path: online-softmax fwd + score-recomputing
            # custom-VJP bwd (repro.kernels.ref / flash_attention on TPU)
            from repro.kernels.ops import flash_attention
            out = flash_attention(q, k, v, q_pos, k_pos_b, causal=causal,
                                  window=window, softcap=cfg.logit_softcap,
                                  chunk=chunk)
        else:
            mask = _mask(q_pos, k_pos_b, causal=causal, window=window)
            out = _attend_dense(q, k, v, mask, cfg.logit_softcap)

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return constrain(y, "batch", None, "act_embed")


def gather_paged_kv(k_pool, v_pool, kp_pool, block_tables):
    """Gather per-row contiguous K/V views from the global block pool.

    k_pool, v_pool: (num_blocks, block_size, K, hd); kp_pool:
    (num_blocks, block_size) int32; block_tables: (B, max_blocks) int32
    with -1 = unmapped.  Returns k, v of shape
    (B, max_blocks*block_size, K, hd) and positions (B, max_blocks*
    block_size) with unmapped entries masked to -1 — exactly the
    contiguous cache layout the non-paged decode path would have seen.
    """
    NB, BS, K, hd = k_pool.shape
    bt = block_tables.astype(jnp.int32)
    B = bt.shape[0]
    safe = jnp.maximum(bt, 0)
    k = k_pool[safe].reshape(B, -1, K, hd)
    v = v_pool[safe].reshape(B, -1, K, hd)
    kp = jnp.where(bt[..., None] >= 0, kp_pool[safe], -1).reshape(B, -1)
    return k, v, kp


def project_kv(p: Dict, x: jax.Array, cfg: ModelConfig,
               positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Project (and rope) new k, v for cache insertion during decode."""
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if cfg.m_rope_sections is not None:
        k = apply_m_rope(k, positions, cfg.rope_theta, cfg.m_rope_sections)
    elif cfg.rope_theta > 0:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


# ---------------------------------------------------------------------------
# MLP
def mlp_defs(cfg: ModelConfig, d_model: Optional[int] = None,
             d_ff: Optional[int] = None) -> Dict:
    D = d_model or cfg.d_model
    F = d_ff or cfg.d_ff
    gated = cfg.activation in (Activation.SWIGLU, Activation.GEGLU)
    defs = {
        "w1": PDef((D, F), ("embed", "mlp")),
        "w2": PDef((F, D), ("mlp", "embed")),
    }
    if gated:
        defs["w3"] = PDef((D, F), ("embed", "mlp"))
    return defs


def mlp(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = {Activation.SWIGLU: jax.nn.silu,
           Activation.GEGLU: functools.partial(jax.nn.gelu, approximate=True),
           Activation.GELU: functools.partial(jax.nn.gelu, approximate=True),
           }[cfg.activation]
    h = act(jnp.einsum("bsd,df->bsf", x, p["w1"].astype(x.dtype)))
    if "w3" in p:
        h = h * jnp.einsum("bsd,df->bsf", x, p["w3"].astype(x.dtype))
    h = constrain(h, "batch", None, "act_mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["w2"].astype(x.dtype))
    return constrain(y, "batch", None, "act_embed")


# ---------------------------------------------------------------------------
# Embedding / unembedding
def embed_defs(cfg: ModelConfig) -> Dict:
    V, D = cfg.padded_vocab, cfg.d_model
    defs = {"embedding": PDef((V, D), ("vocab", "embed"), "normal", 1.0)}
    if not cfg.tie_embeddings:
        defs["unembed"] = PDef((D, V), ("embed", "vocab"))
    return defs


def embed(p: Dict, tokens: jax.Array, cfg: ModelConfig,
          dtype=jnp.bfloat16) -> jax.Array:
    x = p["embedding"].astype(dtype)[tokens]
    # gemma-family scales embeddings by sqrt(d_model)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    return constrain(x, "batch", None, "act_embed")


def unembed(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        # tied unembedding: scale by 1/sqrt(D) (T5/MaxText convention) so the
        # N(0,1)-init table yields unit-variance logits.
        logits = jnp.einsum("bsd,vd->bsv", x * (cfg.d_model ** -0.5),
                            p["embedding"].astype(x.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"].astype(x.dtype))
    logits = constrain(logits, "batch", None, "act_heads")
    if cfg.logit_softcap is not None:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits
