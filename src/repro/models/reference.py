"""Plain float32 reference forward for the dense decoder family.

The independent check of what the serving and training paths compute:
straightforward ``jax.numpy`` in float32 with every matmul at "highest"
precision (on a TPU a float32 matmul otherwise runs in bf16 passes), no
kernels, no cache, no padding or batching.  It follows the published
layer equations of a pre-norm GQA decoder (Qwen3 / Llama style):

    h = x + W_o · attn(RoPE(norm_q(W_q · rms(x))), RoPE(norm_k(W_k · rms(x))),
                       W_v · rms(x))
    y = h + W_2 · (silu(W_1 · rms(h)) ⊙ W_3 · rms(h))

with causal softmax attention, each kv head shared by ``H / K`` query
heads, and logits ``rms(y_L) · W_unembed``.  It reads the model's own
parameter tree (``repro.models.lm.decoder_param_defs``) and widens it to
float32 one piece at a time, so a full-width model fits beside its bf16
weights: one layer's weights at a time, query rows in blocks, and the
unembedding in vocabulary blocks.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import Activation, Family, ModelConfig

F32 = jnp.float32


def _check(cfg: ModelConfig):
    if (cfg.family != Family.DENSE or cfg.activation != Activation.SWIGLU
            or cfg.sliding_window is not None or cfg.local_global_pattern
            or cfg.logit_softcap is not None or cfg.m_rope_sections
            or cfg.name.startswith("gemma")):
        raise NotImplementedError(
            f"{cfg.name}: the reference covers dense global-attention "
            "SwiGLU decoders without softcap or embedding scaling")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, H, d); pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None, None] * freqs              # (S, 1, half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "q_block"))
def _layer(p, x, *, cfg: ModelConfig, q_block: int):
    """One decoder layer on x (S, D) float32."""
    p = jax.tree.map(lambda a: a.astype(F32), p)
    S = x.shape[0]
    eps = cfg.rms_eps
    pos = jnp.arange(S)
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = jnp.einsum("sd,dhk->shk", h, a["wq"])
    k = jnp.einsum("sd,dhk->shk", h, a["wk"])
    v = jnp.einsum("sd,dhk->shk", h, a["wv"])
    if cfg.qk_norm:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q = _rope(q, pos, cfg.rope_theta)
    k = _rope(k, pos, cfg.rope_theta)
    groups = cfg.num_heads // cfg.num_kv_heads
    k = jnp.repeat(k, groups, axis=1)                         # head h -> h // G
    v = jnp.repeat(v, groups, axis=1)
    outs = []
    for lo in range(0, S, q_block):
        qb = q[lo:lo + q_block]
        s = jnp.einsum("shk,thk->hst", qb, k) / math.sqrt(cfg.head_dim)
        causal = (lo + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hst,thk->shk", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, 0)
    x = x + jnp.einsum("shk,hkd->sd", o, a["wo"])
    h = _rms(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    g = jax.nn.silu(h @ m["w1"]) * (h @ m["w3"])
    return x + g @ m["w2"]


@functools.partial(jax.jit, static_argnames=("tied",))
def _unembed(y, table, *, tied: bool):
    table = table.astype(F32)
    if tied:
        return (y * y.shape[-1] ** -0.5) @ table.T
    return y @ table


def dense_logits_f32(params, cfg: ModelConfig, tokens: Sequence[int],
                     rows: Sequence[int], *, q_block: int = 512,
                     vocab_block: int = 32768) -> np.ndarray:
    """Float32 logits of one sequence at the given positions.

    params: the model's parameter tree (any float dtype); tokens: (S,)
    ids; rows: positions whose next-token logits to return.  Returns a
    host array (len(rows), padded_vocab).
    """
    _check(cfg)
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embedding"]
        x = emb[jnp.asarray(tokens, jnp.int32)].astype(F32)
        for i in range(cfg.num_layers):
            p_i = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer(p_i, x, cfg=cfg, q_block=q_block)
        y = _rms(x[jnp.asarray(rows, jnp.int32)],
                 params["final_norm"]["scale"].astype(F32), cfg.rms_eps)
        tied = cfg.tie_embeddings
        table = emb if tied else params["embed"]["unembed"]
        V = cfg.padded_vocab
        blocks = []
        for lo in range(0, V, vocab_block):
            part = table[lo:lo + vocab_block] if tied \
                else table[:, lo:lo + vocab_block]
            blocks.append(np.asarray(_unembed(y, part, tied=tied)))
    return np.concatenate(blocks, axis=-1)
