"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that every PR counts the same work.  Model
FLOPs count 2 per weight per token forward and 6 per weight per token
trained; the input embedding is a gather and counts 0; causal attention
counts the causal half of QK^T and PV; recomputation counts 0.
"""
from __future__ import annotations

from typing import Dict, Iterable


def layer_weights(spec: Dict) -> int:
    """Matmul weights of one dense GQA layer."""
    D, F = spec["hidden_size"], spec["intermediate_size"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def unembed_weights(spec: Dict) -> int:
    return spec["hidden_size"] * spec["vocab_size"]


def _keys_seen(q_len: int, ctx_before: int) -> int:
    """Keys summed over causal queries at positions ctx_before .. +q_len-1."""
    return q_len * ctx_before + q_len * (q_len + 1) // 2


def attention_flops(spec: Dict, q_len: int, ctx_before: int = 0) -> int:
    """Causal QK^T and PV of one layer (forward)."""
    return 4 * spec["num_attention_heads"] * spec["head_dim"] \
        * _keys_seen(q_len, ctx_before)


def prefill_flops(spec: Dict, prompt_len: int) -> int:
    """Forward over a prompt; logits of its last position only."""
    L = spec["num_hidden_layers"]
    return (2 * L * layer_weights(spec) * prompt_len
            + L * attention_flops(spec, prompt_len)
            + 2 * unembed_weights(spec))


def decode_flops(spec: Dict, position: int) -> int:
    """One new token at ``position`` (it attends to position + 1 keys)."""
    L = spec["num_hidden_layers"]
    return (2 * (L * layer_weights(spec) + unembed_weights(spec))
            + L * attention_flops(spec, 1, position))


def train_flops(spec: Dict, batch: int, seq: int) -> int:
    """Forward and backward of one step (backward = 2 x forward)."""
    L = spec["num_hidden_layers"]
    fwd = (2 * (L * layer_weights(spec) + unembed_weights(spec)) * seq
           + L * attention_flops(spec, seq))
    return 3 * fwd * batch


def flash_attention_call(spec: Dict, q_len: int, ctx_before: int = 0,
                         batch: int = 1, dtype_bytes: int = 2):
    """(flops, bytes) of one causal attention call of one layer: q and o
    at every query head, k and v at the native kv-head count."""
    H, K, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                spec["head_dim"])
    flops = attention_flops(spec, q_len, ctx_before) * batch
    nbytes = dtype_bytes * batch * hd * (2 * q_len * H
                                         + 2 * (ctx_before + q_len) * K)
    return flops, nbytes


def flash_decode_call(spec: Dict, lengths: Iterable[int],
                      dtype_bytes: int = 2):
    """(flops, bytes) of one decode attention call of one layer over the
    live rows: each row reads its own cached K/V once."""
    H, K, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                spec["head_dim"])
    lengths = list(lengths)
    keys = sum(lengths)
    flops = 4 * H * hd * keys
    nbytes = dtype_bytes * hd * (2 * K * keys + 2 * H * len(lengths))
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
