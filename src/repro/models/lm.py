"""Decoder-only LM covering the dense / MoE / SSM / hybrid / VLM families.

One scan-over-layers implementation serves every assigned architecture:
per-layer weights are stacked along a leading ``layers`` dimension and the
block body is ``lax.scan``-ed (keeping HLO size O(1) in depth — essential
for 96-layer GPT-3 compiles on this container).  Per-layer static structure
(gemma3's 5:1 local:global window pattern) rides along as scanned arrays.

Entry points (all functional):
  * ``loss(params, batch)``                    — training objective
  * ``prefill(params, batch)``                 — build a KV/SSM cache
  * ``decode_step(params, batch, cache)``      — one token w/ cache
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import Family, ModelConfig, ShapeConfig, StepKind
from repro.kernels import quant as Q
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.param import (PDef, abstract_tree, axes_tree, init_tree,
                                stack_defs)
from repro.parallel.sharding import constrain

BIG_WINDOW = 1 << 30  # "global" layers: window larger than any context


def _zero_aux() -> Dict[str, jnp.ndarray]:
    """Per-layer auxiliary metrics for non-MoE blocks (shape-stable with
    the MoE aux dict so the layer scan can stack them)."""
    return {"aux_loss": jnp.zeros((), jnp.float32),
            "dropped_frac": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
def _block_defs(cfg: ModelConfig) -> Dict:
    """One decoder block's parameter definitions (pre-stacking)."""
    if cfg.family == Family.SSM:
        return {"mixer": S.mamba2_defs(cfg), "ln": L.rmsnorm_defs(cfg.d_model)}
    if cfg.family == Family.HYBRID:
        return {"mixer": S.mamba2_defs(cfg), "ln": L.rmsnorm_defs(cfg.d_model)}
    d: Dict[str, Any] = {
        "ln1": L.rmsnorm_defs(cfg.d_model),
        "attn": L.attention_defs(cfg),
        "ln2": L.rmsnorm_defs(cfg.d_model),
    }
    if cfg.family == Family.MOE:
        d["moe"] = M.moe_defs(cfg)
    else:
        d["mlp"] = L.mlp_defs(cfg)
    return d


def _shared_block_defs(cfg: ModelConfig) -> Dict:
    """Zamba2's weight-tied attention+MLP block."""
    return {
        "ln1": L.rmsnorm_defs(cfg.d_model),
        "attn": L.attention_defs(cfg),
        "ln2": L.rmsnorm_defs(cfg.d_model),
        "mlp": L.mlp_defs(cfg),
    }


def decoder_param_defs(cfg: ModelConfig) -> Dict:
    defs: Dict[str, Any] = {
        "embed": L.embed_defs(cfg),
        "layers": stack_defs(_block_defs(cfg), cfg.num_layers),
        "final_norm": L.rmsnorm_defs(cfg.d_model),
    }
    if cfg.family == Family.HYBRID:
        defs["shared"] = _shared_block_defs(cfg)
    if cfg.family == Family.VLM and cfg.frontend_dim:
        defs["patch_proj"] = {
            "w": PDef((cfg.frontend_dim, cfg.d_model), ("frontend", "embed"))}
    return defs


def window_layout(cfg: ModelConfig, cache_len: int):
    """Cache layout for windowed-attention archs (§Perf iteration C1).

    Returns None for pure-global archs, else a dict:
      local_idx / global_idx — per-layer partition (local:global patterns)
      local_cap              — ring-buffer slots for local layers
                               (min(window, cache_len) instead of cache_len:
                               at 524k context this is the difference
                               between a 73 GB and a 13 GB cache for
                               gemma3-4b — measured 186 s vs 30 s memory
                               terms)."""
    if not cfg.uses_attention or (cfg.sliding_window is None):
        return None
    p = cfg.local_global_pattern
    if p > 0:
        local_idx = [i for i in range(cfg.num_layers) if i % (p + 1) != p]
        global_idx = [i for i in range(cfg.num_layers) if i % (p + 1) == p]
    else:
        local_idx = list(range(cfg.num_layers))
        global_idx = []
    return {
        "local_idx": tuple(local_idx),
        "global_idx": tuple(global_idx),
        "local_cap": min(cfg.sliding_window, cache_len),
        "period": (p + 1) if p > 0 else 0,
    }


def layer_windows(cfg: ModelConfig) -> Optional[jnp.ndarray]:
    """Per-layer attention windows as a scanned array (None = all global)."""
    if not cfg.uses_attention:
        return None
    if cfg.local_global_pattern > 0:
        pat = cfg.local_global_pattern
        w = [cfg.sliding_window if (i % (pat + 1)) != pat else BIG_WINDOW
             for i in range(cfg.num_layers)]
        return jnp.asarray(w, jnp.int32)
    if cfg.sliding_window is not None:
        return jnp.full((cfg.num_layers,), cfg.sliding_window, jnp.int32)
    return None


# ---------------------------------------------------------------------------
# Block applications (shared between train / prefill / decode)
def _attn_mlp_block(p, x, cfg, *, positions, window, cache_kv=None,
                    cache_layer=None, new_kv=None,
                    moe_impl="sorted_capacity"):
    """Returns (x, aux dict, (k, v)) — k,v only when projecting fresh kv;
    aux carries {"aux_loss", "dropped_frac"} (zeros for non-MoE blocks).
    ``cache_layer`` marks ``cache_kv`` as a stacked contiguous cache and
    names the layer attention reads.

    Sequence parallelism (§Perf iteration A1): the residual stream and the
    norm regions live seq-sharded over the `model` axis; GSPMD then lowers
    the TP boundary collectives as reduce-scatter + all-gather instead of
    full all-reduces (half the bytes) and the norms compute on 1/TP of the
    tokens.  Falls back to replication automatically when seq doesn't
    divide (decode S=1) via the logical-rule divisibility check."""
    x = constrain(x, "batch", "act_seq", None)
    h = L.rms_norm(x, p["ln1"]["scale"], cfg.rms_eps)
    if cache_kv is not None:
        a = L.attention(p["attn"], h, cfg, positions=positions,
                        cache_kv=cache_kv, cache_layer=cache_layer,
                        window=window)
        kv = None
    else:
        a = L.attention(p["attn"], h, cfg, positions=positions, window=window)
        kv = L.project_kv(p["attn"], h, cfg,
                          positions if positions.ndim <= 2 else positions
                          ) if new_kv else None
    x = x + constrain(a, "batch", "act_seq", None)
    h = L.rms_norm(x, p["ln2"]["scale"], cfg.rms_eps)
    aux = _zero_aux()
    if "moe" in p:
        m, aux = M.moe(p["moe"], h, cfg, impl=moe_impl)
    else:
        m = L.mlp(p["mlp"], h, cfg)
    return x + constrain(m, "batch", "act_seq", None), aux, kv


def _ssm_block(p, x, cfg, cache=None, return_cache=False):
    h = L.rms_norm(x, p["ln"]["scale"], cfg.rms_eps)
    h = constrain(h, "batch", "act_seq", None)
    y, new_cache = S.mamba2_block(p["mixer"], h, cfg, cache=cache,
                                  return_cache=return_cache)
    return x + y, new_cache


def _cache_write(kc, vc, pc, k_new, v_new, pos, layer=None):
    """Write the new KV at each row's (ring) position.

    kc, vc: (B, T, K, hd); pc: (B, T); k_new, v_new: (B, 1, K, hd);
    pos: scalar () for uniform batches — all rows share one column, and
    the contiguous dynamic_update_slice lowers much leaner than a
    scatter (the decode dry-run cells are memory-dominant) — or (B,)
    per-row positions for slot-pool serving, where rows at different
    sequence lengths land in different cache columns.  The pooled cache
    stays shape-static either way.

    With ``layer`` (a traced scalar) kc, vc, pc are the stacked
    (L, B, T, ...) caches of every layer and only that layer's B new
    entries are written: the update is B x K x hd elements, in place,
    whatever the stack's size."""
    return _write_rows((kc, vc, pc), (k_new, v_new), pos, layer)


def _write_rows(caches, news, pos, layer):
    """Shared body of the contiguous cache writes: each of ``news`` (B,
    1, ...) goes to its cache at (layer, row, slot), then the positions
    (the last of ``caches``) get ``pos``.  Returns the caches."""
    *data, pc = caches
    B, T = pc.shape[-2:]
    slot = jnp.mod(pos.astype(jnp.int32), T)
    lead = () if layer is None else (layer,)
    if pos.ndim == 0:
        col = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)

        def put(c, new):
            new = new.reshape((1,) * len(lead) + new.shape)
            start = lead + (0, slot) + (0,) * (new.ndim - len(lead) - 2)
            return jax.lax.dynamic_update_slice(c, new, start)
        return tuple(put(c, n) for c, n in zip(data, news)) + (put(pc, col),)
    at = lead + (jnp.arange(B), slot)
    return tuple(c.at[at].set(n[:, 0]) for c, n in zip(data, news)) + (
        pc.at[at].set(pos.astype(jnp.int32)),)


def _cache_write_quant(kc, vc, pc, ksc, vsc, k_new, v_new, pos, kv_dtype,
                       layer=None):
    """Quantize-at-scatter twin of ``_cache_write``.

    kc, vc: (B, T, K, hd) int8/fp8; ksc, vsc: (B, T, K) f32 scales —
    one per (token, head) vector, so an appended row quantizes
    independently and no existing cache line is ever requantized.
    ``layer`` as in ``_cache_write``."""
    kq, ks = Q.quantize_kv(k_new, kv_dtype)        # (B, 1, K, hd), (B, 1, K)
    vq, vs = Q.quantize_kv(v_new, kv_dtype)
    kc, vc, ksc, vsc, pc = _write_rows(
        (kc, vc, ksc, vsc, pc),
        (kq.astype(kc.dtype), vq.astype(vc.dtype), ks, vs), pos, layer)
    return kc, vc, pc, ksc, vsc


def _paged_cache_write(kc, vc, pc, k_new, v_new, pos, bt):
    """Scatter new KV into the global block pool through block tables.

    kc, vc: (num_blocks, block_size, K, hd); pc: (num_blocks,
    block_size); k_new, v_new: (B, S, K, hd); pos: (B,) or (B, S)
    absolute token positions with -1 marking pads; bt: (B, max_blocks)
    block tables (-1 = unmapped).

    Each token's target is (bt[row, pos // block_size], pos % block_size).
    Invalid targets — pad positions, positions past the table, unmapped
    table entries — are routed to block index ``num_blocks`` and dropped
    by the scatter (NEVER clamped: JAX wraps negative indices, so a raw
    -1 would silently corrupt the last pool block, which may hold
    another request's KV).
    """
    NB, BS = pc.shape
    MAXB = bt.shape[1]
    p = pos.astype(jnp.int32)
    if p.ndim == 1:
        p = p[:, None]                                     # (B, 1)
    bidx = jnp.clip(p // BS, 0, MAXB - 1)
    blk = jnp.take_along_axis(bt, bidx, axis=1)            # (B, S)
    ok = (p >= 0) & (p // BS < MAXB) & (blk >= 0)
    blk = jnp.where(ok, blk, NB)
    off = jnp.where(ok, jnp.mod(p, BS), 0)
    kc = kc.at[blk, off].set(k_new, mode="drop")
    vc = vc.at[blk, off].set(v_new, mode="drop")
    pc = pc.at[blk, off].set(p, mode="drop")
    return kc, vc, pc


def _paged_cache_write_quant(kc, vc, pc, ksc, vsc, k_new, v_new, pos, bt,
                             kv_dtype):
    """Quantize-at-scatter twin of ``_paged_cache_write``.

    The scale pools (num_blocks, block_size, K) take the SAME invalid →
    ``num_blocks`` drop-mode routing as the data pools: a scale for a
    dropped token must never land on block -1's wraparound either."""
    NB, BS = pc.shape
    MAXB = bt.shape[1]
    kq, ks = Q.quantize_kv(k_new, kv_dtype)        # (B, S, K, hd), (B, S, K)
    vq, vs = Q.quantize_kv(v_new, kv_dtype)
    kq, vq = kq.astype(kc.dtype), vq.astype(vc.dtype)
    p = pos.astype(jnp.int32)
    if p.ndim == 1:
        p = p[:, None]                                     # (B, 1)
    bidx = jnp.clip(p // BS, 0, MAXB - 1)
    blk = jnp.take_along_axis(bt, bidx, axis=1)            # (B, S)
    ok = (p >= 0) & (p // BS < MAXB) & (blk >= 0)
    blk = jnp.where(ok, blk, NB)
    off = jnp.where(ok, jnp.mod(p, BS), 0)
    kc = kc.at[blk, off].set(kq, mode="drop")
    vc = vc.at[blk, off].set(vq, mode="drop")
    ksc = ksc.at[blk, off].set(ks, mode="drop")
    vsc = vsc.at[blk, off].set(vs, mode="drop")
    pc = pc.at[blk, off].set(p, mode="drop")
    return kc, vc, pc, ksc, vsc


# ---------------------------------------------------------------------------
class DecoderModel:
    """Functional wrapper: config + param defs + step functions.

    ``kv_dtype`` is the per-model serving opt-in for the quantized KV
    cache: "bf16" (default, unquantized), "int8", or "fp8" (e4m3, where
    the jax build ships the dtype).  Quantized caches are supported for
    dense global-attention models, contiguous and paged; training and
    prefill compute are untouched — only the cache storage narrows.
    """

    def __init__(self, cfg: ModelConfig, *, remat: str = "full",
                 moe_impl: str = "sorted_capacity",
                 logits_chunk: int = 512, kv_dtype: str = "bf16"):
        if kv_dtype not in Q.KV_DTYPES:
            raise ValueError(f"kv_dtype {kv_dtype!r} not in {Q.KV_DTYPES}")
        self.cfg = cfg
        self.remat = remat
        self.moe_impl = moe_impl
        self.logits_chunk = logits_chunk
        self.kv_dtype = kv_dtype

    # -- params ------------------------------------------------------------
    def param_defs(self) -> Dict:
        return decoder_param_defs(self.cfg)

    def init(self, key, dtype=jnp.float32) -> Dict:
        return init_tree(key, self.param_defs(), dtype)

    def abstract_params(self, dtype=jnp.float32) -> Dict:
        return abstract_tree(self.param_defs(), dtype)

    def logical_axes(self) -> Dict:
        return axes_tree(self.param_defs())

    # -- forward core --------------------------------------------------------
    def _maybe_remat(self, fn):
        if self.remat == "none":
            return fn
        if self.remat == "selective":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            return jax.checkpoint(fn, policy=policy)
        return jax.checkpoint(fn)  # full recompute

    def _embed_inputs(self, params, batch, dtype=jnp.bfloat16):
        cfg = self.cfg
        x = L.embed(params["embed"], batch["tokens"], cfg, dtype)
        if cfg.family == Family.VLM and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(dtype)
            pe = jnp.einsum("bsd,de->bse", pe,
                            params["patch_proj"]["w"].astype(dtype))
            x = jnp.concatenate([pe, x], axis=1)  # patches prefix, then text
        return constrain(x, "batch", None, "act_embed")

    def _positions(self, batch, seq_len: int):
        cfg = self.cfg
        if cfg.m_rope_sections is not None:
            return batch["positions"]                      # (3, B, S)
        if "positions" in batch:
            return batch["positions"]
        return jnp.arange(seq_len, dtype=jnp.int32)

    def _backbone(self, params, x, positions):
        """Training/prefill-style full-sequence pass. Returns (y, aux)."""
        cfg = self.cfg
        windows = layer_windows(cfg)

        if cfg.family in (Family.SSM,):
            def body(h, p_l):
                h, _ = _ssm_block(p_l, h, cfg)
                return h, _zero_aux()
            body = self._maybe_remat(body)
            x, _ = jax.lax.scan(body, x, params["layers"])
            aux = _zero_aux()

        elif cfg.family == Family.HYBRID:
            x, aux = self._hybrid_backbone(params, x, positions)

        else:
            def body(h, xs):
                p_l, win = xs
                h, aux, _ = _attn_mlp_block(
                    p_l, h, cfg, positions=positions, window=win,
                    moe_impl=self.moe_impl)
                return h, aux
            body = self._maybe_remat(body)
            win_arr = (windows if windows is not None
                       else jnp.full((cfg.num_layers,), BIG_WINDOW, jnp.int32))
            x, auxs = jax.lax.scan(body, x, (params["layers"], win_arr))
            # scan stacks the per-layer aux dicts: mean each leaf over layers
            aux = jax.tree.map(lambda a: a.mean(), auxs)

        x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        return x, aux

    def _hybrid_backbone(self, params, x, positions):
        """Zamba2: shared attention block every ``attn_every`` SSM layers."""
        cfg = self.cfg
        ae = cfg.attn_every
        ngroups, tail = divmod(cfg.num_layers, ae)
        shared = params["shared"]

        def ssm_body(h, p_l):
            h, _ = _ssm_block(p_l, h, cfg)
            return h, None
        ssm_body = self._maybe_remat(ssm_body)

        def shared_apply(h):
            h, _, _ = _attn_mlp_block(shared, h, cfg, positions=positions,
                                      window=None)
            return h
        shared_apply = self._maybe_remat(shared_apply)

        grouped = jax.tree.map(
            lambda a: a[:ngroups * ae].reshape((ngroups, ae) + a.shape[1:]),
            params["layers"])
        tail_p = jax.tree.map(lambda a: a[ngroups * ae:], params["layers"])

        def group_body(h, p_g):
            h = shared_apply(h)
            h, _ = jax.lax.scan(ssm_body, h, p_g)
            return h, None

        x, _ = jax.lax.scan(group_body, x, grouped)
        if tail:
            x = shared_apply(x)
            x, _ = jax.lax.scan(ssm_body, x, tail_p)
        return x, _zero_aux()

    # -- losses ----------------------------------------------------------
    def loss(self, params, batch) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed_inputs(params, batch)
        Sfull = x.shape[1]
        positions = self._positions(batch, Sfull)
        y, aux = self._backbone(params, x, positions)
        labels = batch["labels"]
        if cfg.family == Family.VLM and y.shape[1] != labels.shape[1]:
            y = y[:, y.shape[1] - labels.shape[1]:]        # text positions only
        loss, z_loss = chunked_softmax_xent(
            y, params["embed"], cfg, labels, chunk=self.logits_chunk)
        total = loss + 0.01 * aux["aux_loss"] + 1e-4 * z_loss
        # dropped_frac is a pure metric (stop_gradient-free but constant
        # wrt params): the MoE capacity truncation's token-drop rate
        return total, {"xent": loss, "aux_loss": aux["aux_loss"],
                       "dropped_frac": aux["dropped_frac"], "z_loss": z_loss}

    # -- serving -----------------------------------------------------------
    def cache_spec(self, batch_size: int, cache_len: int, *,
                   paged: Optional[Tuple[int, int]] = None,
                   kv_dtype: Optional[str] = None) -> Dict:
        """Abstract cache structure (ShapeDtypeStructs) for serve shapes.

        ``paged=(num_blocks, block_size)`` swaps the per-row contiguous
        K/V for a GLOBAL block pool shared by every request: k/v become
        (layers, num_blocks, block_size, K, hd) and pos
        (layers, num_blocks, block_size) — no batch axis; requests
        address the pool through block tables carried in the decode
        batch.  Paged mode supports dense global-attention caches only
        (no SSM/hybrid state, no windowed ring layouts, no M-RoPE).

        ``kv_dtype`` (defaults to the model's) narrows the k/v storage
        to int8/fp8 and adds f32 ``k_scale``/``v_scale`` leaves — one
        scale per (token, head) vector, same leading axes as k/v with
        head_dim dropped.  Quantized caches are dense-global only (the
        windowed ring layouts and SSM state keep bf16)."""
        cfg = self.cfg
        kv_dtype = self.kv_dtype if kv_dtype is None else kv_dtype
        quant = kv_dtype in Q.QUANTIZED_KV_DTYPES
        kv_store = Q.kv_cache_dtype(kv_dtype)
        if quant and (not cfg.uses_attention
                      or cfg.family in (Family.SSM, Family.HYBRID)
                      or window_layout(cfg, cache_len) is not None
                      or cfg.m_rope_sections is not None):
            raise NotImplementedError(
                "quantized KV cache supports dense global-attention "
                f"models only (family={cfg.family})")
        if paged is not None:
            if (not cfg.uses_attention
                    or cfg.family in (Family.SSM, Family.HYBRID)
                    or window_layout(cfg, cache_len) is not None
                    or cfg.m_rope_sections is not None):
                raise NotImplementedError(
                    "paged KV cache supports dense global-attention "
                    f"models only (family={cfg.family})")
            nb, bs = paged
            Lr = cfg.num_layers
            c = {
                "len": jax.ShapeDtypeStruct((), jnp.int32),
                "k": jax.ShapeDtypeStruct(
                    (Lr, nb, bs, cfg.num_kv_heads, cfg.head_dim),
                    kv_store),
                "v": jax.ShapeDtypeStruct(
                    (Lr, nb, bs, cfg.num_kv_heads, cfg.head_dim),
                    kv_store),
                "pos": jax.ShapeDtypeStruct((Lr, nb, bs), jnp.int32),
            }
            if quant:
                c["k_scale"] = jax.ShapeDtypeStruct(
                    (Lr, nb, bs, cfg.num_kv_heads), jnp.float32)
                c["v_scale"] = jax.ShapeDtypeStruct(
                    (Lr, nb, bs, cfg.num_kv_heads), jnp.float32)
            return c
        c: Dict[str, Any] = {"len": jax.ShapeDtypeStruct((), jnp.int32)}
        Lr = cfg.num_layers
        if cfg.family in (Family.SSM, Family.HYBRID):
            ch = cfg.d_inner + 2 * cfg.ssm_state
            c["ssm_conv"] = jax.ShapeDtypeStruct(
                (Lr, batch_size, cfg.ssm_conv_width - 1, ch), jnp.bfloat16)
            c["ssm_state"] = jax.ShapeDtypeStruct(
                (Lr, batch_size, cfg.ssm_heads, cfg.ssm_head_dim,
                 cfg.ssm_state), jnp.float32)
        if cfg.family == Family.HYBRID:
            napp = -(-cfg.num_layers // cfg.attn_every)
            c["shared_k"] = jax.ShapeDtypeStruct(
                (napp, batch_size, cache_len, cfg.num_kv_heads, cfg.head_dim),
                jnp.bfloat16)
            c["shared_v"] = jax.ShapeDtypeStruct(
                (napp, batch_size, cache_len, cfg.num_kv_heads, cfg.head_dim),
                jnp.bfloat16)
            c["shared_pos"] = jax.ShapeDtypeStruct(
                (napp, batch_size, cache_len), jnp.int32)
        elif cfg.uses_attention:
            wl = window_layout(cfg, cache_len)
            if wl is not None:
                kv = lambda n, s: jax.ShapeDtypeStruct(
                    (n, batch_size, s, cfg.num_kv_heads, cfg.head_dim),
                    jnp.bfloat16)
                pos = lambda n, s: jax.ShapeDtypeStruct(
                    (n, batch_size, s), jnp.int32)
                nloc, cap = len(wl["local_idx"]), wl["local_cap"]
                c["k_loc"], c["v_loc"] = kv(nloc, cap), kv(nloc, cap)
                c["pos_loc"] = pos(nloc, cap)
                if wl["global_idx"]:
                    ng = len(wl["global_idx"])
                    c["k_glob"] = kv(ng, cache_len)
                    c["v_glob"] = kv(ng, cache_len)
                    c["pos_glob"] = pos(ng, cache_len)
            else:
                c["k"] = jax.ShapeDtypeStruct(
                    (Lr, batch_size, cache_len, cfg.num_kv_heads,
                     cfg.head_dim), kv_store)
                c["v"] = jax.ShapeDtypeStruct(
                    (Lr, batch_size, cache_len, cfg.num_kv_heads,
                     cfg.head_dim), kv_store)
                c["pos"] = jax.ShapeDtypeStruct(
                    (Lr, batch_size, cache_len), jnp.int32)
                if quant:
                    c["k_scale"] = jax.ShapeDtypeStruct(
                        (Lr, batch_size, cache_len, cfg.num_kv_heads),
                        jnp.float32)
                    c["v_scale"] = jax.ShapeDtypeStruct(
                        (Lr, batch_size, cache_len, cfg.num_kv_heads),
                        jnp.float32)
        return c

    def cache_logical_axes(self, spec: Dict) -> Dict:
        kvax = ("layers", "cache_batch", "cache_seq", "cache_kv",
                "cache_kv_dim")
        names = {
            "len": (),
            "ssm_conv": ("layers", "cache_batch", None, "act_mlp"),
            # state dims: (L, B, H, head_dim P, state N); "ssm_state" maps
            # to no mesh axis (replicated) but names the dim for the rule
            # table — RL010 keys liveness on annotations, not intentions
            "ssm_state": ("layers", "cache_batch", "ssm_heads", None,
                          "ssm_state"),
            "k": kvax, "v": kvax,
            "pos": ("layers", "cache_batch", "cache_seq"),
            "k_scale": ("layers", "cache_batch", "cache_seq", "cache_kv"),
            "v_scale": ("layers", "cache_batch", "cache_seq", "cache_kv"),
            "k_loc": kvax, "v_loc": kvax,
            "pos_loc": ("layers", "cache_batch", "cache_seq"),
            "k_glob": kvax, "v_glob": kvax,
            "pos_glob": ("layers", "cache_batch", "cache_seq"),
            "shared_k": ("layers", "cache_batch", "cache_seq", "cache_kv",
                         "cache_kv_dim"),
            "shared_v": ("layers", "cache_batch", "cache_seq", "cache_kv",
                         "cache_kv_dim"),
            "shared_pos": ("layers", "cache_batch", "cache_seq"),
        }
        return {k: names[k] for k in spec}

    def init_cache(self, batch_size: int, cache_len: int, *,
                   paged: Optional[Tuple[int, int]] = None,
                   kv_dtype: Optional[str] = None) -> Dict:
        spec = self.cache_spec(batch_size, cache_len, paged=paged,
                               kv_dtype=kv_dtype)

        def zero(name, s):
            if s.dtype == jnp.int32 and s.shape and (
                    name.startswith("pos") or name.endswith("pos")):
                return jnp.full(s.shape, -1, s.dtype)   # empty slots
            return jnp.zeros(s.shape, s.dtype)
        return {name: zero(name, s) for name, s in spec.items()}

    def prefill(self, params, batch) -> Tuple[jax.Array, Dict]:
        """Full-sequence forward that also populates the cache.

        Returns (last-token logits (B, V), cache)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B, Sq, _ = x.shape
        positions = self._positions(batch, Sq)
        windows = layer_windows(cfg)
        cache = {"len": jnp.asarray(Sq, jnp.int32)}

        if cfg.family in (Family.SSM, Family.HYBRID):
            def body(h, p_l):
                hn, c = _ssm_block(p_l, h, cfg, cache=None, return_cache=True)
                return hn, c
            body = self._maybe_remat(body)
            if cfg.family == Family.SSM:
                x, caches = jax.lax.scan(body, x, params["layers"])
                cache["ssm_conv"], cache["ssm_state"] = caches
                cache["ssm_state"] = cache["ssm_state"].astype(jnp.float32)
            else:
                x, cache = self._hybrid_prefill(params, x, positions, cache,
                                                body)
        else:
            def body(h, xs):
                p_l, win = xs
                hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
                k, v = L.project_kv(p_l["attn"], hln, cfg, positions)
                hn, _, _ = _attn_mlp_block(p_l, h, cfg, positions=positions,
                                           window=win, moe_impl=self.moe_impl)
                return hn, (k, v)
            body = self._maybe_remat(body)
            win_arr = (windows if windows is not None
                       else jnp.full((cfg.num_layers,), BIG_WINDOW, jnp.int32))
            x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], win_arr))
            pos1 = positions if positions.ndim <= 2 else positions[0]
            pos_full = jnp.broadcast_to(
                pos1, (cfg.num_layers, B, Sq)).astype(jnp.int32)
            wl = window_layout(cfg, Sq)
            if wl is None:
                if self.kv_dtype in Q.QUANTIZED_KV_DTYPES:
                    # prefill writes quantized tails: compute ran bf16, only
                    # the cache storage narrows (scales per token per head)
                    store = Q.kv_cache_dtype(self.kv_dtype)
                    kq, kscale = Q.quantize_kv(ks, self.kv_dtype)
                    vq, vscale = Q.quantize_kv(vs, self.kv_dtype)
                    cache["k"], cache["v"] = kq.astype(store), vq.astype(store)
                    cache["k_scale"], cache["v_scale"] = kscale, vscale
                else:
                    cache["k"], cache["v"] = ks, vs
                cache["pos"] = pos_full
            else:
                import numpy as _np
                li = _np.asarray(wl["local_idx"], _np.int32)
                gi = _np.asarray(wl["global_idx"], _np.int32)
                cap = wl["local_cap"]
                shift = (Sq - cap) % cap if cap else 0

                def ring(a):  # keep the last `cap` tokens in ring order
                    tail = a[:, :, Sq - cap:]
                    return jnp.roll(tail, shift, axis=2)
                cache["k_loc"] = ring(ks[li])
                cache["v_loc"] = ring(vs[li])
                cache["pos_loc"] = jnp.roll(pos_full[li][:, :, Sq - cap:],
                                            shift, axis=2)
                if gi.size:
                    cache["k_glob"], cache["v_glob"] = ks[gi], vs[gi]
                    cache["pos_glob"] = pos_full[gi]

        x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        if "length" in batch:
            # right-padded prompts (bucketed prefill): read the logits of
            # each row's last REAL token, not the trailing pad token
            idx = jnp.clip(batch["length"].astype(jnp.int32) - 1, 0,
                           x.shape[1] - 1)
            last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        else:
            last = x[:, -1:, :]
        logits = L.unembed(params["embed"], last, cfg)[:, 0]
        return logits, cache

    def _hybrid_prefill(self, params, x, positions, cache, ssm_body):
        cfg = self.cfg
        ae = cfg.attn_every
        ngroups, tail = divmod(cfg.num_layers, ae)
        B, Sq, _ = x.shape
        shared = params["shared"]
        sk, sv = [], []

        def shared_apply(h):
            hln = L.rms_norm(h, shared["ln1"]["scale"], cfg.rms_eps)
            k, v = L.project_kv(shared["attn"], hln, cfg, positions)
            hn, _, _ = _attn_mlp_block(shared, h, cfg, positions=positions,
                                       window=None)
            return hn, (k, v)

        grouped = jax.tree.map(
            lambda a: a[:ngroups * ae].reshape((ngroups, ae) + a.shape[1:]),
            params["layers"])
        tail_p = jax.tree.map(lambda a: a[ngroups * ae:], params["layers"])

        convs, states = [], []
        # python loop over groups: napp is small (<=14); keeps cache emission
        # simple while inner ssm layers stay scanned.
        for gi in range(ngroups):
            x, kv = shared_apply(x)
            sk.append(kv[0]); sv.append(kv[1])
            p_g = jax.tree.map(lambda a: a[gi], grouped)
            x, c = jax.lax.scan(ssm_body, x, p_g)
            convs.append(c[0]); states.append(c[1])
        if tail:
            x, kv = shared_apply(x)
            sk.append(kv[0]); sv.append(kv[1])
            x, c = jax.lax.scan(ssm_body, x, tail_p)
            convs.append(c[0]); states.append(c[1])

        cache["shared_k"] = jnp.stack(sk)
        cache["shared_v"] = jnp.stack(sv)
        napp = len(sk)
        cache["shared_pos"] = jnp.broadcast_to(
            positions, (napp, B, Sq)).astype(jnp.int32)
        cache["ssm_conv"] = jnp.concatenate(convs, axis=0)
        cache["ssm_state"] = jnp.concatenate(states, axis=0).astype(
            jnp.float32)
        return x, cache

    def prefix_prefill(self, params, batch, cache) -> Tuple[jax.Array, Dict]:
        """Multi-token prefill THROUGH the paged block pool.

        The serving engine admits a request whose leading prompt blocks
        may already sit in the pool (prefix-cache hits): only the suffix
        is forwarded here.  Per layer the suffix tokens' K/V are written
        into the slot's blocks FIRST, then attention runs over the
        gathered cache — which now holds cached-prefix + fresh-suffix KV
        — with position-based causal masking, so each suffix token sees
        the shared prefix and its own predecessors exactly as a full
        prefill would.  With zero cached blocks this degrades to a
        normal prefill routed through the pool (the engine uses it as
        the single paged join path).

        batch: tokens (B, S) suffix tokens right-padded, positions
        (B, S) absolute positions with -1 pads, block_tables
        (B, max_blocks), length (B,) real-suffix-token counts.
        Returns (last-real-token logits (B, V), new_cache)."""
        cfg = self.cfg
        if "k" not in cache or "block_tables" not in batch:
            raise NotImplementedError("prefix_prefill requires a paged "
                                      "dense-attention cache + block tables")
        bt = batch["block_tables"]
        x = self._embed_inputs(params, batch)
        B, Sq, _ = x.shape
        positions = batch["positions"]
        new_cache = dict(cache)

        if "k_scale" in cache:
            def body(h, xs):
                p_l, kc, vc, pc, ksc, vsc = xs
                hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
                k_new, v_new = L.project_kv(p_l["attn"], hln, cfg, positions)
                kc, vc, pc, ksc, vsc = _paged_cache_write_quant(
                    kc, vc, pc, ksc, vsc, k_new, v_new, positions, bt,
                    self.kv_dtype)
                hn, _, _ = _attn_mlp_block(
                    p_l, h, cfg, positions=positions, window=None,
                    cache_kv=(kc, vc, pc, bt, ksc, vsc),
                    moe_impl=self.moe_impl)
                return hn, (kc, vc, pc, ksc, vsc)

            x, (ks, vs, ps, kss, vss) = jax.lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"],
                          cache["pos"], cache["k_scale"], cache["v_scale"]))
            new_cache["k_scale"], new_cache["v_scale"] = kss, vss
        else:
            def body(h, xs):
                p_l, kc, vc, pc = xs
                hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
                k_new, v_new = L.project_kv(p_l["attn"], hln, cfg, positions)
                kc, vc, pc = _paged_cache_write(kc, vc, pc, k_new, v_new,
                                                positions, bt)
                hn, _, _ = _attn_mlp_block(
                    p_l, h, cfg, positions=positions, window=None,
                    cache_kv=(kc, vc, pc, bt), moe_impl=self.moe_impl)
                return hn, (kc, vc, pc)

            x, (ks, vs, ps) = jax.lax.scan(
                body, x, (params["layers"], cache["k"], cache["v"],
                          cache["pos"]))
        new_cache["k"], new_cache["v"], new_cache["pos"] = ks, vs, ps
        new_cache["len"] = jnp.maximum(cache["len"],
                                       jnp.max(positions) + 1)
        x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        idx = jnp.clip(batch["length"].astype(jnp.int32) - 1, 0, Sq - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = L.unembed(params["embed"], last, cfg)[:, 0]
        return logits, new_cache

    def decode_step(self, params, batch, cache) -> Tuple[jax.Array, Dict]:
        """One-token decode. batch: {"tokens": (B, 1), ...}.

        ``batch["pos_row"]`` ((B,) int32) requests PER-ROW cache writes
        and positions, so co-batched sequences at different lengths
        decode correctly (slot-pool serving; the caller supplies
        matching ``batch["positions"]``).  Without it every row advances
        at the pooled ``cache["len"]`` via the leaner contiguous-slice
        cache write — exact for uniform-length batches, e.g. the
        dry-run decode cells (which pass uniform ``positions`` only).

        Attention reads the cache GROUPED (native kv-head count) through
        the split-KV flash-decode dispatch in ``kernels.ops`` — no
        repeat-to-full-head-count materialization on this path.

        Returns (logits (B, V), new_cache)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        B = x.shape[0]
        cur = cache["len"]
        pos_row = batch.get("pos_row", cur)
        if cfg.m_rope_sections is not None:
            positions = batch.get(
                "positions",
                jnp.broadcast_to(cur, (3, B, 1)).astype(jnp.int32))
        else:
            positions = batch.get(
                "positions",
                jnp.broadcast_to(cur, (B, 1)).astype(jnp.int32))
        new_cache = dict(cache)
        new_cache["len"] = cur + 1

        if cfg.family == Family.SSM:
            def body(h, xs):
                p_l, conv, st = xs
                hn, c = _ssm_block(p_l, h, cfg, cache=(conv, st))
                return hn, c
            x, (convs, states) = jax.lax.scan(
                body, x, (params["layers"], cache["ssm_conv"],
                          cache["ssm_state"]))
            new_cache["ssm_conv"], new_cache["ssm_state"] = convs, states
        elif cfg.family == Family.HYBRID:
            x, new_cache = self._hybrid_decode(params, x, positions, cache,
                                               new_cache, pos_row)
        else:
            def make_body(win_static=None):
                def body(h, xs):
                    p_l, kc, vc, pc, win = xs
                    hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
                    k_new, v_new = L.project_kv(p_l["attn"], hln, cfg,
                                                positions)
                    kc, vc, pc = _cache_write(kc, vc, pc, k_new, v_new,
                                              pos_row)
                    hn, _, _ = _attn_mlp_block(
                        p_l, h, cfg, positions=positions, window=win,
                        cache_kv=(kc, vc, pc), moe_impl=self.moe_impl)
                    return hn, (kc, vc, pc)
                return body

            wl = window_layout(cfg, 1 << 30)   # layout only (caps from cache)
            if "block_tables" in batch:
                # paged serving: K/V live in the global block pool;
                # writes scatter through the per-row block table and
                # attention gathers through it inside the kernel grid
                bt = batch["block_tables"]
                prow = (jnp.broadcast_to(pos_row, (B,)).astype(jnp.int32)
                        if getattr(pos_row, "ndim", 1) == 0
                        else pos_row.astype(jnp.int32))

                if "k_scale" in cache:
                    def paged_body(h, xs):
                        p_l, kc, vc, pc, ksc, vsc = xs
                        hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
                        k_new, v_new = L.project_kv(p_l["attn"], hln, cfg,
                                                    positions)
                        kc, vc, pc, ksc, vsc = _paged_cache_write_quant(
                            kc, vc, pc, ksc, vsc, k_new, v_new, prow, bt,
                            self.kv_dtype)
                        hn, _, _ = _attn_mlp_block(
                            p_l, h, cfg, positions=positions, window=None,
                            cache_kv=(kc, vc, pc, bt, ksc, vsc),
                            moe_impl=self.moe_impl)
                        return hn, (kc, vc, pc, ksc, vsc)

                    x, (ks, vs, ps, kss, vss) = jax.lax.scan(
                        paged_body, x,
                        (params["layers"], cache["k"], cache["v"],
                         cache["pos"], cache["k_scale"], cache["v_scale"]))
                    new_cache["k_scale"] = kss
                    new_cache["v_scale"] = vss
                else:
                    def paged_body(h, xs):
                        p_l, kc, vc, pc = xs
                        hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
                        k_new, v_new = L.project_kv(p_l["attn"], hln, cfg,
                                                    positions)
                        kc, vc, pc = _paged_cache_write(kc, vc, pc, k_new,
                                                        v_new, prow, bt)
                        hn, _, _ = _attn_mlp_block(
                            p_l, h, cfg, positions=positions, window=None,
                            cache_kv=(kc, vc, pc, bt),
                            moe_impl=self.moe_impl)
                        return hn, (kc, vc, pc)

                    x, (ks, vs, ps) = jax.lax.scan(
                        paged_body, x,
                        (params["layers"], cache["k"], cache["v"],
                         cache["pos"]))
                new_cache["k"], new_cache["v"], new_cache["pos"] = ks, vs, ps
            elif wl is None:
                x, stack = self._decode_in_place(params, x, positions,
                                                 cache, pos_row)
                new_cache.update(stack)
            elif not wl["global_idx"]:
                # uniform sliding window (mixtral): ring caches everywhere
                win_arr = jnp.full((cfg.num_layers,), cfg.sliding_window,
                                   jnp.int32)
                x, (ks, vs, ps) = jax.lax.scan(
                    make_body(), x,
                    (params["layers"], cache["k_loc"], cache["v_loc"],
                     cache["pos_loc"], win_arr))
                new_cache["k_loc"], new_cache["v_loc"] = ks, vs
                new_cache["pos_loc"] = ps
            else:
                x, new_cache = self._local_global_decode(
                    params, x, positions, cache, new_cache, wl, pos_row, B)

        x = L.rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        logits = L.unembed(params["embed"], x, cfg)[:, 0]
        return logits, new_cache

    def _decode_in_place(self, params, x, positions, cache, pos_row):
        """Decode over the stacked contiguous cache, updated in place.

        The layer loop carries the stacked K, V and positions (and the
        quantized cache's scales) instead of scanning them: each layer
        writes its B new entries at (layer, row, slot) and attention
        reads that layer of the carried stack, so nothing of the cache
        is sliced out per layer or written back.  With the cache
        donated, the carry is the caller's buffer.  Returns (x, the
        updated cache leaves)."""
        cfg = self.cfg
        names = ("k", "v", "pos") + (
            ("k_scale", "v_scale") if "k_scale" in cache else ())
        windows = layer_windows(cfg)
        win_arr = (windows if windows is not None
                   else jnp.full((cfg.num_layers,), BIG_WINDOW, jnp.int32))

        def body(carry, xs):
            h, stack = carry
            p_l, win, layer = xs
            hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
            k_new, v_new = L.project_kv(p_l["attn"], hln, cfg, positions)
            if len(stack) == 5:
                stack = _cache_write_quant(*stack, k_new, v_new, pos_row,
                                           self.kv_dtype, layer=layer)
            else:
                stack = _cache_write(*stack, k_new, v_new, pos_row,
                                     layer=layer)
            # cache_kv order: (k, v, k_pos[, k_scale, v_scale])
            h, _, _ = _attn_mlp_block(
                p_l, h, cfg, positions=positions, window=win,
                cache_kv=stack, cache_layer=layer, moe_impl=self.moe_impl)
            return (h, stack), None

        (x, stack), _ = jax.lax.scan(
            body, (x, tuple(cache[n] for n in names)),
            (params["layers"], win_arr,
             jnp.arange(cfg.num_layers, dtype=jnp.int32)))
        return x, dict(zip(names, stack))

    def _local_global_decode(self, params, x, positions, cache, new_cache,
                             wl, pos_row, B):
        """Decode for local:global patterns (gemma3): local layers read/write
        ring buffers of `window` slots, global layers full caches.  Scans
        run per period group (locals are contiguous within a group)."""
        cfg = self.cfg
        import numpy as _np
        li = _np.asarray(wl["local_idx"], _np.int32)
        gi = _np.asarray(wl["global_idx"], _np.int32)
        p = cfg.local_global_pattern
        params_loc = jax.tree.map(lambda a: a[li], params["layers"])
        params_glob = jax.tree.map(lambda a: a[gi], params["layers"])

        def loc_body(h, xs):
            p_l, kc, vc, pc = xs
            hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
            k_new, v_new = L.project_kv(p_l["attn"], hln, cfg, positions)
            kc, vc, pc = _cache_write(kc, vc, pc, k_new, v_new, pos_row)
            hn, _, _ = _attn_mlp_block(
                p_l, h, cfg, positions=positions, window=cfg.sliding_window,
                cache_kv=(kc, vc, pc), moe_impl=self.moe_impl)
            return hn, (kc, vc, pc)

        def glob_apply(h, p_l, kc, vc, pc):
            hln = L.rms_norm(h, p_l["ln1"]["scale"], cfg.rms_eps)
            k_new, v_new = L.project_kv(p_l["attn"], hln, cfg, positions)
            kc, vc, pc = _cache_write(kc, vc, pc, k_new, v_new, pos_row)
            hn, _, _ = _attn_mlp_block(
                p_l, h, cfg, positions=positions, window=None,
                cache_kv=(kc, vc, pc), moe_impl=self.moe_impl)
            return hn, kc, vc, pc

        nloc = len(li)
        ngroups = len(gi)                       # one global per full period
        kls, vls, pls = [], [], []
        kgs, vgs, pgs = [], [], []
        sl = lambda t, a, b: jax.tree.map(lambda z: z[a:b], t)
        for g in range(ngroups):
            lo, hi = g * p, (g + 1) * p
            x, (kl, vl, pl) = jax.lax.scan(
                loc_body, x,
                (sl(params_loc, lo, hi), cache["k_loc"][lo:hi],
                 cache["v_loc"][lo:hi], cache["pos_loc"][lo:hi]))
            kls.append(kl); vls.append(vl); pls.append(pl)
            pg = jax.tree.map(lambda a: a[g], params_glob)
            x, kg, vg, pgp = glob_apply(x, pg, cache["k_glob"][g],
                                        cache["v_glob"][g],
                                        cache["pos_glob"][g])
            kgs.append(kg); vgs.append(vg); pgs.append(pgp)
        if nloc > ngroups * p:                  # trailing local layers
            lo = ngroups * p
            x, (kl, vl, pl) = jax.lax.scan(
                loc_body, x,
                (sl(params_loc, lo, nloc), cache["k_loc"][lo:],
                 cache["v_loc"][lo:], cache["pos_loc"][lo:]))
            kls.append(kl); vls.append(vl); pls.append(pl)

        new_cache["k_loc"] = jnp.concatenate(kls, axis=0)
        new_cache["v_loc"] = jnp.concatenate(vls, axis=0)
        new_cache["pos_loc"] = jnp.concatenate(pls, axis=0)
        new_cache["k_glob"] = jnp.stack(kgs)
        new_cache["v_glob"] = jnp.stack(vgs)
        new_cache["pos_glob"] = jnp.stack(pgs)
        return x, new_cache

    def _hybrid_decode(self, params, x, positions, cache, new_cache, pos_row):
        cfg = self.cfg
        ae = cfg.attn_every
        ngroups, tail = divmod(cfg.num_layers, ae)
        shared = params["shared"]

        def ssm_body(h, xs):
            p_l, conv, st = xs
            hn, c = _ssm_block(p_l, h, cfg, cache=(conv, st))
            return hn, c

        def shared_apply(h, kc, vc, pc):
            hln = L.rms_norm(h, shared["ln1"]["scale"], cfg.rms_eps)
            k_new, v_new = L.project_kv(shared["attn"], hln, cfg, positions)
            kc, vc, pc = _cache_write(kc, vc, pc, k_new, v_new, pos_row)
            hn, _, _ = _attn_mlp_block(shared, h, cfg, positions=positions,
                                       window=None, cache_kv=(kc, vc, pc))
            return hn, kc, vc, pc

        grouped = jax.tree.map(
            lambda a: a[:ngroups * ae].reshape((ngroups, ae) + a.shape[1:]),
            params["layers"])
        conv_g = cache["ssm_conv"][:ngroups * ae].reshape(
            (ngroups, ae) + cache["ssm_conv"].shape[1:])
        st_g = cache["ssm_state"][:ngroups * ae].reshape(
            (ngroups, ae) + cache["ssm_state"].shape[1:])

        sks, svs, sps, convs, states = [], [], [], [], []
        for gi in range(ngroups):
            x, kc, vc, pc = shared_apply(
                x, cache["shared_k"][gi], cache["shared_v"][gi],
                cache["shared_pos"][gi])
            sks.append(kc); svs.append(vc); sps.append(pc)
            p_g = jax.tree.map(lambda a: a[gi], grouped)
            x, (cv, st) = jax.lax.scan(ssm_body, x,
                                       (p_g, conv_g[gi], st_g[gi]))
            convs.append(cv); states.append(st)
        if tail:
            gi = ngroups
            x, kc, vc, pc = shared_apply(
                x, cache["shared_k"][gi], cache["shared_v"][gi],
                cache["shared_pos"][gi])
            sks.append(kc); svs.append(vc); sps.append(pc)
            tail_p = jax.tree.map(lambda a: a[ngroups * ae:],
                                  params["layers"])
            x, (cv, st) = jax.lax.scan(
                ssm_body, x,
                (tail_p, cache["ssm_conv"][ngroups * ae:],
                 cache["ssm_state"][ngroups * ae:]))
            convs.append(cv); states.append(st)

        new_cache["shared_k"] = jnp.stack(sks)
        new_cache["shared_v"] = jnp.stack(svs)
        new_cache["shared_pos"] = jnp.stack(sps)
        new_cache["ssm_conv"] = jnp.concatenate(convs, axis=0)
        new_cache["ssm_state"] = jnp.concatenate(states, axis=0)
        return x, new_cache


# ---------------------------------------------------------------------------
def chunked_softmax_xent(x: jax.Array, embed_params: Dict, cfg: ModelConfig,
                         labels: jax.Array, chunk: int = 512
                         ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy without materializing the full (B, S, V) logits.

    Scans over sequence chunks; for gemma3 (V=262k) at train_4k this cuts
    the logits intermediate from O(S·V) to O(chunk·V) per device — a memory-
    roofline optimization recorded in EXPERIMENTS.md §Perf.  Returns
    (mean xent over valid tokens, mean z-loss term)."""
    B, Sq, D = x.shape
    chunk = min(chunk, Sq)
    if Sq % chunk:
        chunk = Sq  # fallback: single chunk
    n = Sq // chunk
    xc = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint   # recompute chunk logits in bwd: without this the scan
    def body(carry, xs):  # stacks every chunk's logits = full (B,S,V) again
        tot, totz, cnt = carry
        xi, li = xs
        logits = L.unembed(embed_params, xi, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(li, 0)[..., None], axis=-1)[..., 0]
        valid = (li >= 0).astype(jnp.float32)
        xent = (lse - gold) * valid
        z = jnp.square(lse) * valid
        return (tot + xent.sum(), totz + z.sum(), cnt + valid.sum()), None

    (tot, totz, cnt), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (xc, lc))
    denom = jnp.maximum(cnt, 1.0)
    return tot / denom, totz / denom
