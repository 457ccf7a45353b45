"""Configuration system for the repro framework.

Three layers of config, mirroring how a production framework (MaxText,
Megatron) separates concerns:

  * :class:`ModelConfig`  — architecture hyperparameters (one per assigned
    arch, see ``repro.configs``).
  * :class:`ShapeConfig`  — the workload shape (seq_len × global_batch and
    which entry point it lowers: train / prefill / decode).
  * :class:`RunConfig`    — model + shape + mesh + optimizer + runtime knobs.

Everything is a frozen dataclass so configs hash, compare and can be used as
jit static arguments.
"""
from __future__ import annotations

import dataclasses
import enum
import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple


class Family(str, enum.Enum):
    DENSE = "dense"
    MOE = "moe"
    SSM = "ssm"
    HYBRID = "hybrid"
    ENCDEC = "encdec"
    VLM = "vlm"
    AUDIO = "audio"


class Activation(str, enum.Enum):
    SWIGLU = "swiglu"   # silu(xW1) * xW3
    GEGLU = "geglu"     # gelu(xW1) * xW3
    GELU = "gelu"       # plain gelu(xW1) (classic transformer / GPT-3)


class StepKind(str, enum.Enum):
    TRAIN = "train"
    PREFILL = "prefill"
    DECODE = "decode"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters.

    The fields cover every family in the assigned pool; family-specific
    fields default to "absent" values and are validated in ``__post_init__``.
    """

    name: str
    family: Family
    num_layers: int
    d_model: int
    vocab_size: int
    # --- attention ---
    num_heads: int = 0                 # 0 for attention-free (ssm)
    num_kv_heads: int = 0
    head_dim: int = 0
    qk_norm: bool = False              # qwen3-style per-head RMSNorm on q,k
    rope_theta: float = 10_000.0
    m_rope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE
    sliding_window: Optional[int] = None        # SWA window (tokens)
    local_global_pattern: int = 0      # gemma3: N local layers per 1 global
    logit_softcap: Optional[float] = None       # gemma-2 style soft capping
    # --- mlp ---
    d_ff: int = 0
    activation: Activation = Activation.SWIGLU
    # --- moe ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    # --- ssm (mamba2 / SSD) ---
    ssm_state: int = 0                 # N: state dimension per head
    ssm_head_dim: int = 64             # P: channels per SSD head
    ssm_expand: int = 2                # d_inner = expand * d_model
    ssm_conv_width: int = 4
    ssm_chunk: int = 256               # SSD chunk length
    # --- hybrid (zamba2) ---
    attn_every: int = 0                # shared attention block every N layers
    # --- enc-dec ---
    encoder_layers: int = 0
    # --- modality frontend stubs ---
    frontend_dim: int = 0              # dim of precomputed frame/patch embeds
    # --- embedding ---
    tie_embeddings: bool = True
    pad_vocab_to_multiple: int = 256   # production vocab padding (sharding)
    # --- norm ---
    rms_eps: float = 1e-6
    # --- provenance ---
    source: str = ""

    def __post_init__(self):
        if self.family in (Family.SSM,):
            assert self.ssm_state > 0, f"{self.name}: ssm arch needs ssm_state"
        if self.family == Family.HYBRID:
            assert self.attn_every > 0 and self.ssm_state > 0
        if self.family == Family.MOE:
            assert self.num_experts > 0 and self.num_experts_per_tok > 0
        if self.num_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # ------------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, self.pad_vocab_to_multiple)

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def uses_attention(self) -> bool:
        return self.family not in (Family.SSM,)

    @property
    def sub_quadratic(self) -> bool:
        """Whether the arch supports O(<L^2) attention at long context.

        SSM/hybrid archs have O(1)-state decode; SWA archs have window-bounded
        caches; local:global mixes are bounded except on global layers (we
        still count gemma3 as runnable at 500k because 5/6 of layers are
        windowed and global layers are decode-only single-query reads).
        """
        if self.family in (Family.SSM, Family.HYBRID):
            return True
        if self.sliding_window is not None:
            return True
        if self.local_global_pattern > 0:
            return True
        return False

    # ------------------------------------------------------------------
    def param_count(self, active_only: bool = False) -> int:
        """Analytic parameter count (used for 6*N*D MODEL_FLOPS)."""
        V, D = self.padded_vocab, self.d_model
        n = V * D  # embedding
        if not self.tie_embeddings:
            n += V * D
        per_layer = 0
        if self.uses_attention:
            H, K, hd = self.num_heads, self.num_kv_heads, self.head_dim
            attn = D * H * hd + 2 * D * K * hd + H * hd * D
            if self.qk_norm:
                attn += 2 * hd
        else:
            attn = 0
        if self.family == Family.MOE:
            e = (self.num_experts_per_tok if active_only else self.num_experts)
            mlp = e * (3 * D * self.d_ff) + D * self.num_experts  # + router
        elif self.d_ff:
            gated = self.activation in (Activation.SWIGLU, Activation.GEGLU)
            mlp = (3 if gated else 2) * D * self.d_ff
        else:
            mlp = 0
        ssm = 0
        if self.family in (Family.SSM, Family.HYBRID):
            din, N = self.d_inner, self.ssm_state
            ngroups = 1
            # in_proj: z, x, B, C, dt
            ssm = D * (2 * din + 2 * ngroups * N + self.ssm_heads)
            ssm += self.ssm_conv_width * (din + 2 * ngroups * N)   # conv1d
            ssm += self.ssm_heads * 2                              # A_log, D
            ssm += din * D                                         # out_proj
            ssm += 2 * D                                           # norms
        if self.family == Family.HYBRID:
            # every layer is an SSM block; shared attention+MLP block is one
            # extra set of weights (weight-tied across applications).
            per_layer = ssm + 2 * D
            n += self.num_layers * per_layer
            n += attn + 3 * D * (self.d_ff or 4 * D) + 4 * D   # shared block
            return n
        if self.family == Family.SSM:
            n += self.num_layers * (ssm + 2 * D)
            return n
        per_layer = attn + mlp + 4 * D  # two RMSNorms (gemma uses 4; close)
        n += self.num_layers * per_layer
        if self.family == Family.ENCDEC:
            # encoder layers: self-attn + mlp; decoder adds cross-attn
            enc = self.encoder_layers * (attn + mlp + 4 * D)
            dec_cross = self.num_layers * attn
            n += enc + dec_cross
        return n

    def flops_per_token(self, active_only: bool = True) -> float:
        """~6 * N_active params per token (training fwd+bwd)."""
        return 6.0 * self.param_count(active_only=active_only)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: StepKind

    @property
    def tokens_per_step(self) -> int:
        if self.kind == StepKind.DECODE:
            return self.global_batch          # one new token per sequence
        return self.seq_len * self.global_batch


# The four assigned LM shapes -------------------------------------------------
SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, StepKind.TRAIN),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, StepKind.PREFILL),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, StepKind.DECODE),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, StepKind.DECODE),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Which (arch x shape) cells run; mirrors DESIGN.md §Arch-applicability."""
    if shape.name == "long_500k" and not model.sub_quadratic:
        return False, ("pure full-attention arch: O(L^2) attention and "
                       "O(L) unwindowed KV cache at 524k — skipped per spec")
    return True, ""


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    zero1: bool = True                   # shard optimizer state over data axis
    grad_compression: str = "none"       # none | bf16 | int8_ef


@dataclass(frozen=True)
class ParallelConfig:
    """Logical parallelism degrees. dp is inferred from the mesh."""
    tp: int = 1
    pp: int = 1          # pipeline stages
    vp: int = 1          # virtual pipeline (interleaved) stages per device
    cp: int = 1          # context parallel
    sp: bool = True      # sequence-parallel norm regions
    ep: int = 1          # expert parallel
    microbatch: int = 0  # 0 = no grad accumulation
    fsdp: bool = True    # shard weights over the data axis (ZeRO-3 style)
    remat: str = "full"  # none | full | selective


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    parallel: ParallelConfig = ParallelConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    seed: int = 0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Hardware model: per-chip peaks (roofline, fabric model, planner, MFU)
@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float                # FLOP/s per chip
    hbm_bandwidth: float                  # B/s per chip
    ici_link_bandwidth: float             # B/s per link (per direction)
    ici_links_per_chip: int               # 2D torus: 4 links
    hbm_bytes: int
    vmem_bytes: int


# Published peaks, keyed by jax's ``Device.device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of ICI per chip (4 links); 128 MiB of VMEM per core.
PEAKS: Dict[str, ChipSpec] = {
    "TPU v5 lite": ChipSpec(name="tpu-v5e", peak_bf16_flops=197e12,
                            hbm_bandwidth=819e9, ici_link_bandwidth=50e9,
                            ici_links_per_chip=4, hbm_bytes=16 * 2**30,
                            vmem_bytes=128 * 2**20),
}

# The chip the planner and roofline models target.
CHIP = PEAKS["TPU v5 lite"]


def chip_peaks(device_kind: Optional[str]) -> Optional[ChipSpec]:
    """Published peaks of a device kind; None where the table has none
    (a measurement then reports no utilization, never a guessed one)."""
    return PEAKS.get(device_kind) if device_kind else None
