"""Model / run configuration schema.

A ``ModelConfig`` fully determines parameter shapes and the layer stack. The
stack is a repeated ``layer_pattern`` (a tuple of LayerSpec): scan-over-
periods compiles one period body regardless of depth (126-layer llama3-405b
compiles one layer). Patterns express the assigned archs' heterogeneity:
gemma3's 5:1 local:global, gemma2's 1:1 alternation, llama-3.2-vision's
every-5th cross-attention layer, hymba's uniform hybrid blocks.

``InputShape`` describes one dry-run cell (seq_len x global_batch x step
kind); each arch config lists its four assigned shapes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One position within the repeating layer pattern."""

    kind: str = "attn"          # "attn" | "mamba" | "hybrid"
    ffn: str = "mlp"            # "mlp" | "moe" | "none"
    window: Optional[int] = None  # sliding-window size; None = global
    cross_attn: bool = False     # cross-attend to encoder states (VLM)
    rope_theta: float = 10000.0


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8         # experts the router scores
    top_k: int = 2
    d_ff: int = 0                # per-expert hidden dim
    capacity_factor: float = 1.25
    num_shared_experts: int = 0
    router_z_loss: float = 1e-3
    norm_topk_prob: bool = True  # renormalise the top-k gates (Mixtral)
    routed_scaling_factor: float = 1.0
    # The experts this device holds: [held_start, held_start + num_held)
    # of the router's ``num_experts`` (expert parallelism). None: all.
    held_start: int = 0
    num_held: Optional[int] = None

    @property
    def held(self) -> int:
        """Experts whose weights this device holds."""
        return self.num_experts if self.num_held is None else self.num_held


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (DeepSeek-V2 ``rope_scaling``, type "yarn")."""

    factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2 section 2.1), without query
    compression: every query head is ``qk_nope_head_dim`` + ``qk_rope_head_dim``
    wide; keys and values come from one ``kv_lora_rank`` latent per token
    (RMS-normed) plus one shared rope key. The paged cache holds that latent
    and the roped key, ``kv_lora_rank + qk_rope_head_dim`` values a token."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    yarn: Optional[YarnConfig] = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128         # N
    head_dim: int = 64           # P
    num_heads: int = 0           # 0 => derived: expand*d_model // head_dim
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    num_groups: int = 1          # B/C groups (GVA)
    impl: str = "auto"           # "auto" | "xla" | "pallas" (kernels/ssd.py)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layer_pattern: Tuple[LayerSpec, ...] = (LayerSpec(),)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # attention extras
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    rope_theta: float = 10000.0
    qk_norm: bool = False
    # io / modality
    tie_embeddings: bool = True
    num_codebooks: int = 1       # >1: musicgen-style multi-codebook LM
    vision_tokens: int = 0       # >0: VLM with stub patch-embedding frontend
    vision_dim: int = 0
    embed_scale: bool = False    # gemma-style sqrt(d) embedding scale
    # runtime
    max_seq_len: int = 131072
    compute_dtype: str = "bfloat16"
    attn_impl: str = "auto"      # kernels/ops.py dispatch
    mapping_name: str = "auto"   # "auto": kernels/ops.py resolve_mapping
                                 # picks per shape; or a PAPER_MAPPINGS name
                                 # for the fixed A/B configurations
    scan_unroll: int = 1         # lax.scan unroll for the layer stack
    attn_chunk_unroll: bool = False  # unroll the xla_flash KV-chunk scan
                                  # (cost probes: inner scans also count once)
    remat_policy: str = "nothing"  # "nothing" | "dots" — activation ckpt policy
    # Mesh-level head placement (the paper's technique at pod scale):
    # "acc_aligned" keeps whole KV groups per model shard (zero KV motion);
    # "striped" reproduces the naive round-robin baseline for A/B runs.
    head_placement: str = "acc_aligned"
    placement_shards: int = 16
    # training
    z_loss: float = 1e-4

    #: Fields of :class:`LatentModelConfig`; a plain stack has neither.
    lead_pattern = ()
    mla = None

    def pattern_for_depth(self) -> Tuple[Tuple[LayerSpec, ...], Tuple[LayerSpec, ...]]:
        """(scanned periods pattern, remainder layers)."""
        p = len(self.layer_pattern)
        n_periods = self.n_periods
        rem = self.n_layers - len(self.lead_pattern) - n_periods * p
        return self.layer_pattern, self.layer_pattern[:rem]

    @property
    def n_periods(self) -> int:
        return (self.n_layers - len(self.lead_pattern)) // len(self.layer_pattern)

    def all_layers(self) -> Tuple[LayerSpec, ...]:
        """Every layer's spec, in order: lead, scanned periods, remainder."""
        pattern, rem = self.pattern_for_depth()
        return tuple(self.lead_pattern) + tuple(pattern) * self.n_periods + tuple(rem)

    def param_count(self) -> int:
        """Analytic parameter count (embedding included once if tied)."""
        d, h, hkv, hd, dff = (
            self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.d_ff
        )
        layers = self.all_layers()
        attn_p = d * hd * (h + 2 * hkv) + h * hd * d
        if self.mla:
            a = self.mla
            attn_p = (d * h * a.qk_head_dim + d * a.latent_dim + a.kv_lora_rank
                      + a.kv_lora_rank * h * (a.qk_nope_head_dim + a.v_head_dim)
                      + h * a.v_head_dim * d)
        mlp_p = 3 * d * dff if dff else 0
        ssm_p = 0
        if self.ssm:
            s = self.ssm
            d_in = s.expand * d
            nh = s.num_heads or d_in // s.head_dim
            # in_proj (z,x,B,C,dt) + conv + A,D + norm + out_proj
            ssm_p = d * (2 * d_in + 2 * s.num_groups * s.state_dim + nh)
            ssm_p += (d_in + 2 * s.num_groups * s.state_dim) * s.conv_width
            ssm_p += 2 * nh + d_in + d_in * d
        moe_p = 0
        if self.moe:
            m = self.moe
            moe_p = d * m.num_experts + (m.held + m.num_shared_experts) * 3 * d * m.d_ff
        total = 0
        for spec in layers:
            if spec.kind in ("attn", "hybrid"):
                total += attn_p
            if spec.kind in ("mamba", "hybrid"):
                total += ssm_p
            if spec.cross_attn:
                total += attn_p + d  # cross block + its norm
            if spec.ffn == "mlp":
                total += mlp_p
            elif spec.ffn == "moe":
                total += moe_p
            total += 2 * d  # norms
        total += self.vocab * d * self.num_codebooks  # embed (tied head)
        if not self.tie_embeddings:
            total += self.vocab * d * self.num_codebooks
        if self.vision_tokens:
            total += self.vision_dim * d
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if not self.moe:
            return self.param_count()
        m = self.moe
        full_moe = (m.held + m.num_shared_experts) * 3 * self.d_model * m.d_ff
        act_moe = (m.top_k + m.num_shared_experts) * 3 * self.d_model * m.d_ff
        n_moe_layers = sum(1 for s in self.all_layers() if s.ffn == "moe")
        return self.param_count() - n_moe_layers * (full_moe - act_moe)


@dataclasses.dataclass(frozen=True)
class LatentModelConfig(ModelConfig):
    """A stack with leading layers and latent attention (DeepSeek-V2)."""

    # Layers applied once, before the scanned periods (the leading dense
    # layers); counted in ``n_layers``.
    lead_pattern: Tuple[LayerSpec, ...] = ()
    mla: Optional[MLAConfig] = None  # latent attention on every attn layer


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One assigned (arch x shape) dry-run cell."""

    name: str               # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    step: str               # "train" | "prefill" | "decode"

    @property
    def is_decode(self) -> bool:
        return self.step == "decode"


TRAIN_4K = InputShape("train_4k", 4096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32768, 128, "decode")
LONG_500K = InputShape("long_500k", 524288, 1, "decode")

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
