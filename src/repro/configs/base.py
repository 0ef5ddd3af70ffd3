"""Architecture + shape configuration system.

Every assigned architecture gets one module in this package exporting
``CONFIG: ArchConfig`` with the exact published dimensions. The registry in
``__init__`` resolves ``--arch <id>`` strings.

Design notes
------------
* ``ArchConfig`` is a frozen dataclass so configs are hashable and safe to
  close over in jitted functions.
* ``reduced()`` returns a tiny same-family config for CPU smoke tests; the
  full config is only ever *lowered* (dry-run), never allocated on CPU.
* Shapes are global; the sharding layer divides them across the mesh.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Shapes (assigned per the task spec; identical for the LM family)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

ALL_SHAPES: Tuple[ShapeConfig, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES = {s.name: s for s in ALL_SHAPES}


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                      # per-expert FFN hidden dim
    capacity_factor: float = 1.25      # DCRA: the IQ size knob (Table II #8)
    # 'einsum'   : dense dispatch/combine masks, XLA SPMD partitions (baseline)
    # 'dcra'     : shard_map hierarchical two-level all-to-all (paper technique)
    dispatch_impl: str = "einsum"
    router_jitter: float = 0.0
    # gating (core.dispatch.gate): 'softmax' takes the top-k of the
    # softmax; 'sigmoid' is DeepSeek-V3's noaux_tc: sigmoid scores, a
    # selection-only bias, and the top-k of the experts in the topk_group
    # best of n_group groups. Either way the k gates are renormalised to
    # sum to 1, then scaled by routed_scaling_factor.
    scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # shared experts: a dense SwiGLU of width n_shared * d_shared that
    # every token passes through beside its routed experts
    n_shared: int = 0
    d_shared: int = 0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) / RWKV6 recurrence parameters."""
    state_dim: int = 64                # N (mamba2 ssm_state) or head dim (rwkv)
    head_dim: int = 64
    chunk_size: int = 256              # chunked-scan block length
    conv_width: int = 4                # mamba2 depthwise conv
    expand: int = 2                    # mamba2 inner expansion


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                        # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int                     # query heads (0 for attn-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                  # 0 -> d_model // num_heads
    qkv_bias: bool = False
    sliding_window: int = 0            # 0 = full attention; >0 = SWA window
    rope_theta: float = 1e4
    mrope: bool = False                # Qwen2-VL multimodal RoPE
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2): one shared attention block applied every `period` layers
    hybrid_attn_period: int = 0
    # enc-dec (seamless): encoder layer count (decoder = num_layers)
    encoder_layers: int = 0
    frontend: str = "none"             # none | audio_frames | vision_patches
    # source tag from the assignment table
    source: str = ""
    # runtime policy knobs (Table II compile-time analogues)
    remat: str = "block"               # none | block | full | dots
    scan_layers: bool = True
    accum_steps: int = 1               # grad-accumulation microbatches

    # ---- derived -----------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run the long_500k decode cell?"""
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks), for roofline's 6ND."""
        d, v = self.d_model, self.vocab_size
        emb = v * d * (1 if self.tie_embeddings else 2)
        hd = self.resolved_head_dim
        total = emb
        n_dec = self.num_layers
        for _ in range(n_dec):
            total += self._block_params(d, hd)
        if self.family == "hybrid":
            # zamba2: the attention+MLP block is WEIGHT-SHARED across its
            # applications -> counted once, not per application.
            q = d * hd * self.num_heads
            kv = 2 * d * hd * self.num_kv_heads
            o = hd * self.num_heads * d
            total += q + kv + o + 3 * d * self.d_ff
        if self.encoder_layers:
            for _ in range(self.encoder_layers):
                total += self._block_params(d, hd, cross=False)
            # decoder cross-attention adds one attention block per layer
            total += n_dec * (d * hd * self.num_heads + 2 * d * hd * self.num_kv_heads
                              + hd * self.num_heads * d)
        return total

    def _block_params(self, d: int, hd: int, cross: bool = False) -> int:
        p = 2 * d  # norms
        if self.family == "ssm":  # rwkv6: tmix (~4 d^2 + decay mlp) + cmix (~3 d*ff)
            p += 4 * d * d + d * 64 * 2 + 3 * d * self.d_ff
            return p
        if self.family == "hybrid":
            # mamba2 block only (shared attn+MLP counted once in param_count)
            ss = self.ssm or SSMConfig()
            d_in = ss.expand * d
            n_heads = d_in // ss.head_dim
            # in_proj -> [z, x, B, C, dt]; conv over (x,B,C); out_proj
            p += d * (2 * d_in + 2 * ss.state_dim + n_heads)
            p += ss.conv_width * (d_in + 2 * ss.state_dim)
            p += d_in * d
            return p
        # attention
        q = d * hd * self.num_heads
        kv = 2 * d * hd * self.num_kv_heads
        o = hd * self.num_heads * d
        p += q + kv + o
        # ffn
        if self.moe is not None:
            p += self.moe.num_experts * 3 * d * self.moe.d_expert + d * self.moe.num_experts
            p += 3 * d * self.moe.n_shared * self.moe.d_shared
        else:
            p += 3 * d * self.d_ff  # SwiGLU: gate,up,down
        return p

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k experts only)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        full = self.param_count()
        inactive = self.num_layers * (self.moe.num_experts - self.moe.top_k) * 3 * d * self.moe.d_expert
        return full - inactive

    # ---- reduced config for smoke tests -------------------------------
    def reduced(self) -> "ArchConfig":
        """Tiny same-family config: 2 layers, narrow dims, small vocab."""
        kw = {}
        heads = min(self.num_heads, 4) if self.num_heads else 0
        kvh = min(self.num_kv_heads, max(1, heads // 2)) if self.num_kv_heads else 0
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe, num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2), d_expert=64)
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(self.ssm, state_dim=16, head_dim=16,
                                            chunk_size=32)
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            num_layers=2,
            d_model=64,
            num_heads=heads,
            num_kv_heads=kvh,
            head_dim=16 if heads else 0,
            d_ff=128,
            vocab_size=256,
            sliding_window=min(self.sliding_window, 16) if self.sliding_window else 0,
            encoder_layers=2 if self.encoder_layers else 0,
            hybrid_attn_period=2 if self.hybrid_attn_period else 0,
            scan_layers=False,
            **kw,
        )

    def shape_cells(self) -> Tuple[ShapeConfig, ...]:
        """The shape cells this arch runs (skips documented in DESIGN.md §5)."""
        cells = [TRAIN_4K, PREFILL_32K, DECODE_32K]
        if self.sub_quadratic:
            cells.append(LONG_500K)
        return tuple(cells)
