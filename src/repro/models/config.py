"""Model configuration: one dataclass covering all assigned families."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.sparse_linear import SparsityConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int              # 0 for attn-free
    num_kv_heads: int
    d_ff: int                   # dense MLP or per-expert FFN width
    vocab_size: int
    head_dim: int = 128
    # --- MoE ---
    num_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.25
    # expert execution: "gather" scatters a capacity of tokens per expert
    # into a dense tile; "spgemm" keeps the full token set and runs the
    # expert FFN as a sparse x sparse contraction (routing holes become
    # dynamic activation sparsity the masked kernels skip in-block)
    moe_expert_path: str = "gather"
    # --- SSM (Mamba-2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    # --- attention pattern ---
    causal: bool = True
    window: int = 0             # >0: sliding-window size for "local" layers
    local_global_period: int = 0  # e.g. 6 for gemma3's 5:1 (every 6th global)
    hybrid_period: int = 0      # jamba: 8 (1 attn layer per period)
    moe_every: int = 0          # jamba: 2 (MoE on every other layer)
    act: str = "swiglu"         # swiglu | gelu
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-6  # added to the mean square in every RMSNorm
    # --- modality frontend (stub per assignment) ---
    frontend: str = "none"      # none | audio_frames | vision_patches
    num_patches: int = 0        # vlm: image tokens per sample
    # --- sparsity (the paper's feature) ---
    sparsity: SparsityConfig = dataclasses.field(default_factory=SparsityConfig)
    # --- numerics / training ---
    dtype: str = "bfloat16"
    remat_policy: str = "dots_nobatch"  # none | dots | dots_nobatch | full
    attn_chunk: int = 1024      # KV chunk for online-softmax attention
    attn_p_bf16: bool = False   # store attention probs bf16 (perf knob)
    attn_scores_bf16: bool = False  # scores+probs bf16 (bigger perf knob)

    # ------------------------------------------------------------------
    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_encoder(self) -> bool:
        return not self.causal

    @property
    def attn_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def layer_is_global(self, i: int) -> bool:
        """gemma3-style local:global interleave (last of each period global)."""
        if self.local_global_period <= 0 or self.window <= 0:
            return True
        return (i % self.local_global_period) == self.local_global_period - 1

    def with_sparsity(self, sp: SparsityConfig) -> "ModelConfig":
        return dataclasses.replace(self, sparsity=sp)

    def param_count(self) -> int:
        """Approximate parameter count (embedding + blocks)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        n = 0
        n += v * d                              # embed
        if not self.tie_embeddings:
            n += v * d                          # unembed
        n_ffn_mats = 3 if self.act == "swiglu" else 2
        per_mlp = n_ffn_mats * d * ff
        per_attn = d * self.attn_dim + 2 * d * self.kv_dim + self.attn_dim * d
        di = self.d_inner
        g = self.ssm_state
        per_mamba = (
            d * (2 * di + 2 * g + self.ssm_heads)  # in_proj (z,x,B,C,dt)
            + di * d                                # out_proj
            + (di + 2 * g) * self.ssm_conv          # conv
            + 3 * self.ssm_heads                    # A, D, dt_bias
        )
        for i in range(self.num_layers):
            mixer_attn = True
            if self.family == "ssm":
                mixer_attn = False
            elif self.family == "hybrid":
                mixer_attn = (i % self.hybrid_period) == self.hybrid_period - 1
            n += per_attn if mixer_attn else per_mamba
            if self.family == "ssm":
                continue  # pure mamba2: no MLP
            is_moe = self.num_experts > 0 and (
                self.moe_every == 0 or (i % self.moe_every == 1)
            )
            if is_moe:
                n += self.num_experts * per_mlp + d * self.num_experts
            else:
                n += per_mlp
            n += 2 * d  # norms
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k of num_experts)."""
        if self.num_experts == 0:
            return self.param_count()
        full = self.param_count()
        n_ffn_mats = 3 if self.act == "swiglu" else 2
        per_mlp = n_ffn_mats * self.d_model * self.d_ff
        n_moe_layers = sum(
            1
            for i in range(self.num_layers)
            if self.num_experts > 0 and (self.moe_every == 0 or i % self.moe_every == 1)
            and not (self.family == "ssm")
        )
        return full - n_moe_layers * (self.num_experts - self.top_k) * per_mlp


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str          # train_4k | prefill_32k | decode_32k | long_500k
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int
