"""ModelConfig — single dataclass describing every assigned architecture.

Layer-pattern helpers (``layer_kind``/``attn_window``/``ffn_kind``/
``rope_yarn``) express gemma3's 5:1 local:global attention, mellum2's 3:1
with YaRN on its global layers, hymba's hybrid layers with three
global-attention layers, mixtral's all-layer SWA, and mamba2's
attention-free stack — all through one Stack implementation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling (transformers' ``rope_type: "yarn"``, with
    ``truncate`` true): frequencies between the ``beta_fast`` and
    ``beta_slow`` rotation counts over ``original_max`` positions ramp
    from extrapolated to interpolated by ``factor``, and cos/sin scale by
    ``attention_factor``."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    family: str = "causal"        # causal | encdec
    modality: str = "text"        # text | vlm | audio
    kind: str = "attn"            # attn | mamba | hybrid
    ffn: str = "swiglu"           # swiglu | gelu | moe | none
    norm: str = "rmsnorm"         # rmsnorm | layernorm
    mlp_activation: str = "silu"
    causal: bool = True
    tie_embeddings: bool = False
    rope_base: float = 10000.0

    # --- attention window structure ---
    window: Optional[int] = None
    window_all: bool = False                    # SWA on every layer (mixtral)
    local_global_ratio: Optional[Tuple[int, int]] = None  # gemma3 (5, 1)
    global_attn_layers: Tuple[int, ...] = ()    # hymba full-attn layers
    global_yarn: Optional[Yarn] = None          # mellum2: YaRN on global layers

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    # the training forward's slots per expert per sequence, over the mean;
    # serving and calibration route dropless
    capacity_factor: float = 1.25

    # --- SSM (mamba2 / hybrid) ---
    ssm_state: int = 128
    ssm_heads: Optional[int] = None
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    ssm_chunk: int = 128

    # --- modality frontends (stubs) ---
    mm_dim: int = 0        # vlm patch embedding dim
    mm_patches: int = 0    # patches prepended (counted inside seq_len)
    frame_dim: int = 0     # audio frame embedding dim
    dec_ratio: int = 8     # enc-dec: decoder len = seq_len // dec_ratio

    # --- perf knobs (hillclimb surface) ---
    dtype: jnp.dtype = jnp.bfloat16
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    loss_chunk: int = 256
    remat: bool = True
    scan_layers: bool = False  # lax.scan over stacked layer params (prod)

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to a multiple of 128 (TPU lane width; also
        guarantees divisibility by the 16-way model axis).  Padded logits
        are masked to -inf at readout."""
        return -(-self.vocab // 128) * 128

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- per-layer structure ----
    def layer_kind(self, i: int) -> str:
        if self.kind == "mamba":
            return "mamba"
        if self.kind == "hybrid":
            return "hybrid"
        if self.local_global_ratio:
            l, g = self.local_global_ratio
            return "attn_local" if (i % (l + g)) < l else "attn"
        return "attn"

    def attn_window(self, i: int) -> Optional[int]:
        if i in self.global_attn_layers:
            return None
        if self.local_global_ratio:
            l, g = self.local_global_ratio
            return self.window if (i % (l + g)) < l else None
        if self.window_all or self.kind == "hybrid":
            return self.window
        return None

    def ffn_kind(self, i: int) -> str:
        return self.ffn

    def rope_yarn(self, i: int) -> Optional[Yarn]:
        """Layer i's YaRN scaling: ``global_yarn`` on the full-attention
        layers of a windowed stack, else None (plain rotary)."""
        if self.global_yarn is None or self.attn_window(i) is not None:
            return None
        return self.global_yarn

    # ---- sizing helpers (roofline MODEL_FLOPS) ----
    def param_count(self) -> int:
        """Total parameter count N (approximate, matches construction)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        hd, h, kv = self.head_dim, self.n_heads, self.n_kv_heads
        per_layer = 0
        n_attn = sum(
            1 for i in range(self.n_layers)
            if self.layer_kind(i) in ("attn", "attn_local", "hybrid")
        )
        n_mamba = sum(
            1 for i in range(self.n_layers)
            if self.layer_kind(i) in ("mamba", "hybrid")
        )
        attn_p = d * h * hd + 2 * d * kv * hd + h * hd * d
        di = (self.ssm_heads or 1) * self.ssm_head_dim if self.ssm_heads else \
            self.ssm_expand * d
        gn = self.ssm_groups * self.ssm_state
        sh = self.ssm_heads or (di // self.ssm_head_dim)
        mamba_p = 2 * d * di + 2 * d * gn + d * sh + di * d
        if self.ffn == "moe":
            ffn_p = self.n_layers * (self.n_experts * 3 * d * f + d * self.n_experts)
        elif self.ffn == "swiglu":
            ffn_p = self.n_layers * 3 * d * f
        elif self.ffn == "gelu":
            ffn_p = self.n_layers * 2 * d * f
        else:
            ffn_p = 0
        total = n_attn * attn_p + n_mamba * mamba_p + ffn_p + v * d
        if not self.tie_embeddings:
            total += v * d
        if self.family == "encdec":
            total += self.n_layers * (attn_p + 2 * d * f)  # encoder
            total += self.n_layers * attn_p  # cross attention
        return int(total)

    def active_param_count(self) -> int:
        """N_active for MoE rooflines (6*N_active*D)."""
        if self.ffn != "moe":
            return self.param_count()
        d, f = self.d_model, self.d_ff
        dense = self.param_count() - self.n_layers * self.n_experts * 3 * d * f
        return int(dense + self.n_layers * self.top_k * 3 * d * f)
