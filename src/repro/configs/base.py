"""Architecture + shape configuration.

Every assigned architecture is a frozen `ArchConfig`; the four assigned
input-shape cells are `ShapeConfig`s.  `reduced()` produces the small-config
variant used by CPU smoke tests and the RL experiments; `cut()` keeps every
width and trims only depth / vocabulary rows, which is how a full config
runs on one chip; the full config is exercised via the 512-device dry-run
(ShapeDtypeStruct only).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned (seq_len, global_batch) cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    # identity -----------------------------------------------------------
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm | audio
    source: str = ""              # provenance note "[arXiv:... ; tier]"

    # transformer dims -----------------------------------------------------
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0              # 0 => attention-free
    n_kv_heads: int = 0
    d_head: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # MoE --------------------------------------------------------------
    n_experts: int = 0
    top_k: int = 0
    moe_period: int = 1           # layer i is MoE iff n_experts>0 and i % moe_period == moe_offset
    moe_offset: int = 0
    capacity_factor: float = 1.25

    # hybrid (attention : SSM interleave) --------------------------------
    attn_period: int = 0          # 0 = all layers attention; k>0 = 1 attn per k layers

    # SSM (Mamba2 / SSD) ---------------------------------------------------
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64

    # encoder-decoder ------------------------------------------------------
    n_enc_layers: int = 0         # >0 => encoder-decoder

    # modality frontend stub ------------------------------------------------
    frontend: Optional[str] = None   # "audio_frames" | "vision_patches"
    frontend_len: int = 0            # stub prefix length (patches / frames)

    # misc ---------------------------------------------------------------
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    act: str = "silu"
    mlp_gated: bool = True        # SwiGLU-style (3 mats) vs classic 2-mat MLP
    qk_norm: bool = False

    # ------------------------------------------------------------------
    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.n_heads == 0

    @property
    def sub_quadratic(self) -> bool:
        """True when decode cost per token does not require a dense KV cache
        over the whole context for every layer."""
        return self.family in ("ssm", "hybrid")

    def is_attn_layer(self, i: int) -> bool:
        if self.attention_free:
            return False
        if self.attn_period <= 1:
            return True
        return i % self.attn_period == 0

    def is_moe_layer(self, i: int) -> bool:
        return self.n_experts > 0 and i % self.moe_period == self.moe_offset

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    # ------------------------------------------------------------------
    def shapes(self) -> Tuple[ShapeConfig, ...]:
        """The assigned shape cells this arch actually runs (skips recorded
        in DESIGN.md §4 / EXPERIMENTS.md)."""
        cells = [TRAIN_4K, PREFILL_32K, DECODE_32K]
        if self.sub_quadratic:
            cells.append(LONG_500K)
        return tuple(cells)

    def skipped_shapes(self) -> Tuple[Tuple[ShapeConfig, str], ...]:
        if self.sub_quadratic:
            return ()
        return ((LONG_500K, "pure full-attention arch: 500k dense decode "
                            "requires sub-quadratic attention (DESIGN.md §4)"),)

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        per_attn = d * (self.n_heads * self.d_head) * 2 \
            + d * (self.n_kv_heads * self.d_head) * 2 if not self.attention_free else 0
        per_mlp = (3 if self.mlp_gated else 2) * d * f
        per_moe = self.n_experts * 3 * d * f + d * self.n_experts
        per_ssm = 0
        if self.ssm_state:
            di, n, h = self.d_inner, self.ssm_state, self.ssm_heads
            per_ssm = d * (2 * di + 2 * n + h) + di * d \
                + self.ssm_conv * (di + 2 * n) + 3 * h + di
        total = v * d * (1 if self.tie_embeddings else 2)
        for i in range(self.n_layers):
            if self.is_attn_layer(i):
                total += per_attn
            elif self.ssm_state:
                total += per_ssm
            if self.family == "ssm":
                continue  # mamba2 blocks have no separate MLP
            total += per_moe if self.is_moe_layer(i) else per_mlp
        for _ in range(self.n_enc_layers):
            total += per_attn + per_mlp
        return total

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top_k of n_experts)."""
        if self.n_experts == 0:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        dense_total = self.param_count()
        n_moe_layers = sum(self.is_moe_layer(i) for i in range(self.n_layers))
        inactive = n_moe_layers * (self.n_experts - self.top_k) * 3 * d * f
        return dense_total - inactive

    # ------------------------------------------------------------------
    def cut(self, n_layers: Optional[int] = None,
            vocab_size: Optional[int] = None) -> "ArchConfig":
        """Every published width kept; only depth and (for training) the
        vocabulary rows are cut — the way a full config is sized to one
        chip.  None keeps the published value."""
        return dataclasses.replace(
            self,
            n_layers=self.n_layers if n_layers is None else n_layers,
            vocab_size=self.vocab_size if vocab_size is None else vocab_size)

    def reduced(self, **overrides) -> "ArchConfig":
        """Small same-family variant for CPU smoke tests / RL experiments."""
        changes = dict(
            n_layers=min(self.n_layers, 4),
            d_model=min(self.d_model, 128),
            d_ff=min(self.d_ff, 256) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            n_enc_layers=min(self.n_enc_layers, 2),
            frontend_len=min(self.frontend_len, 8) if self.frontend_len else 0,
        )
        if not self.attention_free:
            n_heads = min(self.n_heads, 4)
            ratio = max(1, self.n_heads // max(self.n_kv_heads, 1))
            changes.update(
                n_heads=n_heads,
                n_kv_heads=max(1, n_heads // min(ratio, n_heads)),
                d_head=min(self.d_head, 32),
            )
        if self.n_experts:
            # capacity_factor=8: effectively dropless at smoke-test scale, so
            # the incremental and teacher-forced paths compute the same MoE
            # function (capacity drops are a *grouping-dependent* semantic —
            # see test_decode_matches_teacher_forcing).
            changes.update(n_experts=min(self.n_experts, 4),
                           top_k=min(self.top_k, 2),
                           capacity_factor=8.0)
        if self.ssm_state:
            changes.update(ssm_state=min(self.ssm_state, 16), ssm_head_dim=16)
        if self.attn_period > 1:
            changes.update(n_layers=max(changes["n_layers"], self.attn_period))
        changes.update(overrides)
        return dataclasses.replace(self, **changes)
