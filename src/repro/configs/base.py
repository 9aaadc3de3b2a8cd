"""Architecture + shape configuration schema.

Every assigned architecture is one ``ArchConfig``; the four assigned input
shapes are ``ShapeConfig``s.  ``reduced()`` produces the CPU-smoke variant of
any architecture (same family/topology, tiny dims).
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm | cnn | vit
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 1e6
    window: Optional[int] = None  # sliding-window attention (Mixtral)
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: Optional[float] = None  # None: the norm module's own default
    act: str = "swiglu"  # swiglu | gelu (tanh) | gelu_erf (exact)
    # attention biases: with qkv_bias, k_bias=False gives BEiT's q and v
    # biases only; out_bias puts a bias on the output projection
    k_bias: bool = True
    out_bias: bool = False
    layer_scale: float = 0.0  # init of the residual branches' scales; 0: none
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 1  # MoE on layer i iff i % moe_every == moe_every - 1
    moe_dense_ff: int = 0  # Arctic: parallel dense-residual MLP width
    capacity_factor: float = 1.25
    # hybrid (Jamba): per-period block pattern; empty = all-attention
    block_pattern: tuple[str, ...] = ()  # entries: "attn" | "mamba" | "slstm" | "mlstm"
    # SSM dims
    ssm_d_state: int = 64
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    # encoder-decoder (Whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0  # stub frontend: precomputed frame embeddings
    # VLM (phi-3-vision): stub frontend provides patch embeddings
    prefix_tokens: int = 0
    prefix_dim: int = 0
    # precision
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    opt_state_dtype: str = "float32"
    # runtime
    # "tp": weights tensor-parallel over the model axis (big models)
    # "dp_only": the model axis joins data parallelism; weights fully
    #   FSDP-sharded and gathered per layer (small models — kills the
    #   per-layer activation all-reduces entirely)
    parallelism: str = "tp"
    scan_layers: bool = True
    remat: bool = True
    attn_block_q: int = 512
    attn_block_kv: int = 512
    # whether long_500k is runnable (sub-quadratic / bounded-context)
    sub_quadratic: bool = False
    # DP defaults
    clipping_mode: str = "mixed_ghost"
    # notes for DESIGN.md / dry-run reports
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def supports(self, shape: ShapeConfig) -> bool:
        if shape.name == "long_500k":
            return self.sub_quadratic
        return True

    def reduced(self) -> "ArchConfig":
        """CPU-smoke variant: same topology, tiny dims."""
        pattern = self.block_pattern
        n_layers = max(2, min(4, self.n_layers)) if not pattern else len(pattern)
        heads = max(2, min(4, self.n_heads))
        kv = max(1, min(self.n_kv, heads))
        # keep the GQA grouping style (kv<heads vs kv==heads)
        if self.n_kv == self.n_heads:
            kv = heads
        return dataclasses.replace(
            self,
            n_layers=n_layers,
            d_model=64,
            n_heads=heads,
            n_kv=kv,
            head_dim=None,
            d_ff=96 if self.d_ff else 0,
            vocab=128,
            moe_experts=min(self.moe_experts, 4),
            moe_dense_ff=48 if self.moe_dense_ff else 0,
            encoder_layers=2 if self.encoder_layers else 0,
            encoder_seq=12 if self.encoder_seq else 0,
            prefix_tokens=4 if self.prefix_tokens else 0,
            prefix_dim=16 if self.prefix_dim else 0,
            ssm_d_state=8,
            ssm_head_dim=8,
            ssm_chunk=8,
            attn_block_q=16,
            attn_block_kv=16,
            dtype="float32",
            param_dtype="float32",
        )
