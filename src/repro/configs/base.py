"""Config dataclasses for the repro framework.

Everything in the framework is driven by these configs: model construction
(`repro.models`), sharding rules (`repro.parallel`), the launchers
(`repro.launch`) and the dry-run/roofline tooling.

Configs are plain frozen dataclasses (no external deps) so they can be
constructed in tests, serialized into checkpoints, and diffed in logs.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN configuration."""

    num_experts: int = 8
    top_k: int = 2
    num_shared_experts: int = 0      # deepseek-style always-on shared experts
    d_expert: int = 0                # per-expert hidden dim (0 -> use d_ff)
    capacity_factor: float = 1.25    # tokens per expert = cf * tokens * k / E
    first_k_dense: int = 0           # deepseek: first k layers use dense FFN
    dense_d_ff: int = 0              # d_ff of those dense layers
    moe_period: int = 1              # MoE every `period` layers (jamba: 2)
    router_aux_weight: float = 0.01  # load-balancing aux loss weight
    router_z_weight: float = 1e-4    # router z-loss weight
    # dispatch algorithm: 'einsum' (GShard one-hot matmuls, baseline) or
    # 'scatter' (beyond-paper: indexed scatter/gather — no O(T*E*C)
    # dispatch tensors, no dispatch matmul flops)
    dispatch: str = "einsum"
    # renormalise the top-k gate values to sum to 1 (Mixtral, Qwen-MoE);
    # DeepSeek's gate keeps the softmax scores as they are
    norm_topk_prob: bool = True


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434).

    Keys and values are expanded per head from one compressed latent
    ``c_kv`` of ``kv_lora_rank`` values (normalised), plus one RoPE key
    head of ``qk_rope_head_dim`` shared by every head; the cache holds
    only ``[c_kv; k_pe]``.  Queries are not compressed (no q-LoRA).
    """

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Cached values per token per layer: ``[c_kv; k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_dim(self) -> int:
        """Width of a row of the paged latent pool: ``latent_dim``
        zero-padded to a multiple of 128 lanes (576 -> 640), the width a
        TPU's tiled layout gives the row in HBM anyway and the width a
        kernel's DMA of a whole page must have."""
        return -(-self.latent_dim // 128) * 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (arXiv:2309.00071), DeepSeek-V2's variant."""

    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class MambaConfig:
    """Mamba-1 selective SSM block configuration."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block stack configuration (mLSTM/sLSTM interleave)."""

    # Pattern string over layers, cycled: 'm' = mLSTM, 's' = sLSTM.
    pattern: str = "msmmmms"
    proj_factor_mlstm: float = 2.0
    proj_factor_slstm: float = 1.3333
    conv_kernel: int = 4
    chunk_size: int = 64             # chunkwise-parallel mLSTM chunk


@dataclass(frozen=True)
class FrontendConfig:
    """Modality frontend STUB config ([vlm]/[audio] archs).

    The backbone consumes precomputed patch/frame embeddings; `input_specs`
    produces ShapeDtypeStructs for them.  No frontend weights are built.
    """

    kind: str = "none"               # 'none' | 'vision' | 'audio'
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)  # qwen2-vl M-RoPE
    num_codebooks: int = 4           # musicgen EnCodec streams (stub: folded)


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"            # dense|moe|ssm|vlm|hybrid|audio|cyclegan

    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0                # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 32000

    # attention details
    qk_norm: bool = False            # qwen3
    qkv_bias: bool = False           # qwen1.5/2.5
    rope_theta: float = 10_000.0
    use_mrope: bool = False          # qwen2-vl
    # 'auto': flash-style chunked online-softmax attention for long seqs
    # (the pure-JAX twin of kernels/flash_attention.py), dense for short.
    attn_impl: str = "auto"          # auto | dense | chunked
    attn_chunk: int = 1024           # KV chunk for the chunked impl

    # block pattern for hybrid archs; cycled over layers.
    # 'a' = attention block, 'M' = mamba block. Dense/MoE archs use all-'a'.
    block_pattern: str = "a"

    moe: Optional[MoEConfig] = None
    # latent attention replaces every attention layer's projections and
    # cache; YaRN scales its rotary frequencies
    mla: Optional[MLAConfig] = None
    yarn: Optional[YarnConfig] = None
    mamba: Optional[MambaConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    frontend: FrontendConfig = field(default_factory=FrontendConfig)

    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    # --- derived -----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind, cycling `block_pattern`."""
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.num_layers))

    def is_moe_layer(self, i: int) -> bool:
        if self.moe is None:
            return False
        if i < self.moe.first_k_dense:
            return False
        return (i % self.moe.moe_period) == (self.moe.moe_period - 1) \
            if self.moe.moe_period > 1 else True

    # --- parameter accounting (used for MODEL_FLOPS = 6*N*D) ---------------
    def _attn_params(self) -> int:
        if self.mla is not None:
            m, H = self.mla, self.num_heads
            return (self.d_model * H * m.qk_head_dim             # wq
                    + self.d_model * m.latent_dim                 # wkv_a
                    + m.kv_lora_rank                              # kv norm
                    + m.kv_lora_rank * H * (m.qk_nope_head_dim
                                            + m.v_head_dim)       # wkv_b
                    + H * m.v_head_dim * self.d_model)            # wo
        hd = self.resolved_head_dim
        p = self.d_model * (self.q_dim + 2 * self.kv_dim)      # wq wk wv
        p += self.q_dim * self.d_model                          # wo
        if self.qkv_bias:
            p += self.q_dim + 2 * self.kv_dim
        if self.qk_norm:
            p += 2 * hd
        return p

    def _dense_ffn_params(self, d_ff: int) -> int:
        # SwiGLU: wi, wg: d_model x d_ff ; wo: d_ff x d_model
        return 3 * self.d_model * d_ff

    def _moe_ffn_params(self, active_only: bool) -> int:
        m = self.moe
        d_e = m.d_expert or self.d_ff
        per_expert = 3 * self.d_model * d_e
        router = self.d_model * m.num_experts
        shared = m.num_shared_experts * per_expert
        routed = (m.top_k if active_only else m.num_experts) * per_expert
        return router + shared + routed

    def _mamba_params(self) -> int:
        mc = self.mamba or MambaConfig()
        d_in = mc.expand * self.d_model
        dt_rank = mc.dt_rank or math.ceil(self.d_model / 16)
        p = self.d_model * 2 * d_in                 # in_proj (x and z)
        p += d_in * mc.d_conv                       # depthwise conv
        p += d_in * (dt_rank + 2 * mc.d_state)      # x -> (dt, B, C)
        p += dt_rank * d_in + d_in                  # dt proj + bias
        p += d_in * mc.d_state + d_in               # A_log, D
        p += d_in * self.d_model                    # out_proj
        return p

    def _xlstm_params(self) -> int:
        xc = self.xlstm or XLSTMConfig()
        # mLSTM block: up-proj 2x (pf*d), qkv (pf*d)^2-ish, gates, down-proj
        d = self.d_model
        dm = int(xc.proj_factor_mlstm * d)
        m = 2 * d * dm + 3 * dm * dm // 4 + 3 * dm + dm * d
        ds = d
        s = 4 * (ds * ds + ds * ds // 4) + int(xc.proj_factor_slstm * d) * d * 2
        n_m = sum(1 for i in range(self.num_layers)
                  if xc.pattern[i % len(xc.pattern)] == "m")
        n_s = self.num_layers - n_m
        return n_m * m + n_s * s

    def param_count(self, active_only: bool = False) -> int:
        """Total (or active) parameter count, excluding frontend stubs."""
        n = self.vocab_size * self.d_model                      # embed
        if not self.tie_embeddings:
            n += self.vocab_size * self.d_model                 # lm head
        n += self.d_model                                       # final norm
        if self.family == "ssm" and self.xlstm is not None:
            return n + self._xlstm_params()
        for i, kind in enumerate(self.layer_kinds()):
            n += 2 * self.d_model                               # 2 norms
            if kind == "a":
                n += self._attn_params()
            elif kind == "M":
                n += self._mamba_params()
            if kind == "a" or self.family == "hybrid":
                if self.is_moe_layer(i):
                    n += self._moe_ffn_params(active_only)
                else:
                    d_ff = self.d_ff
                    if self.moe is not None and i < self.moe.first_k_dense:
                        d_ff = self.moe.dense_d_ff or self.d_ff
                    if d_ff:
                        n += self._dense_ffn_params(d_ff)
        return n


# ---------------------------------------------------------------------------
# Shapes (assigned input-shape set for the LM family)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # 'train' | 'prefill' | 'decode'


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4_096, 256, "train"),
    ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    ShapeConfig("decode_32k", 32_768, 128, "decode"),
    ShapeConfig("long_500k", 524_288, 1, "decode"),
)

SHAPE_BY_NAME = {s.name: s for s in SHAPES}


# ---------------------------------------------------------------------------
# Training / LTFB / mesh configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adam"               # adam | adamw | adafactor | sgd
    lr: float = 1e-3                 # paper: Adam, initial lr 0.001
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip_norm: float = 1.0
    warmup_steps: int = 100
    schedule: str = "constant"       # constant | cosine | linear
    total_steps: int = 10_000
    # moment dtype: 'float32' for fidelity, 'bfloat16' to halve optimizer HBM
    moment_dtype: str = "float32"


@dataclass(frozen=True)
class LTFBConfig:
    """Paper §III-C — Let a Thousand Flowers Bloom."""

    num_trainers: int = 4
    interval: int = 100              # mini-batch steps between tournaments
    metric: str = "val_loss"         # lower is better
    exchange: str = "full"           # 'full' | 'generator' (GANs)
    tournament_batches: int = 4      # batches of tournament data per eval
    # PBT-style hyperparameter exploration on tournament loss ties
    perturb_hparams: bool = True
    perturb_factor: float = 1.2
    # straggler mitigation: a trainer whose partner misses the deadline
    # self-pairs (trains through) instead of blocking the round.
    straggler_timeout_s: float = 30.0


@dataclass(frozen=True)
class MeshConfig:
    # axis sizes; trainer axis only used by LTFB meshes
    pod: int = 1
    data: int = 16
    model: int = 16
    # parallelism toggles
    fsdp: bool = True                # shard params/opt over data axis (ZeRO-3)
    seq_parallel: bool = True        # shard activations' seq dim on model ax.
    remat: str = "full"              # 'none' | 'full' | 'selective'
    # beyond-paper: int8 error-feedback compression on the pod (DCN) axis
    compress_pod_grads: bool = False


@dataclass(frozen=True)
class DataConfig:
    dataset: str = "synthetic_tokens"   # synthetic_tokens | jag
    samples_per_file: int = 1_000       # paper: 1000-sample HDF5 bundles
    num_files: int = 100
    store_mode: str = "preload"         # preload | dynamic | none
    prefetch_depth: int = 2
    seed: int = 0


@dataclass(frozen=True)
class RunConfig:
    """Top-level config: one of these per experiment / launch."""

    model: ModelConfig = field(default_factory=ModelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    ltfb: Optional[LTFBConfig] = None
    mesh: MeshConfig = field(default_factory=MeshConfig)
    data: DataConfig = field(default_factory=DataConfig)
    batch_size: int = 128            # paper: mini-batch 128
    steps: int = 1_000
    eval_every: int = 100
    checkpoint_every: int = 500
    checkpoint_dir: str = "/tmp/repro_ckpt"
    seed: int = 0


def replace(cfg, **kw):
    """dataclasses.replace that tolerates nested dotted keys ('moe.top_k')."""
    direct = {k: v for k, v in kw.items() if "." not in k}
    nested = {k: v for k, v in kw.items() if "." in k}
    out = dataclasses.replace(cfg, **direct) if direct else cfg
    for k, v in nested.items():
        head, rest = k.split(".", 1)
        sub = getattr(out, head)
        out = dataclasses.replace(out, **{head: replace(sub, **{rest: v})})
    return out
