"""Architecture config schema shared by the whole model zoo.

One ``ArchConfig`` instance fully determines a model: the 10 assigned
architectures each get a module in ``repro.configs`` exporting
``CONFIG`` (the exact published shape, cited) and ``smoke()`` (a reduced
same-family variant for CPU tests: <=2 layers, d_model<=512, <=4
experts).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 1
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    dispatch: str = "einsum"  # "einsum" (one-hot matmul) | "sort" (gather/scatter)
    # tokens per dispatch group: the [Tg, E, C] dispatch/combine tensors
    # scale LINEARLY with this (volume ~ T*Tg*top_k*capacity_factor), so
    # smaller groups cut MoE memory traffic at the cost of tighter
    # per-group capacity (more drops under load imbalance).  §Perf H2d.
    group_size: int = 512
    # DeepSeek-V3 routing (the held dispatch): "sigmoid" scores, top-k
    # chosen on score + a per-expert bias that takes no part in the
    # weights, the chosen scores renormalised and scaled by routed_scaling
    scoring: str = "softmax"  # softmax | sigmoid
    selection_bias: bool = False
    routed_scaling: float = 1.0
    # expert parallelism: this device holds experts [expert_offset,
    # expert_offset + experts_held) of n_experts; the router still scores
    # all n_experts.  experts_held > 0 selects the dropless "held"
    # dispatch (sort by expert, grouped products), which computes only
    # the held experts' share of the layer's output
    experts_held: int = 0
    expert_offset: int = 0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model/16)
    chunk: int = 256  # sequence chunk for the chunked associative scan
    # unroll the chunk loop in Python (cost-analysis variants only: XLA
    # counts while-loop bodies once, so the dry-run unrolls instead)
    unroll: bool = False
    # use the Pallas selective-scan kernel (VMEM-resident state; HBM
    # traffic = kernel I/O) instead of the jnp chunked associative scan
    use_kernel: bool = False
    # measurement-only (kernel_adjust): replace the scan with a cheap
    # [B,S,di]-level consumer of the same inputs, so "model minus scan"
    # HLO bytes can be measured in cost-analysis currency
    bypass_scan: bool = False


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Layer pattern for hybrid stacks, as (pattern, which-is-attention).

    ``pattern_len`` layers form a scanned block; ``attn_slots`` are the
    in-block indices that use attention (the rest use the recurrent /
    local mixer).  ``tail_layers`` handles n_layers % pattern_len.
    """

    pattern_len: int = 1
    attn_slots: Tuple[int, ...] = ()
    lru_width: int = 0  # RG-LRU recurrence width (0 -> d_model)
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    rope_theta: float = 10000.0
    use_rope: bool = True
    qkv_bias: bool = False
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    mlp: str = "swiglu"  # swiglu | gelu
    # attention regime: full | causal | window | chunk (chunk => iRoPE-style
    # local layers; global layers configured via global_every)
    attn_kind: str = "causal"
    window: int = 0
    global_every: int = 0  # every Nth layer is global full-causal (llama4)
    q_block: int = 1024
    q_unroll: bool = False  # unroll query-block loop (dry-run cost analysis)
    # attention implementation: "xla" (blocked exact softmax, used by the
    # dry-run so HLO cost analysis sees the real op mix) or "flash" (the
    # Pallas online-softmax kernel; interpret-mode on CPU, Mosaic on TPU)
    attn_impl: str = "xla"
    moe: MoEConfig = MoEConfig()
    ssm: SSMConfig = SSMConfig()
    hybrid: HybridConfig = HybridConfig()
    # modality frontends (stub carve-out)
    frontend_dim: int = 0  # audio frame / vision patch embedding dim
    n_patches: int = 0  # vlm: image-prefix length in train/prefill shapes
    tied_embeddings: bool = True
    norm_eps: float = 1e-6
    # multi-head latent attention (DeepSeek-V2, no query compression):
    # kv_lora_rank > 0 replaces the attention slot by MLA
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_k_dense: int = 0  # leading dense-MLP layers before the MoE stack
    source: str = ""  # citation

    @property
    def dt_rank(self) -> int:
        return self.ssm.dt_rank or -(-self.d_model // 16)

    @property
    def d_inner(self) -> int:
        return self.ssm.expand * self.d_model

    @property
    def lru_width(self) -> int:
        return self.hybrid.lru_width or self.d_model

    def supports_decode(self) -> bool:
        return self.arch_type != "audio"

    def subquadratic(self) -> bool:
        """Eligible for long_500k per the assignment rules."""
        return (
            self.arch_type in ("ssm", "hybrid")
            or self.attn_kind in ("window", "chunk")
        )


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def param_count(cfg: ArchConfig) -> int:
    """Analytic parameter count (embedding + blocks), for MODEL_FLOPS."""
    d, L = cfg.d_model, cfg.n_layers
    emb = cfg.vocab * d * (1 if cfg.tied_embeddings else 2)
    attn = d * cfg.n_heads * cfg.head_dim * 2 + d * cfg.n_kv_heads * cfg.head_dim * 2
    if cfg.mlp == "swiglu":
        mlp = 3 * d * cfg.d_ff
    else:
        mlp = 2 * d * cfg.d_ff
    per_layer = attn + mlp
    if cfg.arch_type == "moe":
        e = cfg.moe
        mlp_moe = 3 * d * e.d_ff_expert * (e.n_experts + e.n_shared_experts)
        router = d * e.n_experts
        per_layer = attn + mlp_moe + router
    if cfg.arch_type == "ssm":
        di, ds, dtr = cfg.d_inner, cfg.ssm.d_state, cfg.dt_rank
        per_layer = (
            d * 2 * di  # in_proj
            + di * cfg.ssm.d_conv  # conv
            + di * (dtr + 2 * ds)  # x_proj
            + dtr * di  # dt_proj
            + di * ds  # A_log
            + di  # D
            + di * d  # out_proj
        )
    if cfg.arch_type == "hybrid":
        w = cfg.lru_width
        # RG-LRU block: in/out proj + depthwise conv + block-diag gates
        rec = d * 2 * w + w * cfg.hybrid.conv_width + 2 * w * (w // 8) + w * d + 2 * w
        n_attn = sum(
            1
            for i in range(cfg.n_layers)
            if i % cfg.hybrid.pattern_len in cfg.hybrid.attn_slots
        )
        n_rec = cfg.n_layers - n_attn
        return emb + n_attn * (attn + mlp) + n_rec * (rec + mlp)
    return emb + L * per_layer


def active_param_count(cfg: ArchConfig) -> int:
    """Activated params per token (MoE: top_k + shared experts only)."""
    if cfg.arch_type != "moe":
        return param_count(cfg)
    d, L, e = cfg.d_model, cfg.n_layers, cfg.moe
    emb = cfg.vocab * d * (1 if cfg.tied_embeddings else 2)
    attn = d * cfg.n_heads * cfg.head_dim * 2 + d * cfg.n_kv_heads * cfg.head_dim * 2
    mlp_act = 3 * d * e.d_ff_expert * (e.top_k + e.n_shared_experts)
    router = d * e.n_experts
    return emb + L * (attn + mlp_act + router)
