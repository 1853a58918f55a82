"""Architecture and input-shape configuration system.

Every assigned architecture is expressed as an :class:`ArchConfig` and
registered in :data:`ARCH_REGISTRY` under its public ``--arch`` id.  The
four assigned input shapes live in :data:`SHAPE_REGISTRY`.

Configs are frozen dataclasses so they can be hashed into jit static
arguments and compared in tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    num_experts: int
    top_k: int
    d_ff_expert: int          # hidden dim of each expert FFN
    shared_expert: bool = False
    shared_d_ff: int = 0
    capacity_factor: float = 1.25
    router_impl: str = "dispatch"   # "dispatch" (capacity one-hot) | "dense"
    aux_loss_weight: float = 0.01
    router_group: int = 4096        # tokens per routing group for dispatch


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 style SSD (state space duality) block configuration."""

    state_dim: int            # N, per-head SSM state size
    head_dim: int = 64        # P, channels per SSM head
    expand: int = 2           # d_inner = expand * d_model
    n_groups: int = 1         # B/C groups (like GQA for SSM)
    conv_width: int = 4       # depthwise causal conv width
    chunk: int = 256          # SSD chunk length


@dataclass(frozen=True)
class ArchConfig:
    """One selectable architecture (``--arch <name>``)."""

    name: str
    family: str               # dense | moe | ssm | hybrid | encdec | vlm | lstm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    citation: str = ""

    head_dim: int = 0         # 0 -> derived as d_model // n_heads
    rope_theta: float = 10_000.0
    norm: str = "rmsnorm"     # rmsnorm | layernorm
    act: str = "swiglu"       # swiglu | gelu
    use_bias: bool = False
    tie_embeddings: bool = True

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None

    # Sliding-window attention. 0 = full attention.  For pure full-attention
    # architectures the ``long_500k`` shape is run with
    # ``window_for_long`` > 0 as a documented variant (DESIGN.md).
    window: int = 0
    window_for_long: int = 8192
    # layers (by index mod pattern) that keep global attention when a window
    # is active; e.g. hymba keeps first/middle/last global.
    global_attn_layers: tuple = ()

    # encoder-decoder (whisper): encoder layers; log-mel bins into the
    # conv front end; encoder positions (after the front end's stride 2,
    # so 2 x n_audio_ctx mel frames: 30 s at a 10 ms hop); learned decoder
    # positions.  The encoder always takes its whole window; a shape's
    # seq_len is the decoder's tokens, at most n_text_ctx.
    n_enc_layers: int = 0
    n_mels: int = 0
    n_audio_ctx: int = 0
    n_text_ctx: int = 0

    # vlm: number of prefix patch-embedding positions for a given seq_len is
    # seq_len // vlm_patch_fraction_denom.
    vlm_patch_frac: float = 0.25

    # modality frontend stub: 'none' | 'vision' (precomputed patch
    # embeddings); whisper's audio front end is a layer of the model.
    frontend: str = "none"

    # lstm acoustic model (the paper's own architecture)
    lstm_hidden: int = 0      # per-direction hidden size
    lstm_bottleneck: int = 0
    input_dim: int = 0        # acoustic feature dim (paper: 260)
    # Pallas LSTM kernel knobs (repro.kernels.lstm_cell): batch tile of
    # the (B//bB, T) grid; 0 -> auto-picked from the VMEM budget, which
    # the kernels also pass to Mosaic (plus headroom) as the VMEM limit.
    lstm_block_b: int = 0
    lstm_vmem_budget_mb: int = 96
    # training-forward residual stash precision ('float32' | 'bfloat16'):
    # bf16 halves the ~55MB/direction gate/cell stash at ~1e-2 normalized
    # gradient error (see kernels/lstm_cell.py 'Residual stashing').
    lstm_stash_dtype: str = "float32"
    # sequence-chunked recompute for long utterances: 0 = per-step stash,
    # K > 0 = stash only (h, c) chunk-entry carries every K frames and
    # rebuild gate residuals in VMEM in the backward (O(T/K) stash HBM at
    # the cost of one extra forward pass), -1 = auto-tune (block_b, K)
    # jointly from the VMEM budget (kernels/lstm_cell.py 'Sequence-chunked
    # recompute', docs/kernels.md).
    lstm_seq_chunk: int = 0

    # distribution defaults (see repro/core/strategies.py and DESIGN.md)
    train_strategy: str = "sd_psgd"   # sc_psgd | sd_psgd | ad_psgd | bmuf | hring
    n_learners: int = 16
    fsdp: bool = False        # shard params over the data axis (SC-PSGD only)
    expert_axis: str = ""     # mesh axis for expert parallelism ("data" or "")

    # ---- communication substrate (repro/core/transport.py; the full
    # strategy × topology × wire matrix is in docs/strategies.md) ----
    # mixing topology override; "" = the strategy's default
    # (uniform | ring | hierarchical | exp | none)
    comm_topology: str = ""
    # wire codec for payloads that cross the wire; "" = strategy default
    # (f32 | bf16 | int8 | topk)
    comm_wire: str = ""
    # hierarchical only: codec of the intra-pod allreduce ("" = f32;
    # f32 | bf16 | int8 — topk is gossip-only); the inter-pod ring uses
    # comm_wire — e.g. bf16 intra + topk inter
    comm_intra_wire: str = ""
    # chunked collectives: split payloads into buckets of this many MB so
    # XLA can interleave mixing with backward compute (0 = fused payload)
    comm_bucket_mb: int = 0
    # hierarchical topology: learners per pod (must divide n_learners)
    comm_pod_size: int = 1
    # topk wire: fraction of entries shipped per bucket
    comm_topk_frac: float = 0.01
    # elastic (fault-tolerant) mixing only: staleness damping λ — a
    # learner whose params are s steps behind mixes with confidence
    # 1/(1 + λ·s) (mixing.staleness_damped; docs/fault_tolerance.md).
    # 0 disables damping; ignored outside --fault-* runs.
    comm_staleness_lambda: float = 0.0

    # ---- CTC decode / recognition quality (repro/decode;
    # docs/decoding.md; --beam-* flags of evaluate.py and serve.py) ----
    # prefix-beam width of the eval/serve decoder (1 = greedy best-path)
    beam_width: int = 8
    # prefix-score merge: 'max' (Viterbi — beam=1 provably equals greedy
    # best-path) | 'sum' (classic log-semiring prefix beam search)
    beam_semiring: str = "max"
    # length-normalization alpha for the final hypothesis ranking
    # (score / max(len, 1)**alpha; 0 = raw log-prob)
    beam_len_norm: float = 0.0
    # per-frame top-C vocab pruning of the beam candidate grid (0 = off:
    # full beam x V).  Exact whenever C covers the frame's extend support
    # (docs/decoding.md §Top-C); candidate VMEM scales with C, not V
    beam_topc: int = 0
    # decode-step attention: '' (follow the launcher's --kernel-impl) |
    # 'jax' | 'pallas' (repro.kernels.decode_attention streaming kernel)
    attn_decode_impl: str = ""
    # ---- serving KV-cache layout (serve.py --cache; docs/serving.md
    # §KV paging) ----
    # 'dense' (per-slot max_len rows) | 'paged' (shared page pool with
    # prompt-prefix sharing + COW; attention-only decoder families)
    cache_mode: str = "dense"
    # cache positions per physical KV page under cache_mode='paged'
    # (serve.py --page-size overrides; must divide the serve max_len)
    page_size: int = 16

    # which shapes this arch supports (see DESIGN.md skip notes)
    skip_shapes: tuple = ()

    # numerics
    param_dtype: str = "bfloat16"
    remat: bool = True
    microbatches: int = 4     # gradient-accumulation microbatches for train

    # ---- beyond-paper performance knobs (EXPERIMENTS.md §Perf) ----
    # 'replicated' (baseline: attention weights+compute replicated over the
    # model axis) | 'seq' (sequence-parallel attention: head_dim-sharded
    # projections, q-chunk positions sharded over 'model')
    attn_sharding: str = "replicated"
    # fuse the dense-MoE combine into one (experts, ff) contraction instead
    # of materializing per-expert outputs (kills the giant psum)
    moe_dense_fused: bool = False

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    # ------------------------------------------------------------------
    @property
    def is_subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid") or self.window > 0

    @property
    def supports_decode(self) -> bool:
        return self.family != "lstm"   # frame classifier has no decode loop

    def supports_shape(self, shape_name: str) -> bool:
        return shape_name not in self.skip_shapes

    # ------------------------------------------------------------------
    def optimized(self) -> "ArchConfig":
        """§Perf overlay: the beyond-paper optimized variant of this arch
        (sequence-parallel attention, fused dense-MoE combine, smaller
        routing groups, fewer grad-accumulation round-trips)."""
        changes = dict(attn_sharding="seq", moe_dense_fused=True,
                       microbatches=max(2, self.microbatches // 4))
        if self.moe is not None and self.moe.router_impl == "dispatch":
            changes["moe"] = replace(self.moe, router_group=1024)
        return replace(self, **changes)

    def reduced(self) -> "ArchConfig":
        """A smoke-test variant of the same family: <=2 layers, d_model<=256,
        <=4 experts, small vocab.  Used by per-arch CPU smoke tests."""
        d = min(self.d_model, 256)
        heads = min(self.n_heads, 4) or self.n_heads
        kv = min(self.n_kv_heads, 2) or self.n_kv_heads
        hd = max(d // max(heads, 1), 8) if heads else 0
        changes = dict(
            n_layers=2,
            d_model=d,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab=min(self.vocab, 512),
            n_learners=2,
            microbatches=1,
            window=min(self.window, 64) if self.window else 0,
        )
        if self.moe is not None:
            changes["moe"] = replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2),
                d_ff_expert=min(self.moe.d_ff_expert, 128),
                shared_d_ff=min(self.moe.shared_d_ff, 128),
                router_group=64,
            )
        if self.ssm is not None:
            changes["ssm"] = replace(
                self.ssm,
                state_dim=min(self.ssm.state_dim, 16),
                head_dim=16,
                chunk=16,
            )
        if self.n_enc_layers:
            changes.update(n_enc_layers=1, n_mels=min(self.n_mels, 16),
                           n_audio_ctx=min(self.n_audio_ctx, 32),
                           n_text_ctx=min(self.n_text_ctx, 64))
        if self.lstm_hidden:
            changes["lstm_hidden"] = 64
            changes["lstm_bottleneck"] = 32
        return replace(self, **changes)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned (seq_len, global_batch) workload shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPE_REGISTRY = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# populated by repro.configs (one module per assigned architecture)
ARCH_REGISTRY: dict = {}


def register(cfg: ArchConfig) -> ArchConfig:
    ARCH_REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    import repro.configs  # noqa: F401  (ensures registry is populated)

    try:
        return ARCH_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown arch {name!r}; available: {sorted(ARCH_REGISTRY)}"
        ) from None


def get_shape(name: str) -> ShapeConfig:
    try:
        return SHAPE_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown shape {name!r}; available: {sorted(SHAPE_REGISTRY)}"
        ) from None
