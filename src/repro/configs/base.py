"""Configuration system for the ADPSGD reproduction framework.

Every assigned architecture is expressed as a ``ModelConfig`` (architecture
hyper-parameters), a ``ParallelismPlan`` (how it maps onto the production
mesh) and an ``AveragingConfig`` (the paper's technique — Algorithm 2
hyper-parameters).  Configs are plain frozen dataclasses so they hash and
can key jit caches.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration (GShard-style grouped dispatch)."""

    n_experts: int = 8
    top_k: int = 2
    d_ff_expert: int = 0          # expert hidden width
    n_shared_experts: int = 0     # DeepSeek-style always-on shared experts
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3
    first_k_dense: int = 0        # first k layers use a dense MLP instead
    d_ff_dense: int = 0           # width of those dense layers (0 -> d_ff_expert)
    moe_every: int = 1            # apply MoE every k-th layer (1 = every layer)


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0              # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention."""

    kv_lora_rank: int = 512
    q_lora_rank: int = 0          # 0 -> direct q projection (V2-Lite)
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder tower for enc-dec models (whisper).  The modality frontend
    (mel spectrogram + conv subsampling) is stubbed: ``input_specs`` feeds
    post-frontend frame embeddings of shape (B, n_frames, d_model)."""

    n_layers: int = 24
    n_heads: int = 16
    n_frames: int = 1500          # whisper: 30 s of audio @ 2x conv stride


@dataclass(frozen=True)
class VisionStubConfig:
    """VLM vision frontend stub: ``input_specs`` feeds patch embeddings
    (B, n_patches, d_model) which are prepended to the token sequence."""

    n_patches: int = 64
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)  # t/h/w split of dh/2


# ---------------------------------------------------------------------------
# ModelConfig
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"         # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""              # citation of the config's source

    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_head: int = 0               # 0 -> d_model // n_heads
    d_ff: int = 1024
    vocab_size: int = 32000
    max_seq_len: int = 4096

    # --- norm / activation ---
    norm_type: str = "rmsnorm"    # rmsnorm | layernorm | nonparametric_ln
    norm_eps: float = 1e-5
    mlp_type: str = "swiglu"      # swiglu | gelu
    tie_embeddings: bool = False

    # --- attention ---
    attention_type: str = "gqa"   # gqa | mla
    attn_qkv_bias: bool = False
    pos_type: str = "rope"        # rope | mrope | sinusoidal | learned | none
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    sliding_window: int = 0       # 0 = full attention; >0 = SWA window
    attn_logit_softcap: float = 0.0

    # --- scaling tricks (minicpm / mup-style) ---
    emb_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0

    # --- block pattern ---
    # None -> all "attn".  Otherwise a repeating pattern over layers, e.g.
    # jamba: ("mamba","mamba","mamba","attn","mamba","mamba","mamba","mamba")
    # xlstm: ("mlstm","mlstm","mlstm","mlstm","mlstm","mlstm","slstm")
    layer_pattern: Optional[Tuple[str, ...]] = None

    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    mla: Optional[MLAConfig] = None
    encoder: Optional[EncoderConfig] = None
    vision: Optional[VisionStubConfig] = None

    # --- numerics / compile ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing" # nothing(_saveable) | dots (dots_saveable)
    scan_layers: bool = True      # lax.scan over repeating layer groups
                                  # (compile time ~O(1) in depth; MaxText-style)
    act_dp_axis: str = ""         # constrain residual-stream batch dim to
                                  # this mesh axis (hillclimb A3: forces
                                  # GSPMD to keep compute batch-sharded)
    act_seq_axis: str = ""        # megatron sequence parallelism: shard the
                                  # residual seq dim over this axis between
                                  # sublayers (hillclimb C2)
    vocab_pad_multiple: int = 1   # pad embedding/vocab rows up to a multiple
                                  # (hillclimb D1: odd vocabs such as
                                  # minicpm's 122753 become shardable)

    # ------------------------------------------------------------------
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    def padded_vocab(self) -> int:
        m = max(1, self.vocab_pad_multiple)
        return ((self.vocab_size + m - 1) // m) * m

    def block_kind(self, layer_idx: int) -> str:
        if self.layer_pattern is None:
            return "attn"
        return self.layer_pattern[layer_idx % len(self.layer_pattern)]

    def layer_uses_moe(self, layer_idx: int) -> bool:
        m = self.moe
        if m is None:
            return False
        if layer_idx < m.first_k_dense:
            return False
        return (layer_idx % m.moe_every) == (m.moe_every - 1) if m.moe_every > 1 else True

    def scan_grouping(self) -> Optional[Tuple[int, int, int]]:
        """(prefix_len, period, n_groups) for lax.scan over layers, or None.
        Layers [prefix:] form n_groups repetitions of a `period`-long block
        pattern with identical parameter structure per slot."""
        if not self.scan_layers:
            return None
        import math as _math
        period = len(self.layer_pattern) if self.layer_pattern else 1
        if self.moe is not None:
            period = _math.lcm(period, max(1, self.moe.moe_every))
        prefix = self.moe.first_k_dense if self.moe else 0
        body = self.n_layers - prefix
        if body <= 0 or body % period or body // period < 2:
            return None
        return prefix, period, body // period

    def is_subquadratic(self) -> bool:
        """True if a 500k-token decode is feasible (bounded attention state)."""
        if self.layer_pattern is not None:
            kinds = set(self.layer_pattern)
            if kinds <= {"mamba", "mlstm", "slstm"}:
                return True
            # hybrid: attention layers must be sliding-window or rare-but-SWA;
            # jamba's attention is full but 1:7 — we allow it because the KV
            # cache is bounded by the few attention layers (documented).
            if "attn" in kinds and ("mamba" in kinds or "mlstm" in kinds):
                return True
        return self.sliding_window > 0

    def has_decoder(self) -> bool:
        return True  # all assigned archs have a decoder


# ---------------------------------------------------------------------------
# Parallelism / averaging / run configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelismPlan:
    """How an architecture maps onto the production mesh.

    plan = 'replica_dp' : parameters carry a leading replica axis sharded over
        the data axis (paper-faithful local-SGD workers; each worker is
        tensor-sharded over 'model').
    plan = 'fsdp'       : synchronous DP with parameter sharding over 'data'
        + tensor over 'model'; ADPSGD applies over the 'pod' axis when the
        mesh has one (DiLoCo-style hierarchical deployment).

    ``placement`` names how the execution backend lays replicas out
    (DESIGN.md §5): 'replica_ddp' keeps each replica a whole-model copy on
    its own replica-axis slot; 'replica_tp' lets one replica *span* the
    'model' mesh axis, sharding inner parameter dims with the megatron
    ``base_spec`` rules (partial-manual shard_map: manual over data/pod,
    'model' left to GSPMD).
    """

    plan: str = "replica_dp"      # replica_dp | fsdp | replica_ddp
    placement: str = "replica_ddp"  # replica_ddp | replica_tp
    shard_activations: bool = True
    remat_policy: str = "full"    # full | dots | none
    vocab_parallel_embed: bool = True   # megatron vocab-parallel embedding
                                        # (hillclimb #1; False = d-sharded)


@dataclass(frozen=True)
class AveragingConfig:
    """Paper technique hyper-parameters (Algorithm 2 + baselines)."""

    # any name registered in repro/strategies: adpsgd | cpsgd | fullsgd |
    # qsgd | decreasing | hier_adpsgd | qsgd_periodic | adacomm | dasgd | ...
    method: str = "adpsgd"
    p_init: int = 4               # initial averaging period
    p_const: int = 8              # CPSGD constant period
    k_sample_frac: float = 0.25   # K_s = frac * K  (paper: 0.25 CIFAR, 0.2 ImageNet)
    warmup_full_sync_steps: int = 0   # period-1 warmup (paper: first epoch)
    lower: float = 0.7            # S_k < lower * gamma * C2 -> p += 1
    upper: float = 1.3            # S_k > upper * gamma * C2 -> p -= 1
    p_min: int = 1
    p_max: int = 256
    sync_momentum: bool = False   # beyond-paper: average optimizer state too
    qsgd_bits: int = 8            # QSGD baseline quantization width
    # decreasing-period baseline of Wang & Joshi (paper §V-B shows harmful)
    decreasing_p0: int = 20
    decreasing_p1: int = 5
    # hierarchical (hier_adpsgd): in-pod sync period and replica-group size
    # (0 -> half the replicas form one group)
    inner_period: int = 1
    group_size: int = 0
    # AdaComm (Wang & Joshi, arXiv:1810.08313): refresh the period every
    # `adacomm_interval` steps as tau = ceil(p_init * sqrt(F_t / F_0)).
    # adacomm_mode='time' uses the paper's wall-clock form instead: blocks
    # of `adacomm_t0` *seconds* on the engine's telemetry clock, with
    # straggler rescaling (runtime/clock.py; controller AdaCommTimeController)
    adacomm_interval: int = 20
    adacomm_mode: str = "iterations"   # iterations | time
    adacomm_t0: float = 1.0            # seconds per adaptation block
    # DaSGD (arXiv:2006.00441): the averaged correction from a sync at step
    # k is applied at step k + dasgd_delay (overlap window)
    dasgd_delay: int = 2


@dataclass(frozen=True)
class InputShape:
    name: str = "train_4k"
    seq_len: int = 4096
    global_batch: int = 256
    kind: str = "train"           # train | prefill | decode


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig = field(default_factory=ModelConfig)
    parallelism: ParallelismPlan = field(default_factory=ParallelismPlan)
    averaging: AveragingConfig = field(default_factory=AveragingConfig)
    # optimizer
    optimizer: str = "momentum"   # sgd | momentum | adamw
    learning_rate: float = 0.1
    momentum: float = 0.9         # paper: 0.9
    weight_decay: float = 0.0
    lr_schedule: str = "step"     # step | cosine | wsd | constant
    lr_warmup_steps: int = 0
    lr_decay_steps: Tuple[int, ...] = ()
    lr_decay_factor: float = 0.1
    total_steps: int = 1000
    seed: int = 0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Any] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> RunConfig:
    if name not in _REGISTRY:
        # late import so that `configs/<arch>.py` modules self-register
        import importlib
        mod = name.replace("-", "_").replace(".", "_")
        try:
            importlib.import_module(f"repro.configs.{mod}")
        except ImportError as exc:
            raise KeyError(
                f"unknown config '{name}'; available: {sorted(_REGISTRY)}"
            ) from exc
    return _REGISTRY[name]()


def available_configs() -> Sequence[str]:
    import importlib
    import pkgutil
    import repro.configs as pkg
    for m in pkgutil.iter_modules(pkg.__path__):
        if m.name not in ("base",):
            importlib.import_module(f"repro.configs.{m.name}")
    return sorted(_REGISTRY)


def reduced(model: ModelConfig, **overrides) -> ModelConfig:
    """Smoke-test variant: same family/block pattern, tiny dims."""
    changes: Dict[str, Any] = dict(
        n_layers=2,
        d_model=min(model.d_model, 128),
        n_heads=4,
        n_kv_heads=min(model.n_kv_heads, 4) or 4,
        d_head=32,
        d_ff=min(model.d_ff, 256) if model.d_ff else 0,
        vocab_size=min(model.vocab_size, 512),
        max_seq_len=256,
        param_dtype="float32",
        compute_dtype="float32",
        remat=False,
        scan_layers=False,
    )
    if model.moe is not None:
        changes["moe"] = dataclasses.replace(
            model.moe,
            n_experts=min(model.moe.n_experts, 4),
            top_k=min(model.moe.top_k, 2),
            d_ff_expert=64,
            d_ff_dense=64 if model.moe.d_ff_dense else 0,
            first_k_dense=min(model.moe.first_k_dense, 1),
        )
    if model.mla is not None:
        changes["mla"] = dataclasses.replace(
            model.mla, kv_lora_rank=64, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32)
        changes["d_head"] = 0
    if model.encoder is not None:
        changes["encoder"] = dataclasses.replace(
            model.encoder, n_layers=2, n_heads=4, n_frames=32)
    if model.vision is not None:
        changes["vision"] = dataclasses.replace(
            model.vision, n_patches=8, mrope_sections=(4, 6, 6))
    if model.layer_pattern is not None and len(model.layer_pattern) > 2:
        # keep one of each kind in a 2-layer smoke model
        kinds = list(dict.fromkeys(model.layer_pattern))
        changes["layer_pattern"] = tuple(kinds[:2]) if len(kinds) >= 2 else model.layer_pattern
    changes.update(overrides)
    return dataclasses.replace(model, **changes)
