"""Config system for WI-JAX.

Three layers of config:
  * ModelConfig     — architecture hyperparameters (one per assigned arch).
  * ShapeConfig     — the assigned input-shape cells (train_4k, prefill_32k, ...).
  * ParallelConfig  — mesh / sharding / remat / microbatching knobs.
  * RunConfig       — bundles the above plus runtime (WI) options.

Everything is a frozen dataclass so configs hash and can key jit caches.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-layer / block structure
# ---------------------------------------------------------------------------
# A model is a stack of *groups*; each group repeats a *pattern* of blocks
# R times via lax.scan.  A block is a named kind:
#   'attn'        self-attention (+ mlp handled separately in pattern)
#   'mlp'         gated FFN
#   'moe'         mixture-of-experts FFN
#   'ssd'         Mamba-2 SSD block (includes its own in/out projections)
#   'rglru'       Griffin RG-LRU recurrent block
#   'cross_attn'  decoder cross-attention (enc-dec only)
# Patterns are tuples of tuples: e.g. (('attn', 'mlp'),) repeated R times, or
# gemma-2's (('attn_local', 'mlp'), ('attn_global', 'mlp')) repeated L/2 times.


@dataclass(frozen=True)
class AttnConfig:
    causal: bool = True
    window: Optional[int] = None          # sliding-window size (None = global)
    logit_softcap: Optional[float] = None  # gemma-2 style attn softcap
    query_scale: Optional[float] = None    # override 1/sqrt(head_dim)


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_dtype: str = "float32"


@dataclass(frozen=True)
class SSDConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk_size: int = 256
    n_groups: int = 1


@dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0           # 0 => d_model
    conv_width: int = 4
    block_width: int = 0         # diagonal-block proj width (0 => heads of 256? unused)
    c: float = 8.0               # Griffin's fixed constant


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # block pattern: tuple of block-kind tuples; repeated scan groups derived in
    # models/model.py.  Default: uniform ('attn','mlp') stack.
    pattern: Tuple[Tuple[str, ...], ...] = (("attn", "mlp"),)
    attn: AttnConfig = AttnConfig()
    attn_local: Optional[AttnConfig] = None   # for *_local blocks
    moe: Optional[MoEConfig] = None
    ssd: Optional[SSDConfig] = None
    rglru: Optional[RGLRUConfig] = None
    # enc-dec (whisper): encoder stack config
    enc_layers: int = 0
    enc_seq_ratio: int = 1        # encoder frames per decoder token (shape split)
    # vlm: number of leading positions fed by the (stubbed) vision frontend
    n_vision_tokens: int = 0
    # misc
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-6
    final_logit_softcap: Optional[float] = None
    tie_embeddings: bool = True
    emb_scale_by_sqrt_dim: bool = False     # gemma family
    post_block_norm: bool = False            # gemma-2 sandwich norms
    act_dtype: str = "bfloat16"

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows padded to a multiple of 256 so the vocab dim
        shards evenly on the 16-wide model axis (MaxText-style padding; the
        logical vocab is unchanged — padded logits are masked to -inf)."""
        return (self.vocab_size + 255) // 256 * 256

    @property
    def sub_quadratic(self) -> bool:
        """True if every block avoids global quadratic attention."""
        kinds = [k for pat in self.pattern for k in pat]
        for k in kinds:
            if k == "attn" and self.attn.window is None:
                return False
            if k == "cross_attn":
                return False
        return True

    @property
    def n_params(self) -> int:
        """Analytical parameter count (matches abstract_params; see tests)."""
        from repro.models.model import count_params  # local import, no cycle
        return count_params(self)

    @property
    def n_active_params(self) -> int:
        from repro.models.model import count_params
        return count_params(self, active_only=True)


@dataclass(frozen=True)
class ShapeConfig:
    name: str                      # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                      # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class ParallelConfig:
    # mesh axis sizes; pod=1 means single-pod
    pod: int = 1
    data: int = 16
    model: int = 16
    # sharding strategy
    fsdp: bool = True              # shard params over the data axis too (ZeRO-3)
    seq_shard_acts: bool = True    # sequence-shard saved activations (SP)
    # training memory knobs
    microbatch: int = 0            # 0 => no accumulation (single microbatch)
    grad_accum_dtype: str = "float32"
    opt_state_dtype: str = "float32"
    remat: str = "full"            # full | dots | none
    # hillclimb levers (see EXPERIMENTS.md §Perf)
    gather_barrier: bool = False   # pin FSDP weight gathers at loop-body top
    moe_cap_shard: bool = False    # shard MoE dispatch buffers over data
    # attention impl: dense | flash (pure-JAX chunked)
    attn_impl: str = "flash"
    flash_q_chunk: int = 512
    flash_kv_chunk: int = 512
    flash_causal_skip: bool = False   # balanced triangular schedule (hillclimb opt)
    # loss computation chunk (tokens per step of the chunked x-ent)
    loss_chunk: int = 0            # 0 => unchunked
    # gradient compression: none | int8
    grad_compression: str = "none"
    # collective schedule for the DP gradient reduction under shard_map paths
    dp_collective: str = "all_reduce"  # all_reduce | reduce_scatter

    def __post_init__(self):
        if self.attn_impl not in ("dense", "flash"):
            raise NotImplementedError(
                f"attn_impl={self.attn_impl!r}: only 'dense' and 'flash' "
                "are implemented")

    def axis_names(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.pod > 1 else ("data", "model")

    def mesh_shape(self) -> Tuple[int, ...]:
        return ((self.pod, self.data, self.model) if self.pod > 1
                else (self.data, self.model))

    @property
    def dp_axes(self):
        return ("pod", "data") if self.pod > 1 else ("data",)

    @property
    def n_devices(self):
        return self.pod * self.data * self.model


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    parallel: ParallelConfig = ParallelConfig()
    seed: int = 0
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1000
    optimizer: str = "adamw"       # adamw | adafactor
    z_loss: float = 0.0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


def mconfig_replace(cfg: ModelConfig, **kw) -> ModelConfig:
    return dataclasses.replace(cfg, **kw)


def pconfig_replace(cfg: ParallelConfig, **kw) -> ParallelConfig:
    return dataclasses.replace(cfg, **kw)
