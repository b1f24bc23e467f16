"""Architecture configuration system.

Every assigned architecture is a :class:`ArchConfig` built from
:class:`LayerSpec` patterns; the paper's numerics (multiplier choice /
segmented passes) is a first-class field (``numerics``) — the
"compiler-integrated accuracy knob" at system level.

Layer patterns are expressed as ``segments``: a list of
``(repeats, [LayerSpec, ...])``.  Each segment is executed as a
scan-over-repeats with params stacked on a leading ``layers`` axis, which
keeps compile time flat in depth.  ``shared=True`` specs reuse one weight
set across all repeats (zamba2's shared attention block).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from repro.core.numerics import NumericsConfig
from repro.core.policy import Numerics


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str = "dense"          # dense | moe | ssm
    attn: str = "global"         # global | local | mla | none
    window: int = 4096           # local-attention window
    shared: bool = False         # reuse one weight set across repeats


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 1
    n_shared: int = 0            # always-on shared experts (deepseek style)
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_size: int = 128
    head_dim: int = 64           # P
    expansion: int = 2           # d_inner = expansion * d_model
    conv_width: int = 4
    chunk: int = 128


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    arch_id: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    segments: Tuple[Tuple[int, Tuple[LayerSpec, ...]], ...]
    head_dim: Optional[int] = None
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # attention details
    qk_norm: bool = False
    logit_softcap: Optional[float] = None      # gemma2 style final softcap
    attn_softcap: Optional[float] = None       # gemma2 attention softcap
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE
    # enc-dec (whisper)
    encoder_layers: int = 0
    decoder_len: int = 256        # fixed decoder length for enc-dec shapes
    enc_len: int = 1500           # encoder output length kept in serving state
    frontend: str = "none"        # none | audio_stub | vision_stub
    dense_d_ff: Optional[int] = None  # dense-layer ff when it differs from d_ff (deepseek)
    # numerics (the paper's knob): one global NumericsConfig, or a
    # NumericsPolicy mapping layer paths to configs (repro.core.policy)
    numerics: Numerics = NumericsConfig(mode="exact")
    # training/serving details
    dtype: str = "bfloat16"
    param_dtype: str = "float32"  # weight storage; bfloat16 where the published
                                  # checkpoint is bf16 or memory forces it
    optimizer: str = "adamw"      # adamw | adafactor (giants)
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    grad_accum: int = 1
    loss_batch_chunks: int = 8    # CE loss chunking (1 = off; keep chunk rows
                                  # divisible by the batch-sharding degree)
    remat: str = "full"           # full | dots | none
    # sharding behaviour (see repro/distributed/sharding.py)
    fsdp: bool = False            # shard weight 'embed' axis over data
    seq_shard_activations: bool = True  # sequence parallelism on residual
    sharding_overrides: Optional[Tuple[Tuple[str, object], ...]] = None  # rule overrides
    moment_dtype: str = "float32" # optimizer moments (bf16 for the giants)
    # long-context capability: sub-quadratic archs run long_500k
    subquadratic: bool = False

    @property
    def n_layers(self) -> int:
        return sum(r * len(p) for r, p in self.segments)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def dense_ff(self) -> int:
        return self.dense_d_ff or self.d_ff

    def layer_specs(self):
        """Flat list of LayerSpec in execution order (for reference/counting)."""
        out = []
        for repeats, pattern in self.segments:
            for _ in range(repeats):
                out.extend(pattern)
        return out

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab
        hd = self.resolved_head_dim
        total = v * d * (1 if self.tie_embeddings else 2)
        for repeats, pattern in self.segments:
            seg = 0
            for spec in pattern:
                if spec.kind == "ssm":
                    s = self.ssm
                    din = s.expansion * d
                    nheads = din // s.head_dim
                    seg_p = d * (2 * din + 2 * s.state_size + nheads) + din * d
                    seg_p += s.conv_width * din + 2 * nheads
                elif spec.kind in ("dense", "moe"):
                    if spec.attn == "mla":
                        m = self.mla
                        qd = self.n_heads * (m.nope_head_dim + m.rope_head_dim)
                        seg_p = d * m.q_lora_rank + m.q_lora_rank * qd
                        seg_p += d * (m.kv_lora_rank + m.rope_head_dim)
                        seg_p += m.kv_lora_rank * self.n_heads * (m.nope_head_dim + m.v_head_dim)
                        seg_p += self.n_heads * m.v_head_dim * d
                    elif spec.attn == "none":
                        seg_p = 0
                    else:
                        seg_p = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
                    if spec.kind == "moe":
                        e = self.moe
                        seg_p += d * e.n_experts  # router
                        seg_p += 3 * d * ff * (e.n_experts + e.n_shared)
                    else:
                        seg_p += 3 * d * ff
                else:
                    raise ValueError(spec.kind)
                seg += seg_p
            total += seg * (repeats if not all(s.shared for s in pattern) else 1)
        if self.encoder_layers:
            # whisper-style encoder blocks + cross-attention in decoder
            enc = self.encoder_layers * (4 * d * d + 3 * d * ff)
            cross = self.n_layers * 4 * d * d
            total += enc + cross
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        if not self.moe:
            return self.param_count()
        e = self.moe
        active = dataclasses.replace(
            self, moe=dataclasses.replace(e, n_experts=e.top_k))
        # param_count counts (n_experts + n_shared) expert MLPs + router;
        # replacing n_experts with top_k yields the active set. Router cost
        # (d*E) is negligible either way.
        return active.param_count()

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        def cut_pattern(pattern):
            return tuple(
                dataclasses.replace(s, window=min(s.window, 64)) for s in pattern
            )

        segs = tuple((min(r, 2), cut_pattern(p)) for r, p in self.segments)
        small_heads = max(2, min(4, self.n_heads))
        kv = max(1, min(self.n_kv_heads, small_heads))
        return dataclasses.replace(
            self,
            d_model=64,
            n_heads=small_heads,
            n_kv_heads=kv,
            head_dim=16,
            d_ff=128,
            vocab=256,
            segments=segs,
            # generous capacity: smoke tests check cache/step consistency,
            # which capacity drops would (legitimately) perturb
            moe=dataclasses.replace(self.moe, n_experts=4,
                                    top_k=min(2, self.moe.top_k),
                                    capacity_factor=4.0)
            if self.moe
            else None,
            mla=MLAConfig(kv_lora_rank=32, q_lora_rank=48, rope_head_dim=8,
                          nope_head_dim=16, v_head_dim=16)
            if self.mla
            else None,
            ssm=dataclasses.replace(self.ssm, state_size=16, head_dim=8, chunk=16)
            if self.ssm
            else None,
            mrope_sections=(2, 3, 3) if self.mrope_sections else None,  # half=8
            encoder_layers=min(self.encoder_layers, 2),
            decoder_len=32,
            enc_len=64,
            grad_accum=1,
            fsdp=False,
            seq_shard_activations=False,
            dtype="float32",   # tight numerics for CPU smoke assertions
            param_dtype="float32",
            dense_d_ff=128 if self.dense_d_ff else None,
            remat="none",
        )


_REGISTRY: dict[str, ArchConfig] = {}


def register_arch(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.arch_id] = cfg
    return cfg


def get_arch(arch_id: str) -> ArchConfig:
    # import the config modules lazily so registration happens on first use
    from repro import configs as _c  # noqa: F401

    if arch_id not in _REGISTRY:
        raise ValueError(f"unknown arch {arch_id!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[arch_id]


def list_archs() -> list[str]:
    from repro import configs as _c  # noqa: F401

    return sorted(_REGISTRY)
