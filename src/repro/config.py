"""Configuration system for the repro framework.

Every architecture is described by a ``ModelConfig``; every benchmark
input shape by a ``ShapeConfig``.  Configs are plain frozen dataclasses so
they hash, compare, and print cleanly, and so jit caches key on them.

Layer kinds (one token-mixing module + one FFN per layer, except noted):
  "attn"      global causal self-attention + FFN
  "local"     sliding-window causal self-attention + FFN
  "cross"     (gated) cross-attention to static source embeddings + FFN
  "selfcross" self-attention + cross-attention + FFN  (whisper decoder)
  "rglru"     RG-LRU recurrent block + FFN            (recurrentgemma)
  "ssd"       Mamba-2 SSD block (no separate FFN)
  "mla"       multi-head latent attention (DeepSeek-V2/V3) + FFN
  "mla_dense" multi-head latent attention + a dense FFN of ``d_ff``, in
              a model whose other layers are MoE

A model's layer stack is ``lead_pattern``, then ``block_pattern``
repeated ``n_blocks`` times, then ``remainder_pattern``; the repeated
part is executed with ``jax.lax.scan`` over stacked parameters so HLO
size (and compile time) is O(len(block_pattern)), not O(n_layers).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

LAYER_KINDS = ("attn", "local", "cross", "selfcross", "rglru", "ssd",
               "mla", "mla_dense")
MLA_KINDS = ("mla", "mla_dense")
# layer kinds whose FFN is the model's MoE when it has one
_MOE_FFN_KINDS = ("attn", "local", "cross", "selfcross", "rglru", "mla")


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN configuration."""
    n_experts: int
    top_k: int
    d_ff_expert: int
    # qwen2-moe style always-on shared experts (computed densely).
    n_shared_experts: int = 0
    d_ff_shared: int = 0
    # arctic style parallel dense-FFN residual (computed densely).
    d_ff_dense_residual: int = 0
    capacity_factor: float = 1.25
    # token group size for GShard-style einsum dispatch (memory control)
    group_size: int = 2048
    router_aux_coef: float = 0.01
    # the shared expert's output is scaled by a learned sigmoid gate
    # (qwen2-moe); DeepSeek's shared expert is added as it is
    shared_gated: bool = True
    # "softmax": top-k of the softmax, renormalised.  "sigmoid"
    # (DeepSeek-V3 noaux_tc): sigmoid scores; experts are chosen on the
    # scores plus a per-expert correction bias, inside the ``topk_group``
    # of ``n_group`` groups whose two best biased scores sum highest;
    # the chosen experts' unbiased scores, normalised to sum 1, times
    # ``routed_scaling`` weight them
    scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0
    # expert parallelism's share: the layer holds ``n_held`` of the
    # router's ``n_experts`` routed experts, ``first_held`` onwards, and
    # computes their part of the result (0 = every expert is held)
    n_held: int = 0
    first_held: int = 0

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router scoring {self.scoring!r}")
        if self.n_experts % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError(f"{self.n_experts} experts in {self.n_group} "
                             f"groups, {self.topk_group} kept")
        if not (0 <= self.first_held
                and self.first_held + self.held <= self.n_experts):
            raise ValueError(f"experts {self.first_held}.."
                             f"{self.first_held + self.held - 1} held of "
                             f"{self.n_experts}")

    @property
    def held(self) -> int:
        """Routed experts this layer holds and computes."""
        return self.n_held or self.n_experts

    @property
    def holds_all(self) -> bool:
        return self.held == self.n_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3).  Queries go through a
    ``q_lora_rank`` bottleneck; keys and values are expanded per head from
    one ``kv_lora_rank`` latent, and a ``qk_rope_head_dim`` rotary key is
    shared by all heads.  The decode cache holds the latent and the
    rotary key: ``kv_lora_rank + qk_rope_head_dim`` values a position."""
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (``rope_scaling`` of type "yarn")."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD configuration."""
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    chunk: int = 64
    conv_width: int = 4

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class RGLRUConfig:
    """RG-LRU (Griffin / RecurrentGemma) recurrent block configuration."""
    lru_width: int
    conv_width: int = 4
    # c exponent in a_t = a^(c * r_t)
    c: float = 8.0


@dataclass(frozen=True)
class EncoderConfig:
    """Auxiliary encoder (whisper audio encoder).  Consumes precomputed
    frame embeddings from the stubbed conv/mel frontend."""
    n_layers: int
    source_len: int  # number of frames/patches produced by the frontend


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                      # dense|moe|ssm|hybrid|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None      # default: d_model // n_heads
    block_pattern: Tuple[str, ...] = ("attn",)
    remainder_pattern: Tuple[str, ...] = ()
    lead_pattern: Tuple[str, ...] = ()  # unscanned layers ahead of the blocks
    window: int = 4096                  # sliding window for "local"
    attn_softcap: float = 0.0           # gemma2
    logit_softcap: float = 0.0          # gemma2
    use_post_norm: bool = False         # gemma2 post-block norms
    act: str = "silu"                   # silu (swiglu) | gelu (geglu)
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    rope_scaling: Optional[YarnConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    encoder: Optional[EncoderConfig] = None
    cross_source_len: int = 0           # image/audio token count for "cross"
    # which input shapes this arch supports ("train","prefill","decode","long")
    supports_long_context: bool = False
    long_context_note: str = ""
    source: str = ""                    # citation for the config

    def __post_init__(self):
        n_rem = len(self.remainder_pattern) + len(self.lead_pattern)
        n_pat = len(self.block_pattern)
        if self.n_layers < n_rem or (self.n_layers - n_rem) % n_pat != 0:
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} incompatible with "
                f"pattern of {n_pat} + lead and remainder of {n_rem}")
        for k in self.layer_kinds:
            if k not in LAYER_KINDS:
                raise ValueError(f"unknown layer kind {k!r}")
            if k in MLA_KINDS and self.mla is None:
                raise ValueError(f"{self.name}: layer kind {k!r} needs an "
                                 f"MLAConfig")

    @property
    def n_blocks(self) -> int:
        return ((self.n_layers - len(self.remainder_pattern)
                 - len(self.lead_pattern)) // len(self.block_pattern))

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The kind of each layer, first to last."""
        return (self.lead_pattern + self.block_pattern * self.n_blocks
                + self.remainder_pattern)

    @property
    def n_moe_layers(self) -> int:
        """Layers whose FFN is the MoE."""
        if self.moe is None:
            return 0
        return sum(k in _MOE_FFN_KINDS for k in self.layer_kinds)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def has_cross(self) -> bool:
        return any(k in ("cross", "selfcross") for k in self.layer_kinds)

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + all layers)."""
        d, hd = self.d_model, self.resolved_head_dim
        qkv = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d

        def ffn_params(dense: bool = False) -> int:
            if self.moe is not None and not dense:
                m = self.moe
                p = d * m.n_experts  # router
                p += m.held * 3 * d * m.d_ff_expert
                if m.n_shared_experts:
                    p += 3 * d * m.d_ff_shared
                if m.d_ff_dense_residual:
                    p += 3 * d * m.d_ff_dense_residual
                return p
            return 3 * d * self.d_ff

        def mla_params() -> int:
            a, H = self.mla, self.n_heads
            return (d * a.q_lora_rank + a.q_lora_rank * H * a.qk_head_dim
                    + d * a.latent_dim
                    + a.kv_lora_rank * H * (a.qk_nope_head_dim + a.v_head_dim)
                    + H * a.v_head_dim * d + a.q_lora_rank + a.kv_lora_rank)

        def layer_params(kind: str) -> int:
            if kind in MLA_KINDS:
                return mla_params() + ffn_params(kind == "mla_dense") + 2 * d
            if kind in ("attn", "local"):
                return qkv + ffn_params() + 2 * d
            if kind == "cross":
                return qkv + ffn_params() + 3 * d + 2
            if kind == "selfcross":
                return 2 * qkv + ffn_params() + 3 * d
            if kind == "rglru":
                r = self.rglru
                w = r.lru_width
                return (2 * d * w + r.conv_width * w + 2 * w * w + w
                        + w * d + ffn_params() + 2 * d)
            if kind == "ssd":
                s = self.ssm
                di = s.d_inner(d)
                h = s.n_heads(d)
                proj_in = d * (2 * di + 2 * s.d_state + h)
                return (proj_in + s.conv_width * (di + 2 * s.d_state)
                        + 3 * h + di + di * d + d)
            raise ValueError(kind)

        total = self.vocab * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab * d
        total += d  # final norm
        for k in self.block_pattern:
            total += layer_params(k) * self.n_blocks
        for k in self.lead_pattern + self.remainder_pattern:
            total += layer_params(k)
        if self.encoder is not None:
            enc_layer = 2 * qkv // 2 + 3 * d * self.d_ff // 3 * 0  # placeholder
            enc_layer = qkv + 3 * d * self.d_ff + 2 * d
            total += self.encoder.n_layers * enc_layer + d
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only top-k experts)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        inactive_per_layer = (m.n_experts - m.top_k) * 3 * self.d_model * m.d_ff_expert
        return self.param_count() - inactive_per_layer * self.n_layers


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}

_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if not _REGISTRY:
        _load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list:
    if not _REGISTRY:
        _load_all()
    return sorted(_REGISTRY)


def _load_all():
    # importing the package registers every config module
    from repro import configs as _  # noqa: F401


def chip_share(cfg: ModelConfig, *, n_layers: int = 0, lead_layers: int = 0,
               experts_held: int = 0, vocab: int = 0) -> ModelConfig:
    """One chip's share of a model, every width kept.

    ``lead_layers`` > 0 keeps that many of the leading layers and
    ``n_layers`` > 0 cuts the whole stack to that many (the leading
    layers among them): the layers left out would be further pipeline
    stages.  ``experts_held`` > 0 holds that many routed experts of each
    MoE layer, the first of them; the router keeps every output.
    ``vocab`` > 0 serves that slice of the vocabulary.  0 keeps what the
    configuration has."""
    kw = {}
    if lead_layers:
        kw.update(lead_pattern=cfg.lead_pattern[:lead_layers],
                  n_layers=cfg.n_layers - len(cfg.lead_pattern) + lead_layers)
    if n_layers:
        kw["n_layers"] = n_layers
    if experts_held:
        if cfg.moe is None:
            raise ValueError(f"{cfg.name} has no routed experts to hold")
        kw["moe"] = dataclasses.replace(cfg.moe, n_held=experts_held,
                                        first_held=0)
    if vocab:
        kw["vocab"] = vocab
    return dataclasses.replace(cfg, **kw) if kw else cfg


def reduced(cfg: ModelConfig, *, n_layers: int = 2, d_model: int = 256,
            n_heads: int = 4, vocab: int = 512) -> ModelConfig:
    """A tiny same-family variant for CPU smoke tests.

    Keeps the layer-kind pattern, MoE-ness, softcaps etc., shrinks dims:
    <=2 effective blocks after at most one leading layer, d_model<=512,
    <=4 experts (16 in 4 groups under group-limited sigmoid routing).
    """
    hd = 64
    n_kv = max(1, min(cfg.n_kv_heads, n_heads) // max(1, cfg.n_heads // max(cfg.n_kv_heads, 1) and 1))
    # preserve GQA ratio where possible
    ratio = max(1, cfg.n_heads // max(cfg.n_kv_heads, 1))
    n_kv = max(1, n_heads // ratio)
    pat = cfg.block_pattern
    rem = ()
    layers = len(pat) * max(1, n_layers // len(pat)) if len(pat) <= n_layers else len(pat)
    moe = None
    if cfg.moe is not None and cfg.moe.scoring == "sigmoid":
        # group-limited routing needs groups to choose among
        moe = dataclasses.replace(
            cfg.moe, n_experts=16, top_k=min(cfg.moe.top_k, 4),
            n_group=min(cfg.moe.n_group, 4),
            topk_group=min(cfg.moe.topk_group, 2), d_ff_expert=128,
            d_ff_shared=128 if cfg.moe.n_shared_experts else 0,
            n_shared_experts=min(cfg.moe.n_shared_experts, 1),
            n_held=0, first_held=0, group_size=64)
    elif cfg.moe is not None:
        moe = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=min(cfg.moe.top_k, 2), d_ff_expert=128,
            d_ff_shared=128 if cfg.moe.n_shared_experts else 0,
            n_shared_experts=min(cfg.moe.n_shared_experts, 1),
            d_ff_dense_residual=128 if cfg.moe.d_ff_dense_residual else 0,
            group_size=64)
    ssm = dataclasses.replace(cfg.ssm, d_state=16, head_dim=32, chunk=16) if cfg.ssm else None
    rgl = dataclasses.replace(cfg.rglru, lru_width=d_model) if cfg.rglru else None
    enc = dataclasses.replace(cfg.encoder, n_layers=2, source_len=32) if cfg.encoder else None
    mla = MLAConfig(q_lora_rank=64, kv_lora_rank=64, qk_nope_head_dim=32,
                    qk_rope_head_dim=16, v_head_dim=32) if cfg.mla else None
    lead = cfg.lead_pattern[:1]
    return dataclasses.replace(
        cfg, name=cfg.name + "-smoke", n_layers=len(lead) + layers,
        lead_pattern=lead, mla=mla, d_model=d_model,
        n_heads=n_heads, n_kv_heads=n_kv, head_dim=hd,
        d_ff=4 * d_model if cfg.d_ff else 0, vocab=vocab,
        block_pattern=pat, remainder_pattern=rem, window=min(cfg.window, 16),
        moe=moe, ssm=ssm, rglru=rgl, encoder=enc,
        cross_source_len=16 if cfg.cross_source_len else 0)
