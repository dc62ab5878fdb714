"""Architecture + shape configuration system.

Every assigned architecture is an :class:`ArchConfig`; every assigned input
shape is a :class:`ShapeConfig`.  ``registry()`` exposes them to the
launcher (``--arch <id> --shape <id>``).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

ARCH_IDS = [
    "moonshot-v1-16b-a3b",
    "qwen3-moe-30b-a3b",
    "whisper-small",
    "granite-8b",
    "starcoder2-7b",
    "starcoder2-3b",
    "granite-3-2b",
    "pixtral-12b",
    "zamba2-1.2b",
    "mamba2-780m",
    "granite-4.0-h-micro",
    # the paper's own workload (EMPA Y86 sumup) is a simulator config, not
    # an LM; see configs/empa_y86.py
]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str    # dense | moe | ssm | hybrid | interleaved | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    n_shared_experts: int = 0      # DeepSeek/Moonlight-style always-on experts
    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_ngroups: int = 1
    ssm_chunk: int = 64            # SSD chunk length (interleaved family)
    # --- interleaved: a Mamba2 or attention mixer per layer, named by
    #     `layer_types` ("mamba" | "attention"), each followed by an MLP ---
    layer_types: tuple = ()
    # --- hybrid (zamba2): one shared attention+MLP block applied
    #     every `shared_attn_every` SSM blocks ---
    shared_attn_every: int = 0
    # --- enc-dec (whisper) ---
    enc_layers: int = 0
    dec_layers: int = 0
    # --- VLM / audio frontend stubs ---
    frontend: Optional[str] = None   # "vision" | "audio" | None
    frontend_dim: int = 1024         # precomputed patch/frame embedding width
    n_frontend_tokens: int = 256     # prepended stub tokens per sequence
    # --- common ---
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    act: str = "silu"
    pos_embed: str = "rope"          # rope | learned | none
    max_position: int = 1 << 20
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # sub-quadratic attention available? (drives long_500k applicability)
    subquadratic: bool = False
    # --- muP multipliers (interleaved family): token embeddings times
    #     `embedding_multiplier`; attention scores times
    #     `attention_multiplier` (None: 1/sqrt(head_dim)); each residual
    #     branch times `residual_multiplier`; logits over `logits_scaling`
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.n_heads and self.n_kv_heads:
            assert self.n_heads % self.n_kv_heads == 0, \
                f"{self.name}: GQA requires n_heads % n_kv_heads == 0"
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.pos_embed not in ("rope", "learned", "none"):
            raise ValueError(f"{self.name}: pos_embed {self.pos_embed!r}")
        if self.family == "interleaved":
            kinds = set(self.layer_types)
            if len(self.layer_types) != self.n_layers \
                    or not kinds <= {"mamba", "attention"}:
                raise ValueError(
                    f"{self.name}: layer_types must name 'mamba' or "
                    f"'attention' for each of the {self.n_layers} layers")
        else:
            mup = (self.embedding_multiplier, self.attention_multiplier,
                   self.residual_multiplier, self.logits_scaling)
            if self.layer_types or mup != (1.0, None, 1.0, 1.0):
                raise ValueError(
                    f"{self.name}: layer_types and the muP multipliers "
                    f"are served for the interleaved family only, not "
                    f"{self.family!r}")

    # ---- derived sizes -----------------------------------------------
    @property
    def vocab_padded(self) -> int:
        """Vocab padded to a TP-friendly multiple (Megatron-style): odd
        vocabs (51865/49155/50280) otherwise force replicated unembed
        tables, whose FSDP-sharded d-contraction all-reduces partial
        logits per loss chunk (see EXPERIMENTS.md §Perf)."""
        mult = 32
        return (self.vocab + mult - 1) // mult * mult

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def layer_period(self) -> int:
        """Layers in one repeat of ``layer_types`` (interleaved): the
        stack scans whole periods, so each layer's kind is static."""
        t, n = self.layer_types, self.n_layers
        return next(p for p in range(1, n + 1)
                    if n % p == 0 and t == t[:p] * (n // p))

    @property
    def n_attn_layers(self) -> int:
        return self.layer_types.count("attention")

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def recurrent(self) -> bool:
        """Some layer keeps a recurrent state that absorbs every token."""
        return self.family in ("ssm", "hybrid", "interleaved")

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs generate tokens (whisper decodes text)

    def param_count(self) -> int:
        """Exact parameter count from the definition table."""
        from repro.models import model as _m
        return sum(int(_prod(d.shape)) for d in _m.param_defs(self))

    def active_param_count(self) -> int:
        """Active-per-token parameters (MoE: only routed-in experts)."""
        from repro.models import model as _m
        total = 0
        for d in _m.param_defs(self):
            n = int(_prod(d.shape))
            if "experts" in (d.axes or ()) and self.n_experts:
                n = n * (self.top_k + self.n_shared_experts) // self.n_experts
            total += n
        return total


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str     # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(arch: "ArchConfig", shape: ShapeConfig) -> tuple[bool, str]:
    """long_500k runs only for sub-quadratic archs (assignment directive)."""
    if shape.name == "long_500k" and not arch.subquadratic:
        return False, ("skip: pure full-attention arch — 512k dense decode "
                       "excluded per assignment (see DESIGN.md §4)")
    return True, ""


def get_arch(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(
        "repro.configs." + arch_id.replace("-", "_").replace(".", "_"))
    return mod.CONFIG


def registry() -> dict[str, ArchConfig]:
    return {a: get_arch(a) for a in ARCH_IDS}


def reduced(cfg: ArchConfig, **overrides) -> ArchConfig:
    """A tiny same-family config for CPU smoke tests."""
    small = dict(
        n_layers=min(cfg.n_layers, 2),
        d_model=128,
        n_heads=max(4, min(cfg.n_heads, 4)),
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads else 0,
        d_ff=256,
        vocab=512,
        head_dim=32,
        max_position=4096,
    )
    if cfg.n_experts:
        # capacity_factor == n_experts makes the reduced config dropless, so
        # decode-vs-forward consistency is exact (drop semantics are covered
        # by the dedicated MoE unit tests).
        small.update(n_experts=8, top_k=2, d_ff=64, capacity_factor=8.0)
    if cfg.ssm_state:
        small.update(ssm_state=16, ssm_headdim=32)
    if cfg.shared_attn_every:
        small.update(shared_attn_every=2, n_layers=4)
    if cfg.layer_types:
        small.update(layer_types=("mamba", "attention", "mamba"),
                     n_layers=3)
    if cfg.enc_layers:
        small.update(enc_layers=2, dec_layers=2)
    if cfg.frontend:
        small.update(frontend_dim=64, n_frontend_tokens=8)
    if cfg.n_kv_heads and cfg.n_heads % max(cfg.n_kv_heads, 1):
        small.update(n_kv_heads=2)
    small.update(overrides)
    return dataclasses.replace(cfg, **small)
