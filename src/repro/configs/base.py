"""Config system: architectures, input shapes, registry.

Every assigned architecture gets a module ``configs/<id>.py`` exporting
``full_config()`` (the exact assigned spec) and ``smoke_config()``
(a reduced same-family variant: <=2 layers, d_model<=512, <=4 experts)
plus registration into the global registry.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | audio | vlm | gnn
    n_layers: int
    d_model: int
    n_heads: int = 0                # query heads (0 for attn-free)
    n_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    head_dim: int = 0               # 0 -> d_model // n_heads
    # --- MLP ---
    mlp_act: str = "silu"           # "silu" (SwiGLU) | "gelu" (GeGLU)
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 1
    capacity_factor: float = 1.25
    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    # --- layer pattern ---
    # pattern tokens: "attn" (global), "local" (sliding window), "mamba",
    # "shared_attn" (zamba2-style weight-shared attention block).
    # None => ("attn",) * n_layers.
    layer_pattern: Optional[Tuple[str, ...]] = None
    sliding_window: int = 0
    # --- encoder-decoder (whisper) ---
    n_enc_layers: int = 0
    enc_seq: int = 0                # encoder frames (stub frontend output length)
    # --- modality frontend stub (vlm) ---
    frontend_seq: int = 0           # patch embeddings prepended to the text seq
    # --- misc ---
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = True
    tie_embeddings: bool = False
    # attention chunking for the online-softmax scan
    q_chunk: int = 512
    kv_chunk: int = 1024
    # mlp/moe group size for capacity routing (tokens per routing group)
    moe_group: int = 256
    source: str = ""                # citation

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def pattern(self) -> Tuple[str, ...]:
        if self.layer_pattern is not None:
            assert len(self.layer_pattern) == self.n_layers, (
                f"{self.name}: pattern length {len(self.layer_pattern)} != "
                f"n_layers {self.n_layers}")
            return self.layer_pattern
        return ("attn",) * self.n_layers

    @property
    def is_sub_quadratic(self) -> bool:
        """True if every layer has bounded receptive field (SSM or window)."""
        return all(
            t in ("mamba",) or (t in ("local",) and self.sliding_window > 0)
            for t in self.pattern
        ) or self.supports_long_decode

    @property
    def supports_long_decode(self) -> bool:
        """long_500k eligibility: SSM/hybrid, or dense with a sliding-window /
        chunked-local variant on most layers (global layers keep a
        model-sharded KV, which is memory- not compute-quadratic at decode)."""
        toks = set(self.pattern)
        if toks <= {"mamba"}:
            return True
        if "mamba" in toks:                      # hybrid
            return True
        if "local" in toks and self.sliding_window > 0:
            return True
        return False

    @property
    def has_decode(self) -> bool:
        """Encoder-only / pure-encoder families would return False; all our
        assigned archs are decoders (whisper has a decoder stack)."""
        return True

    def validate(self) -> None:
        assert self.d_model > 0 and self.n_layers > 0
        if self.family not in ("ssm",):
            assert self.vocab_size > 0
        for t in self.pattern:
            assert t in ("attn", "local", "mamba", "shared_attn"), t
        if "local" in self.pattern:
            assert self.sliding_window > 0


# ---------------------------------------------------------------------------
# GNN configuration (the paper's own system)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    family: str = "gnn"
    model: str = "graphsage"        # gcn | graphsage | gat
    n_nodes: int = 0
    feat_dim: int = 0
    hidden: int = 256
    n_classes: int = 0
    n_layers: int = 2
    fanout: Tuple[int, ...] = (15, 10)   # β per hop (mini-batch)
    batch_size: int = 1024               # b (mini-batch)
    # ELL width (neighbours kept per row) of full-graph training,
    # evaluation and the embedding store; None keeps every neighbour
    max_degree: Optional[int] = None
    gat_heads: int = 4
    dtype: str = "float32"
    loss: str = "ce"                     # ce | mse
    # --- Pallas neighbor-aggregation kernel (kernels/neighbor_agg) ---
    # Routes the Ã-weighted aggregation of gcn/graphsage through the
    # batch-tiled software-gather kernel in BOTH forward paths, and GAT's
    # full-graph attention sum (one call per head, the softmax weights
    # as edge weights); GAT's mini-batch sum stays an einsum.
    # The kernel compiles on a TPU backend and runs in the Pallas
    # interpreter on any other (repro.kernels.default_interpret).
    use_agg_kernel: bool = False
    agg_b_tile: int = 8
    agg_d_tile: int = 128                # 32-bit lanes per step; 128 on TPU
    agg_k_slab: int = 4
    # --- feature-table layout (kernels/neighbor_agg/featshard) ---
    # "replicated": every device holds the full [n, d] gather source (the
    # PR-5 sharded kernel's layout).  "sharded": the table rows over the
    # NODES mesh axis with a degree-ordered hot cache of the top
    # feat_cache_rows high-degree rows replicated per shard — per-device
    # memory drops to n·d/shards + C·d and cold rows move via one
    # compacted all_gather overlapped with the shard-local aggregation.
    # Takes effect on the sharded kernel paths (sharded sources +
    # use_agg_kernel); einsum/unsharded paths ignore it.
    feats_layout: str = "replicated"     # replicated | sharded
    feat_cache_rows: int = -1            # -1 auto (n//8) | 0 off | explicit C
    source: str = ""

    @property
    def has_decode(self) -> bool:
        return False

    def validate(self) -> None:
        """Reject bad (b, β) grids and kernel tilings up front — a zero
        tile or fan-out otherwise surfaces as an opaque Pallas shape
        error deep inside the aggregation kernel."""
        def req(cond: bool, msg: str) -> None:
            if not cond:
                raise ValueError(f"GNNConfig {self.name!r}: {msg}")
        req(self.model in ("gcn", "graphsage", "gat"),
            f"unknown model {self.model!r}")
        req(self.n_layers > 0, f"n_layers must be > 0, got {self.n_layers}")
        req(self.hidden > 0, f"hidden must be > 0, got {self.hidden}")
        req(len(self.fanout) == self.n_layers,
            f"fanout {self.fanout} must have one β per layer "
            f"(n_layers={self.n_layers})")
        req(all(int(b) > 0 for b in self.fanout),
            f"fan-outs must be positive, got {self.fanout}")
        req(self.batch_size > 0,
            f"batch_size must be > 0, got {self.batch_size}")
        req(self.n_nodes <= 0 or self.batch_size <= self.n_nodes,
            f"batch_size must not exceed the graph "
            f"(b={self.batch_size} > n_nodes={self.n_nodes}); the engine "
            f"pads b > n_train, but b > n can only be a grid typo")
        req(self.max_degree is None or self.max_degree > 0,
            f"max_degree must be > 0, got {self.max_degree}")
        if self.model == "gat":
            req(self.gat_heads > 0,
                f"gat_heads must be > 0, got {self.gat_heads}")
            req(self.hidden % self.gat_heads == 0,
                f"hidden ({self.hidden}) must split evenly over "
                f"gat_heads ({self.gat_heads})")
        for f in ("agg_b_tile", "agg_d_tile", "agg_k_slab"):
            req(getattr(self, f) > 0,
                f"{f} must be > 0, got {getattr(self, f)}")
        req(self.feats_layout in ("replicated", "sharded"),
            f"unknown feats_layout {self.feats_layout!r} "
            f"(expected 'replicated' or 'sharded')")
        req(self.feat_cache_rows >= -1,
            f"feat_cache_rows must be -1 (auto), 0 (off) or a positive "
            f"cache size, got {self.feat_cache_rows}")


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    kind: str           # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k":    InputShape("train_4k",    "train",   4_096,   256),
    "prefill_32k": InputShape("prefill_32k", "prefill", 32_768,  32),
    "decode_32k":  InputShape("decode_32k",  "decode",  32_768,  128),
    "long_500k":   InputShape("long_500k",   "decode",  524_288, 1),
}


def shape_applicable(cfg, shape: InputShape) -> Tuple[bool, str]:
    """Whether (arch, shape) should run, and why not if skipped."""
    if cfg.family == "gnn":
        return False, (
            "GNN configs use their own dry-run shapes (fullgraph_step / "
            "minibatch_step); see launch/dryrun.py")
    if shape.name == "long_500k" and not cfg.supports_long_decode:
        return False, (
            f"{cfg.name} is a pure full-attention stack; long_500k needs "
            "sub-quadratic attention (see DESIGN.md §Arch-applicability)")
    if shape.kind == "decode" and not cfg.has_decode:
        return False, f"{cfg.name} has no decode step"
    return True, ""


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_ARCH_MODULES = [
    "llama4_scout_17b_a16e",
    "gemma_7b",
    "whisper_medium",
    "llama4_maverick_400b_a17b",
    "mamba2_130m",
    "gemma3_12b",
    "granite_3_2b",
    "stablelm_1_6b",
    "zamba2_7b",
    "internvl2_76b",
    "gnn_papers100m",        # bonus: the paper's own system at scale
]

_REGISTRY: Dict[str, Any] = {}


def _load() -> None:
    if _REGISTRY:
        return
    for mod_name in _ARCH_MODULES:
        mod = importlib.import_module(f"repro.configs.{mod_name}")
        cfg = mod.full_config()
        _REGISTRY[cfg.name] = mod


def list_archs() -> Tuple[str, ...]:
    _load()
    return tuple(_REGISTRY.keys())


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    _load()
    key = name.replace("_", "-")
    for k, mod in _REGISTRY.items():
        if k == key or k.replace("-", "_") == name:
            cfg = mod.smoke_config() if smoke else mod.full_config()
            cfg.validate()
            return cfg
    raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
