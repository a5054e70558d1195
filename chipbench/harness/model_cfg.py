"""A configuration file's sizes, read once into plain fields.

Configuration files keep the keys of the model's published ``config.json``
(``hidden_size``, ``num_experts``, ...) with the values as they are run;
the keys the benchmark adds sit in the group ``"run"``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path


@dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    top_k: int
    d_expert: int
    d_shared: int
    vocab: int
    rope_theta: float
    rms_eps: float
    qk_norm: bool
    norm_topk: bool
    lora_rank: int
    lora_alpha: float
    registry_id: str

    @property
    def lora_scale(self) -> float:
        return self.lora_alpha / self.lora_rank


def load(path: Path | str) -> dict:
    return json.loads(Path(path).read_text())


def sizes(c: dict) -> Sizes:
    r = c["run"]
    d = int(c["hidden_size"])
    h = int(c["num_attention_heads"])
    return Sizes(
        layers=int(c["num_hidden_layers"]),
        d_model=d,
        n_heads=h,
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c.get("head_dim") or d // h),
        n_experts=int(c["num_experts"]),
        top_k=int(c["num_experts_per_tok"]),
        d_expert=int(c.get("moe_intermediate_size")
                     or c["intermediate_size"]),
        d_shared=int(c.get("shared_expert_intermediate_size") or 0),
        vocab=int(c["vocab_size"]),
        rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        qk_norm=bool(r["qk_norm"]),
        norm_topk=bool(c["norm_topk_prob"]),
        lora_rank=int(r["lora_rank"]),
        lora_alpha=float(r["lora_alpha"]),
        registry_id=str(r["registry_id"]),
    )


def count_params(s: Sizes) -> int:
    """Parameters of the base model (no adapters)."""
    d, hd = s.d_model, s.head_dim
    attn = d * s.n_heads * hd * 2 + d * s.n_kv_heads * hd * 2
    attn += 2 * hd if s.qk_norm else 0
    moe = d * s.n_experts + 3 * s.n_experts * d * s.d_expert
    moe += 3 * d * s.d_shared
    per_layer = attn + moe + 2 * d
    return s.layers * per_layer + 2 * s.vocab * d + d
