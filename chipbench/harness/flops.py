"""Operations and bytes the algorithm needs, computed from shapes.

Model FLOPs count every multiply-add of the served (or trained) model at
the request's (or client's) expert budget k, adapters included (FLAME
serves and trains them unmerged), attention over the causal context, and
the output head only where logits are needed.  Nothing the implementation
adds by padding, bucketing or recomputation is counted.
"""
from __future__ import annotations

from .model_cfg import Sizes


def token_flops(s: Sizes, k: int, context: int, head: bool) -> float:
    """FLOPs of one token through every layer at budget ``k``, attending
    over ``context`` positions (itself included)."""
    D, H, KV, hd, r = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, \
        s.lora_rank
    q, kv = H * hd, KV * hd
    proj = 2 * D * q * 2 + 2 * D * kv * 2
    proj += 2 * r * (D + q) * 2 + 2 * r * (D + kv) * 2
    attn = 2 * 2 * H * hd * context
    router = 2 * D * s.n_experts
    experts = k * 3 * (2 * D * s.d_expert + 2 * r * (D + s.d_expert))
    shared = 3 * (2 * D * s.d_shared + 2 * r * (D + s.d_shared)) \
        if s.d_shared else 0
    out = s.layers * (proj + attn + router + experts + shared)
    return out + (2 * D * s.vocab if head else 0)


def prefill_flops(s: Sizes, k: int, length: int, start: int = 0) -> float:
    """Prompt positions ``start .. length - 1`` (causal), logits at the
    last one only."""
    n = length - start
    attn_ctx = (length * (length + 1) - start * (start + 1)) // 2
    base = token_flops(s, k, 0, False) * n
    per_ctx = s.layers * 2 * 2 * s.n_heads * s.head_dim
    return base + per_ctx * attn_ctx + 2 * s.d_model * s.vocab


def decode_flops(s: Sizes, k: int, prompt: int, generated: int) -> float:
    """The ``generated - 1`` decode steps after prefill: step j feeds the
    token at position ``prompt + j - 1`` and reads logits there."""
    n = generated - 1
    if n <= 0:
        return 0.0
    ctx_sum = n * prompt + n * (n + 1) // 2
    base = token_flops(s, k, 0, True) * n
    return base + s.layers * 2 * 2 * s.n_heads * s.head_dim * ctx_sum


def request_flops(s: Sizes, k: int, prompt: int, generated: int,
                  cached: int = 0) -> float:
    """One served request: prefill of the uncached prompt, then decode."""
    return (prefill_flops(s, k, prompt, cached)
            + decode_flops(s, k, prompt, generated))


def expert_matmul(s: Sizes, rows: int, touched: int, d_in: int,
                  d_out: int, bytes_per: int = 2):
    """(FLOPs, bytes) of one grouped expert matmul over ``rows`` assigned
    (token, expert) rows, reading the weights and adapter factors of the
    ``touched`` experts once, the rows in and the outputs out."""
    r = s.lora_rank
    fl = 2.0 * rows * (d_in * d_out + r * (d_in + d_out))
    by = bytes_per * (touched * (d_in * d_out + r * (d_in + d_out))
                      + rows * (d_in + d_out))
    return fl, by


def moe_layer_matmuls(s: Sizes, rows: int, touched: int):
    """The three expert matmuls of one MoE layer (w1, w3: D->F; w2: F->D),
    as a list of (FLOPs, bytes)."""
    D, F = s.d_model, s.d_expert
    return [expert_matmul(s, rows, touched, D, F),
            expert_matmul(s, rows, touched, D, F),
            expert_matmul(s, rows, touched, F, D)]
