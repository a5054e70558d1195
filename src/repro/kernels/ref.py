"""Pure-jnp oracles for every Pallas kernel (the correctness references).

These are deliberately naive (materialise the full score matrix, loop the
top-k) — clarity over speed.  Kernel tests sweep shapes/dtypes and
``assert_allclose`` against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True, window: int = 0,
                        scale: float | None = None) -> jnp.ndarray:
    """q: (B,H,S,D); k,v: (B,KV,S,D) -> (B,H,S,D).  fp32 softmax."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    rep = H // KV
    scale = scale if scale is not None else D ** -0.5
    kr = jnp.repeat(k, rep, axis=1)
    vr = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    if causal:
        valid = kpos <= qpos
        if window > 0:
            valid &= kpos > qpos - window
        s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def lora_matmul_ref(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray, scale: float) -> jnp.ndarray:
    """x: (M,K); w: (K,N); a: (K,r); b: (r,N) -> x@w + (x@a)@b·scale."""
    f32 = jnp.float32
    y = x.astype(f32) @ w.astype(f32)
    y = y + (x.astype(f32) @ a.astype(f32)) @ b.astype(f32) * scale
    return y.astype(x.dtype)


def lora_matmul_experts_ref(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
                            b: jnp.ndarray, scale: float) -> jnp.ndarray:
    """Stacked per-expert oracle: x (E,C,K); w (E,K,N); a (E,K,r);
    b (E,r,N) -> (E,C,N).  Same numerics contract as the kernel: all math
    in fp32, one cast at the end."""
    f32 = jnp.float32
    xf, wf, af, bf = (t.astype(f32) for t in (x, w, a, b))
    y = jnp.einsum("eck,ekn->ecn", xf, wf)
    xa = jnp.einsum("eck,ekr->ecr", xf, af)
    y = y + jnp.einsum("ecr,ern->ecn", xa, bf) * scale
    return y.astype(x.dtype)


def topk_router_ref(logits: jnp.ndarray, k: int):
    """logits: (T,E) -> (weights (T,E) fp32, mask (T,E) fp32, counts (E,)).

    Softmax -> iterative argmax top-k -> renormalised weights.  Identical
    semantics to models.moe_layer.topk_routing plus the count reduction.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    masked = probs
    mask = jnp.zeros_like(probs)
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
        mask = mask + onehot
        masked = masked * (1.0 - onehot)
    weights = probs * mask
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, mask, mask.sum(axis=0)


def ragged_expert_matmul_ref(xs: jnp.ndarray, block_expert: jnp.ndarray,
                             w: jnp.ndarray, a: jnp.ndarray = None,
                             b: jnp.ndarray = None,
                             scale: float = 0.0) -> jnp.ndarray:
    """Oracle for the grouped (segment) LoRA matmul over the ragged
    buffer: xs (N, K); block_expert (N // bm,) int32; w (E, K, H);
    optional LoRA factors a (E, K, r), b (E, r, H).  Row block ``i``
    multiplies expert ``block_expert[i]``'s weights — here spelled as a
    per-block weight gather + batched einsum.  Same numerics contract as
    the kernel: fp32 accumulate, one cast."""
    f32 = jnp.float32
    N, K = xs.shape
    nb = block_expert.shape[0]
    xb = xs.reshape(nb, N // nb, K).astype(f32)
    y = jnp.einsum("bmk,bkh->bmh", xb, w[block_expert].astype(f32))
    if a is not None:
        xa = jnp.einsum("bmk,bkr->bmr", xb, a[block_expert].astype(f32))
        y = y + jnp.einsum("bmr,brh->bmh", xa,
                           b[block_expert].astype(f32)) * scale
    return y.reshape(N, -1).astype(xs.dtype)


def adaptive_topk_router_ref(logits: jnp.ndarray, k_tok: jnp.ndarray,
                             max_k: int):
    """Per-token-budget routing: token ``t`` activates its top ``k_tok[t]``
    experts (FLAME's adaptive-k at serving time, per slot of a mixed batch).

    logits: (T,E); k_tok: (T,) int with 0 <= k_tok[t] <= max_k (static;
    ``k_tok`` itself may be traced — budget 0 deselects the token entirely,
    which is how the serving engine masks free slots out of routing).
    Returns (weights, mask, counts) with the same layout as
    :func:`topk_router_ref`.  Because top-k selection is nested (the top-j
    experts are a prefix of the top-(j+1) experts under the same argmax tie
    break), truncating the ranked selection at ``k_tok[t]`` and
    renormalising is *exactly* ``topk_router_ref(logits[t], k_tok[t])`` per
    token — uniform ``k_tok == k`` reproduces the static router bit-for-bit.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    masked = probs
    mask = jnp.zeros_like(probs)
    take = k_tok.astype(jnp.int32)[:, None]
    for rank in range(max_k):
        idx = jnp.argmax(masked, axis=-1)
        onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
        mask = mask + onehot * (rank < take)
        masked = masked * (1.0 - onehot)
    weights = probs * mask
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, mask, mask.sum(axis=0)
