"""Pallas TPU kernel: fused SMoE router — softmax + top-k + activation counts.

FLAME's adaptive routing needs, per token block: (1) routing probabilities,
(2) the top-``k_i`` selection mask, (3) renormalised combine weights, and
(4) the **per-expert activation counts** that feed the activation-aware
aggregation (Eq. 6).  On GPU the counts would be a scatter-add; on TPU we
fuse everything into one VMEM-resident pass over token blocks:

  grid = (T / bt,)  — one program per token block;
  * softmax over the expert axis in fp32 (E ≤ a few hundred, fits a lane);
  * iterative top-k: k repeats of (argmax → one-hot → mask out), which is
    exactly the oracle semantics and MXU/VPU friendly (no sort);
  * weights renormalised over the selected experts;
  * counts: ``mask.sum(0)`` accumulated into a single (1, E) output block
    that every grid step maps to — TPU grid iterations are sequential, so
    the revisited block acts as an accumulator (init at step 0).

Validated against ``ref.topk_router_ref`` in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _router_kernel(logits_ref, w_ref, m_ref, c_ref, *, k: int):
    it = pl.program_id(0)

    @pl.when(it == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    logits = logits_ref[...].astype(jnp.float32)          # (bt, E)
    z = logits - logits.max(axis=-1, keepdims=True)
    ez = jnp.exp(z)
    probs = ez / ez.sum(axis=-1, keepdims=True)

    masked = probs
    mask = jnp.zeros_like(probs)
    for _ in range(k):                                    # k is static
        idx = jnp.argmax(masked, axis=-1)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, probs.shape, 1)
                  == idx[:, None]).astype(jnp.float32)
        mask = mask + onehot
        masked = masked * (1.0 - onehot)

    weights = probs * mask
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    w_ref[...] = weights.astype(w_ref.dtype)
    m_ref[...] = mask.astype(m_ref.dtype)
    c_ref[...] += mask.sum(axis=0, keepdims=True).astype(c_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def topk_router(logits: jnp.ndarray, k: int, *, block_t: int = 1024,
                interpret: bool = True):
    """logits: (T, E) -> (weights (T, E) f32, mask (T, E) f32, counts (E,)).

    ``k`` static (the client budget k_i).  Semantics identical to
    ``moe_layer.topk_routing`` plus the fused count reduction.
    """
    T, E = logits.shape
    bt = min(block_t, T)
    while T % bt:
        bt //= 2
    nt = T // bt

    kernel = functools.partial(_router_kernel, k=k)
    weights, mask, counts = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((bt, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, E), lambda i: (i, 0)),
            pl.BlockSpec((bt, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),   # accumulator block
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, E), jnp.float32),
            jax.ShapeDtypeStruct((T, E), jnp.float32),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
        ],
        interpret=interpret, name="topk_router",
    )(logits)
    return weights, mask, counts[0]
