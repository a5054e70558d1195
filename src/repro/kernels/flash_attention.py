"""Pallas TPU flash attention (causal / sliding-window, GQA-aware).

Online-softmax blockwise attention: grid = (batch·heads, q_blocks,
kv_blocks), kv innermost so the (m, l, acc) running statistics live in VMEM
scratch across kv iterations.  Block shapes are MXU-aligned (multiples of
128 on the matmul dims; fp32 accumulation).

GQA is handled in the BlockSpec index maps — K/V blocks are fetched from the
shared kv head ``h // rep`` so query-head replication never touches HBM.

This kernel is the TPU *target*; CPU correctness is validated with
``interpret=True`` against ``ref.flash_attention_ref`` over shape/dtype
sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  window: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # skip fully-masked blocks (strictly above the diagonal / out of window)
    if causal:
        run = k_start <= q_start + block_q - 1
        if window > 0:
            # block must intersect [qpos - window + 1, qpos] for some qpos
            run = run & (k_start + block_k - 1 > q_start - window)
    else:
        run = jnp.asarray(True)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        if causal:
            valid = kpos <= qpos
            if window > 0:
                valid &= kpos > qpos - window
            s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jnp.ndarray:
    """q: (B, H, S, D); k, v: (B, KV, S, D) -> (B, H, S, D)."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    rep = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq = S // block_q
    nk = S // block_k
    scale = D ** -0.5

    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * KV, S, D)
    vf = v.reshape(B * KV, S, D)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        b = bh // H
        h = bh % H
        return (b * KV + h // rep, ik, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running sum)
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
        ],
        interpret=interpret, name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
