"""Pallas TPU kernel: fused LoRA matmul  y = x·W + (x·A)·B · scale.

The expert-FFN matmul is the compute hot spot of FLAME fine-tuning.  Naively
the LoRA bypass ``(x@A)@B`` is a separate pair of skinny matmuls whose
intermediates round-trip HBM.  This kernel fuses base + bypass in one pass:

  grid = (M/bm, N/bn, K/bk)   — k innermost (sequential on TPU), so the
  fp32 accumulator and the running ``x·A`` projection live in VMEM scratch
  across k iterations;

  * every k step: ``acc += x_blk @ w_blk`` (MXU, 128-aligned tiles) and
    ``xa += x_blk @ a_blk`` (A is sliced along K with the same index map
    as x, so the bypass never re-reads x from HBM);
  * last k step: ``acc += (xa @ B_blk) · scale`` — B is tiny ((r, bn));
    then the fp32 accumulator is cast once and written out.

VMEM working set per program: bm·bk + bk·bn + bm·bn + bm·r + r·bn floats —
with bm=bn=bk=256, r≤64 that is ~1 MB, far under the ~16 MB v5e VMEM budget.

Validated against ``ref.lora_matmul_ref`` with interpret=True shape/dtype
sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lora_matmul_kernel(x_ref, w_ref, a_ref, b_ref, o_ref, acc_scr, xa_scr,
                        *, scale: float, nk: int, k_axis: int = 2):
    ik = pl.program_id(k_axis)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        xa_scr[...] = jnp.zeros_like(xa_scr)

    x = x_ref[...].reshape(x_ref.shape[-2:]).astype(jnp.float32)  # (bm, bk)
    w = w_ref[...].reshape(w_ref.shape[-2:]).astype(jnp.float32)  # (bk, bn)
    a = a_ref[...].reshape(a_ref.shape[-2:]).astype(jnp.float32)  # (bk, r)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    xa_scr[...] += jax.lax.dot_general(
        x, a, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        b = b_ref[...].reshape(b_ref.shape[-2:]).astype(jnp.float32)  # (r, bn)
        bypass = jax.lax.dot_general(
            xa_scr[...], b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = (acc_scr[...] + bypass * scale).astype(o_ref.dtype)
        o_ref[...] = out.reshape(o_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_m", "block_n", "block_k", "interpret"))
def lora_matmul(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
                b: jnp.ndarray, *, scale: float = 1.0,
                block_m: int = 256, block_n: int = 256, block_k: int = 256,
                interpret: bool = True) -> jnp.ndarray:
    """x: (M, K); w: (K, N); a: (K, r); b: (r, N) -> (M, N)."""
    M, K = x.shape
    Kw, N = w.shape
    r = a.shape[-1]
    assert Kw == K and a.shape == (K, r) and b.shape == (r, N)
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nk = K // bk

    kernel = functools.partial(_lora_matmul_kernel, scale=scale, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((bk, bn), lambda im, jn, ik: (ik, jn)),
            pl.BlockSpec((bk, r), lambda im, jn, ik: (ik, 0)),
            pl.BlockSpec((r, bn), lambda im, jn, ik: (0, jn)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),   # base accumulator
            pltpu.VMEM((bm, r), jnp.float32),    # running x·A
        ],
        interpret=interpret, name="lora_matmul",
    )(x, w, a, b)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_m", "block_n", "block_k", "interpret"))
def lora_matmul_experts(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
                        b: jnp.ndarray, *, scale: float = 1.0,
                        block_m: int = 128, block_n: int = 256,
                        block_k: int = 256,
                        interpret: bool = True) -> jnp.ndarray:
    """Stacked per-expert variant: x (E, C, K); w (E, K, N); a (E, K, r);
    b (E, r, N) -> (E, C, N).  The expert axis becomes the outer grid dim so
    each expert's LoRA factors are fetched once and stay VMEM-resident."""
    E, C, K = x.shape
    N = w.shape[-1]
    r = a.shape[-1]
    bm = min(block_m, C)
    bn = min(block_n, N)
    bk = min(block_k, K)
    assert C % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk

    kernel = functools.partial(_lora_matmul_kernel, scale=scale, nk=nk,
                               k_axis=3)

    return pl.pallas_call(
        kernel,
        grid=(E, C // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, im, jn, ik: (e, im, ik)),
            pl.BlockSpec((1, bk, bn), lambda e, im, jn, ik: (e, ik, jn)),
            pl.BlockSpec((1, bk, r), lambda e, im, jn, ik: (e, ik, 0)),
            pl.BlockSpec((1, r, bn), lambda e, im, jn, ik: (e, 0, jn)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, im, jn, ik: (e, im, jn)),
        out_shape=jax.ShapeDtypeStruct((E, C, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        interpret=interpret, name="lora_matmul_experts",
    )(x, w, a, b)
