"""Kernel-backend dispatch: one switch between Pallas kernels and jnp refs.

This is the layer that turns the kernel suite from a validated appendix into
the actual training hot path.  Model code never imports a Pallas kernel
directly — it asks this module for an op, passing the model's
:class:`repro.configs.base.KernelConfig`, and gets back either the fused
Pallas implementation or the pure-jnp reference:

==================  =======================  ============================
``backend=``        on TPU                   off TPU (CPU/GPU)
==================  =======================  ============================
``"auto"``          Pallas (compiled)        jnp reference
``"pallas"``        Pallas (compiled, or     Pallas **interpreter**
                    interpreter if
                    ``interpret=True``)
``"reference"``     jnp reference            jnp reference
==================  =======================  ============================

Two further dispatch rules live at the call sites (documented in
docs/kernels.md):

* attention falls back to the blockwise jnp path whenever
  ``attn_logit_softcap > 0`` (the Pallas kernel does not implement softcap)
  and on the decode path (single-token attention has no flash structure);
* a matrix with no LoRA adapter (``lp is None``) uses the plain einsum —
  the fused kernel only pays off when the bypass rides along.

Differentiability
-----------------
``pallas_call`` has no autodiff rule, so every op here is wrapped in
``jax.custom_vjp``: the **forward** runs the Pallas kernel, the **backward**
is reference math (exact analytic formulas for the linear LoRA ops; the vjp
of the jnp oracle for attention and routing).  Gradients through a
``backend="pallas"`` model are therefore the *reference* gradients evaluated
at kernel-forward primals — which is exactly what the CI parity suite
(tests/test_backend.py) asserts.  A dedicated Pallas backward kernel for
flash attention is future work; until then the attention backward
re-materialises the (S, S) score matrix like the oracle does.

Block sizes are chosen per call as the largest divisor of each dim below the
MXU-friendly target; shapes whose best divisor is tiny (prime dims) fall
back to the reference implementation rather than dispatching a degenerate
near-1-wide grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..configs.base import KernelConfig
from . import ref
from .flash_attention import flash_attention as _flash_pallas
from .lora_matmul import lora_matmul as _lora_pallas
from .lora_matmul import lora_matmul_experts as _lora_experts_pallas
from .ops import on_tpu
from .ragged_dispatch import ragged_expert_matmul as _ragged_mm_pallas
from .topk_router import topk_router as _router_pallas

_F32 = jnp.float32


# ==========================================================================
# resolution
# ==========================================================================

def resolve(kcfg: KernelConfig | None) -> str:
    """Resolve ``backend="auto"`` against the runtime platform."""
    if kcfg is None:
        return "reference"
    if kcfg.backend == "auto":
        return "pallas" if on_tpu() else "reference"
    assert kcfg.backend in ("pallas", "reference"), kcfg.backend
    return kcfg.backend


def use_pallas(kcfg: KernelConfig | None) -> bool:
    return resolve(kcfg) == "pallas"


def resolve_interpret(kcfg: KernelConfig) -> bool:
    """Pallas only compiles on TPU — everywhere else the interpreter runs
    the kernel; ``interpret=True`` forces it even on TPU (escape hatch)."""
    return bool(kcfg.interpret) or not on_tpu()


def _block(dim: int, target: int) -> int:
    """Largest divisor of ``dim`` that is <= ``target``."""
    b = min(dim, target)
    while dim % b:
        b -= 1
    return max(b, 1)


# A dim whose largest divisor under the target is tiny (prime seq lens etc.)
# would produce a pathological near-1-wide Pallas grid.  Such shapes fall
# back to the reference implementation instead of dispatching — no silent
# performance cliffs.
_BLOCK_FLOOR = 8


def _degenerate(dim: int, target: int) -> bool:
    return dim >= _BLOCK_FLOOR and _block(dim, target) < _BLOCK_FLOOR


def flash_blocks_ok(seq_len: int) -> bool:
    """Whether the flash kernel gets non-degenerate blocks for this S
    (checked at the attention call site alongside the softcap rule)."""
    return not _degenerate(seq_len, 128)


# ==========================================================================
# fused LoRA matmul (2-D): y = x @ W + (x @ A) @ B * scale
# ==========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lora_matmul_p(scale, interpret, x, w, a, b):
    M, K = x.shape
    N = w.shape[1]
    return _lora_pallas(x, w, a, b, scale=scale,
                        block_m=_block(M, 256), block_n=_block(N, 256),
                        block_k=_block(K, 256), interpret=interpret)


def _lora_matmul_fwd(scale, interpret, x, w, a, b):
    return _lora_matmul_p(scale, interpret, x, w, a, b), (x, w, a, b)


def _lora_matmul_bwd(scale, interpret, res, g):
    # exact vjp of ref.lora_matmul_ref (fp32 math, single output cast)
    x, w, a, b = res
    gf, xf, wf, af, bf = (t.astype(_F32) for t in (g, x, w, a, b))
    gb = gf @ bf.T                                    # (M, r)
    dx = (gf @ wf.T + (gb @ af.T) * scale).astype(x.dtype)
    dw = (xf.T @ gf).astype(w.dtype)
    da = ((xf.T @ gb) * scale).astype(a.dtype)
    db = (((xf @ af).T @ gf) * scale).astype(b.dtype)
    return dx, dw, da, db


_lora_matmul_p.defvjp(_lora_matmul_fwd, _lora_matmul_bwd)


def lora_matmul(kcfg: KernelConfig, x, w, a, b, *, scale: float):
    """Differentiable fused LoRA matmul.  x (M,K); w (K,N); a (K,r); b (r,N)."""
    M, K = x.shape
    N = w.shape[1]
    if use_pallas(kcfg) and not (_degenerate(M, 256) or _degenerate(N, 256)
                                 or _degenerate(K, 256)):
        return _lora_matmul_p(float(scale), resolve_interpret(kcfg),
                              x, w, a, b)
    return ref.lora_matmul_ref(x, w, a, b, scale)


# ==========================================================================
# fused LoRA matmul, stacked per expert: x (E,C,K) w (E,K,N) a (E,K,r) b (E,r,N)
# ==========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lora_experts_p(scale, interpret, x, w, a, b):
    E, C, K = x.shape
    N = w.shape[-1]
    return _lora_experts_pallas(x, w, a, b, scale=scale,
                                block_m=_block(C, 128), block_n=_block(N, 256),
                                block_k=_block(K, 256), interpret=interpret)


def _lora_experts_fwd(scale, interpret, x, w, a, b):
    return _lora_experts_p(scale, interpret, x, w, a, b), (x, w, a, b)


def _lora_experts_bwd(scale, interpret, res, g):
    x, w, a, b = res
    gf, xf, wf, af, bf = (t.astype(_F32) for t in (g, x, w, a, b))
    gb = jnp.einsum("ecn,ern->ecr", gf, bf)           # g @ B^T per expert
    xa = jnp.einsum("eck,ekr->ecr", xf, af)           # x @ A  per expert
    dx = (jnp.einsum("ecn,ekn->eck", gf, wf)
          + jnp.einsum("ecr,ekr->eck", gb, af) * scale).astype(x.dtype)
    dw = jnp.einsum("eck,ecn->ekn", xf, gf).astype(w.dtype)
    da = (jnp.einsum("eck,ecr->ekr", xf, gb) * scale).astype(a.dtype)
    db = (jnp.einsum("ecr,ecn->ern", xa, gf) * scale).astype(b.dtype)
    return dx, dw, da, db


_lora_experts_p.defvjp(_lora_experts_fwd, _lora_experts_bwd)


def lora_matmul_experts(kcfg: KernelConfig, x, w, a, b, *, scale: float):
    """Differentiable stacked per-expert fused LoRA matmul (3-D operands)."""
    E, C, K = x.shape
    N = w.shape[-1]
    if use_pallas(kcfg) and not (_degenerate(C, 128) or _degenerate(N, 256)
                                 or _degenerate(K, 256)):
        return _lora_experts_p(float(scale), resolve_interpret(kcfg),
                               x, w, a, b)
    return ref.lora_matmul_experts_ref(x, w, a, b, scale)


# ==========================================================================
# flash attention (model layout: q (B,S,H,D); k,v (B,S,KV,D))
# ==========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash_p(causal, window, interpret, q, k, v):
    # kernel layout is (B, H, S, D)
    S = q.shape[2]
    bq = _block(S, 128)
    bk = _block(S, 128)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         block_q=bq, block_k=bk, interpret=interpret)


def _flash_fwd(causal, window, interpret, q, k, v):
    return _flash_p(causal, window, interpret, q, k, v), (q, k, v)


def _flash_bwd(causal, window, interpret, res, g):
    # vjp of the jnp oracle at the same primals: reference gradients.  This
    # re-materialises the (S, S) scores — acceptable until a Pallas flash
    # backward lands (see docs/kernels.md).
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window), q, k, v)
    return vjp(g)


_flash_p.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(kcfg: KernelConfig, q, k, v, *, causal: bool = True,
                    window: int = 0):
    """Differentiable flash attention in the MODEL layout:
    q (B,S,H,D); k,v (B,S,KV,D) -> (B,S,H,D).

    Only called on the pallas path — the reference path is the blockwise
    ``repro.models.attention.flash_attention_jnp`` (which also owns the
    softcap and decode fallbacks, see its module docstring)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash_p(causal, window, resolve_interpret(kcfg), qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)


# ==========================================================================
# top-k router
# ==========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _router_p(k, interpret, logits):
    T = logits.shape[0]
    return _router_pallas(logits, k, block_t=_block(T, 1024),
                          interpret=interpret)


def _router_fwd(k, interpret, logits):
    return _router_p(k, interpret, logits), (logits,)


def _router_bwd(k, interpret, res, g):
    (logits,) = res
    _, vjp = jax.vjp(lambda l: ref.topk_router_ref(l, k), logits)
    return vjp(g)


_router_p.defvjp(_router_fwd, _router_bwd)


def router(kcfg: KernelConfig, logits, k: int):
    """Differentiable fused router.  logits (T,E) ->
    (weights (T,E) f32, mask (T,E) f32, counts (E,) f32)."""
    if use_pallas(kcfg) and not _degenerate(logits.shape[0], 1024):
        return _router_p(k, resolve_interpret(kcfg), logits)
    return ref.topk_router_ref(logits, k)


# ==========================================================================
# ragged (sort-based) MoE dispatch: grouped matmul
# ==========================================================================
# The kernel behind ``apply_moe(dispatch="ragged")`` — see
# kernels/ragged_dispatch.py for the layout (its gather and combine are
# plain XLA gathers) and docs/kernels.md for the dispatch-mode trade-offs.
# The plan's block_expert array is int32 and carries no gradient: the
# backward rules return a ``None`` cotangent for it and reference-math
# gradients for the float operands.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ragged_mm_p(scale, interpret, xs, be, w):
    return _ragged_mm_pallas(xs, be, w, scale=scale, interpret=interpret)


def _ragged_mm_fwd(scale, interpret, xs, be, w):
    return _ragged_mm_p(scale, interpret, xs, be, w), (xs, be, w)


def _ragged_mm_bwd(scale, interpret, res, g):
    xs, be, w = res
    _, vjp = jax.vjp(
        lambda xs_, w_: ref.ragged_expert_matmul_ref(xs_, be, w_), xs, w)
    dxs, dw = vjp(g)
    return dxs, None, dw


_ragged_mm_p.defvjp(_ragged_mm_fwd, _ragged_mm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ragged_mm_lora_p(scale, interpret, xs, be, w, a, b):
    return _ragged_mm_pallas(xs, be, w, a, b, scale=scale,
                             interpret=interpret)


def _ragged_mm_lora_fwd(scale, interpret, xs, be, w, a, b):
    return (_ragged_mm_lora_p(scale, interpret, xs, be, w, a, b),
            (xs, be, w, a, b))


def _ragged_mm_lora_bwd(scale, interpret, res, g):
    xs, be, w, a, b = res
    _, vjp = jax.vjp(
        lambda xs_, w_, a_, b_: ref.ragged_expert_matmul_ref(
            xs_, be, w_, a_, b_, scale), xs, w, a, b)
    dxs, dw, da, db = vjp(g)
    return dxs, None, dw, da, db


_ragged_mm_lora_p.defvjp(_ragged_mm_lora_fwd, _ragged_mm_lora_bwd)


def ragged_expert_matmul(kcfg: KernelConfig, xs, block_expert, w,
                         a=None, b=None, *, scale: float = 0.0):
    """Differentiable grouped (segment) LoRA matmul over the ragged
    buffer: xs (N,K); block_expert (N//bm,) int32; w (E,K,H); optional
    per-expert LoRA a (E,K,r) / b (E,r,H).  Contraction/output dims with
    tiny divisors fall back to the reference, like every other matmul op
    here — no degenerate compiled tiles."""
    K = xs.shape[1]
    H = w.shape[-1]
    if use_pallas(kcfg) and not (_degenerate(K, 256) or _degenerate(H, 256)):
        interp = resolve_interpret(kcfg)
        if a is None:
            return _ragged_mm_p(float(scale), interp, xs, block_expert, w)
        return _ragged_mm_lora_p(float(scale), interp, xs, block_expert,
                                 w, a, b)
    return ref.ragged_expert_matmul_ref(xs, block_expert, w, a, b, scale)
