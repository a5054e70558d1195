"""Shared neural-net building blocks (pure JAX, no flax).

Parameters are plain nested dicts of jnp arrays.  Every ``apply``-style
function takes the param sub-tree as its first argument.  LoRA adapters are
threaded through as optional parallel sub-trees (``None`` = no adapter) so the
same forward code serves frozen-base fine-tuning and plain inference.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------
# initialisers
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float = 1.0):
    """Truncated-normal fan-in init (matches common LLM init)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / (fan_in ** 0.5)
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rms_norm(scale: jnp.ndarray, x: jnp.ndarray, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def gated_rms_norm(scale: jnp.ndarray, x: jnp.ndarray, z: jnp.ndarray,
                   eps: float = 1e-6):
    """Mamba-2 output norm: RMSNorm(x * silu(z))."""
    x = x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    return rms_norm(scale, x, eps)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)          # (head_dim/2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                          # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# LoRA-aware dense application
# --------------------------------------------------------------------------

def lora_dense(x: jnp.ndarray, w: jnp.ndarray,
               lp: Optional[dict], scale: float,
               kernels=None) -> jnp.ndarray:
    """y = x @ W (+ (x @ A) @ B * scale when a LoRA adapter is present).

    ``x``: (..., d_in); ``w``: (d_in, d_out); ``lp``: {"a": (d_in, r),
    "b": (r, d_out)} or None.  The correction is added unmerged (the
    federated protocol keeps A/B separate so the server can aggregate
    them).

    On the pallas path (``kernels``, a KernelConfig) an adapter-carrying
    projection runs the fused ``repro.kernels.lora_matmul`` kernel over the
    flattened token dim.  The reference path keeps the kernel's numerics
    contract (kernels/ref.py): fp32 math, one cast at the end.
    """
    if lp is None:
        return x @ w
    from ..kernels import backend as kernel_backend
    if kernel_backend.use_pallas(kernels):
        xf = x.reshape(-1, x.shape[-1])
        y = kernel_backend.lora_matmul(kernels, xf, w, lp["a"],
                                       lp["b"], scale=scale)
        return y.reshape(x.shape[:-1] + (w.shape[-1],))
    f32 = jnp.float32
    xf = x.astype(f32)
    y = xf @ w.astype(f32)
    y = y + ((xf @ lp["a"].astype(f32)) @ lp["b"].astype(f32)) * scale
    return y.astype(x.dtype)


def lora_expert_einsum(x: jnp.ndarray, w: jnp.ndarray,
                       lp: Optional[dict], scale: float,
                       kernels=None) -> jnp.ndarray:
    """Per-expert matmul over stacked expert weights.

    ``x``: (E, C, d_in) or grouped (G, E, C, d_in) expert-major token slots;
    ``w``: (E, d_in, d_out);
    ``lp``: {"a": (E, d_in, r), "b": (E, r, d_out)} or None.

    ``kernels`` (a :class:`repro.configs.base.KernelConfig`) selects the
    implementation: on the pallas path an adapter-carrying matmul runs the
    fused ``repro.kernels.lora_matmul.lora_matmul_experts`` kernel (base +
    LoRA bypass in one VMEM pass).  The reference path and the no-adapter
    case use plain einsums — both accumulate in fp32 and cast once, the
    same numerics contract as the kernel.
    """
    from ..kernels import backend as kernel_backend
    from ..kernels import ref as kernel_ref

    if lp is not None and x.ndim == 3:
        if kernel_backend.use_pallas(kernels):
            return kernel_backend.lora_matmul_experts(
                kernels, x, w, lp["a"], lp["b"], scale=scale)
        return kernel_ref.lora_matmul_experts_ref(x, w, lp["a"], lp["b"],
                                                  scale)

    f32 = jnp.float32
    if x.ndim == 4:
        # grouped path: keep the G axis un-reshaped in the reference
        # einsums — G is the data-sharded routing-group dim and GSPMD must
        # see it intact (the pallas fold below is a per-device kernel view)
        if lp is not None and kernel_backend.use_pallas(kernels):
            G, E, C, K = x.shape
            xt = jnp.swapaxes(x, 0, 1).reshape(E, G * C, K)
            y = kernel_backend.lora_matmul_experts(
                kernels, xt, w, lp["a"], lp["b"], scale=scale)
            return jnp.swapaxes(y.reshape(E, G, C, -1), 0, 1)
        # operands upcast to fp32, as in kernels/ref.py (XLA:CPU has no
        # bf16 x bf16 -> f32 dot for this batched 4-D contraction)
        xf = x.astype(f32)
        y = jnp.einsum("geci,eio->geco", xf, w.astype(f32))
        if lp is not None:
            xa = jnp.einsum("geci,eir->gecr", xf, lp["a"].astype(f32))
            y = y + jnp.einsum("gecr,ero->geco", xa,
                               lp["b"].astype(f32)) * scale
        return y.astype(x.dtype)
    y = jnp.einsum("eci,eio->eco", x, w, preferred_element_type=f32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# SwiGLU FFN
# --------------------------------------------------------------------------

def init_ffn(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": dense_init(k1, (d_model, d_ff), dtype),
        "w3": dense_init(k2, (d_model, d_ff), dtype),
        "w2": dense_init(k3, (d_ff, d_model), dtype),
    }


def apply_ffn(p: dict, x: jnp.ndarray, lora: Optional[dict] = None,
              lora_scale: float = 0.0, kernels=None) -> jnp.ndarray:
    lg = (lora or {})
    gate = lora_dense(x, p["w1"], lg.get("w1"), lora_scale, kernels=kernels)
    up = lora_dense(x, p["w3"], lg.get("w3"), lora_scale, kernels=kernels)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    return lora_dense(h, p["w2"], lg.get("w2"), lora_scale, kernels=kernels)


# --------------------------------------------------------------------------
# misc
# --------------------------------------------------------------------------

def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap <= 0:
        return x
    return jnp.tanh(x / cap) * cap
