"""Sort-based ragged MoE dispatch: loss-free AND sum(k)-proportional.

The GShard one-hot dispatch (models/moe_layer.py) buys static shapes with a
per-expert capacity ``C``: every expert's token queue is padded to ``C``
and tokens past it are DROPPED.  The serving engine's loss-free mode pins
``C`` to the whole token count, so nothing ever drops — but then every
expert pays worst-case padding and the FLOPs-adaptivity of a small expert
budget k (FLAME's whole point) is gone.

This module is the third way: a **counting-sort** dispatch (the
static-shape form of argsort-by-expert + segment offsets).  Assignments
are laid out expert-major in one ragged buffer of ``N`` rows:

  =========  ============================================================
  segment    expert ``e`` owns rows ``[off[e], off[e] + count[e])``,
             its segment padded up to a multiple of ``block_m`` so
             matmul tiles never straddle two experts;
  src        ``src[i]``: which token sits in buffer row ``i``
             (tokens ascending within each expert — a stable sort by
             expert key, computed with cumsums instead of a sort);
  blocks     ``block_expert[i]``: which expert's weights row-block ``i``
             multiplies (the segment-offset lookup, precomputed);
  inverse    ``rows[t, j]``: the buffer row holding token ``t``'s rank-j
             assignment, with combine weight ``wrank[t, j]`` — the
             combine is a per-token gather, no scatter races.
  =========  ============================================================

``N`` is **static**: the worst-case assignment count (``T * k``, or
``S * sum(slot_k)`` for per-slot budgets) plus one block of padding per
expert — so expert compute is proportional to the *activated budget*, not
``num_tokens × num_experts``.  Every token the router selects is routed —
no capacity limit, no dropping — and each token's output depends only on
its own row: co-batched rows provably cannot change results, which is why
the serving engine runs this mode by default (docs/kernels.md).

The row movement is XLA's own gather: :func:`ragged_gather` copies token
rows into the buffer (``xs[i] = x[src[i]] * valid[i]``) and
:func:`ragged_combine` sums each token's ranks back
(``out[t] = sum_j wrank[t,j] * eo[rows[t,j]]``).  A row gather is pure
data movement with no compute to fuse into it, so it is left to XLA, and
both differentiate as plain jnp (their vjps are scatter-adds).  The
compute sits in one Pallas kernel, :func:`ragged_expert_matmul`, the
grouped (segment) LoRA matmul: row block ``i`` multiplies
``w[block_expert[i]]`` (+ the LoRA bypass), its expert picked through the
scalar-prefetched plan.  Its jnp oracle is
:func:`repro.kernels.ref.ragged_expert_matmul_ref` and its ``custom_vjp``
(kernel forward, reference backward) lives in :mod:`repro.kernels.backend`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row-block size of the ragged buffer: every expert segment is padded to a
# multiple of this, so grouped-matmul tiles never straddle experts.  8 is
# the fp32 sublane minimum — the smallest padding that still tiles.
BLOCK_M = 8


def ragged_rows(budget: int, num_experts: int,
                block_m: int = BLOCK_M) -> int:
    """Static ragged-buffer size for a worst-case assignment ``budget``:
    the budget rounded up to blocks, plus one block of segment padding per
    expert (each expert's count rounds up independently)."""
    return -(-budget // block_m) * block_m + num_experts * block_m


class RaggedPlan(NamedTuple):
    """Integer dispatch plan (plus the differentiable combine weights).

    Built once per MoE layer call from the router outputs; consumed by
    the gather, the grouped matmul and the combine.  All layout arrays are
    int32 and carry no gradient; ``wrank`` is the per-rank combine weight
    and is differentiable back to the router weights."""

    src: jnp.ndarray           # (N,)  token id per buffer row
    valid: jnp.ndarray         # (N,)  0/1 — padding rows are 0
    block_expert: jnp.ndarray  # (N // block_m,) expert id per row block
    rows: jnp.ndarray          # (T, max_k) buffer row per (token, rank)
    wrank: jnp.ndarray         # (T, max_k) combine weight per rank (f32)


def ragged_plan(mask: jnp.ndarray, weights: jnp.ndarray, *, budget: int,
                max_k: int, block_m: int = BLOCK_M) -> RaggedPlan:
    """Counting-sort dispatch plan from router outputs.

    ``mask``/``weights``: (T, E) selection one-hots and renormalised
    combine weights (``ref.topk_router_ref`` layout); ``budget``: static
    worst-case total assignments (>= ``mask.sum()`` always); ``max_k``:
    static per-token selection cap (``rows``' second dim).

    The forward plan scatters each selected (token, expert) pair to its
    segment slot ``off[e] + rank_of_t_within_e``; the inverse plan reads
    the same expression at each token's top-``max_k`` experts.  Ranks past
    a token's own budget have ``wrank == 0`` and point at row 0 — they
    gather a live row times zero, never influencing anything.
    """
    T, E = mask.shape
    N = ragged_rows(budget, E, block_m)
    nb = N // block_m
    m = mask.astype(jnp.float32)
    counts = m.sum(axis=0).astype(jnp.int32)                       # (E,)
    padded = -(-counts // block_m) * block_m
    ends = jnp.cumsum(padded)
    off = ends - padded                                            # exclusive
    # rank of token t within expert e's segment (valid where selected)
    pos = (jnp.cumsum(m, axis=0) - 1.0).astype(jnp.int32)          # (T, E)
    slot = off[None, :] + pos
    dst = jnp.where(m > 0, slot, N)                # unselected -> dropped
    tok = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, E))
    src = jnp.zeros((N,), jnp.int32).at[dst].set(tok, mode="drop")
    valid = jnp.zeros((N,), jnp.int32).at[dst].set(1, mode="drop")
    starts = jnp.arange(nb, dtype=jnp.int32) * block_m
    block_expert = jnp.minimum(
        (ends[None, :] <= starts[:, None]).sum(axis=1), E - 1
    ).astype(jnp.int32)
    # inverse plan: a token's selected experts are exactly its nonzero
    # combine weights, in descending-weight order (the router's own rank
    # order — selection is nested, so rank order never matters for the sum)
    top_w, top_idx = jax.lax.top_k(weights, max_k)
    rank_valid = (top_w > 0).astype(weights.dtype)
    rows = jnp.take_along_axis(slot, top_idx, axis=1)
    rows = jnp.where(rank_valid > 0, rows, 0).astype(jnp.int32)
    wrank = top_w * rank_valid
    return RaggedPlan(src=src, valid=valid, block_expert=block_expert,
                      rows=rows, wrank=wrank)


# ==========================================================================
# gather / grouped matmul (Pallas) / combine
# ==========================================================================

def ragged_gather(x: jnp.ndarray, src: jnp.ndarray,
                  valid: jnp.ndarray) -> jnp.ndarray:
    """x: (T, D); src, valid: (N,) int32 -> xs (N, D) with
    ``xs[i] = x[src[i]] * valid[i]`` (padding rows zero)."""
    return x[src] * valid.astype(x.dtype)[:, None]


def _matmul_kernel(be_ref, x_ref, w_ref, o_ref, *, scale: float):
    del be_ref, scale
    xf = x_ref[...].astype(jnp.float32)
    y = jnp.dot(xf, w_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _matmul_lora_kernel(be_ref, x_ref, w_ref, a_ref, b_ref, o_ref, *,
                        scale: float):
    del be_ref
    xf = x_ref[...].astype(jnp.float32)
    y = jnp.dot(xf, w_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    xa = jnp.dot(xf, a_ref[0].astype(jnp.float32),
                 preferred_element_type=jnp.float32)
    y = y + jnp.dot(xa, b_ref[0].astype(jnp.float32),
                    preferred_element_type=jnp.float32) * scale
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def ragged_expert_matmul(xs: jnp.ndarray, block_expert: jnp.ndarray,
                         w: jnp.ndarray, a: Optional[jnp.ndarray] = None,
                         b: Optional[jnp.ndarray] = None, *,
                         scale: float = 0.0, interpret: bool = True):
    """Grouped (segment) matmul over the ragged buffer.

    xs: (N, K); block_expert: (N // bm,) int32; w: (E, K, H);
    a/b: optional per-expert LoRA factors (E, K, r) / (E, r, H).
    Row block ``i`` computes ``xs_i @ w[be[i]]`` (+ LoRA bypass) — the
    expert index comes in through the scalar-prefetched block spec, the
    MegaBlocks-style grouped GEMM.  fp32 accumulate, one cast at the end
    (the suite-wide numerics contract)."""
    N, K = xs.shape
    nb = block_expert.shape[0]
    assert N % nb == 0, (N, nb)
    bm = N // nb
    H = w.shape[-1]
    in_specs = [
        pl.BlockSpec((bm, K), lambda i, be: (i, 0)),
        pl.BlockSpec((1, K, H), lambda i, be: (be[i], 0, 0)),
    ]
    if a is None:
        kernel = functools.partial(_matmul_kernel, scale=scale)
        args = (block_expert, xs, w)
    else:
        r = a.shape[-1]
        in_specs += [
            pl.BlockSpec((1, K, r), lambda i, be: (be[i], 0, 0)),
            pl.BlockSpec((1, r, H), lambda i, be: (be[i], 0, 0)),
        ]
        kernel = functools.partial(_matmul_lora_kernel, scale=scale)
        args = (block_expert, xs, w, a, b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, H), lambda i, be: (i, 0)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, H), xs.dtype),
        interpret=interpret, name="ragged_expert_matmul",
    )(*args)


def ragged_combine(eo: jnp.ndarray, rows: jnp.ndarray,
                   wrank: jnp.ndarray) -> jnp.ndarray:
    """eo: (N, D); rows: (T, max_k) int32; wrank: (T, max_k) ->
    out (T, D) with ``out[t] = sum_j wrank[t, j] * eo[rows[t, j]]`` — a
    per-token gather (ranks past the token's budget carry weight 0 and
    point at row 0), summed in fp32 and cast once."""
    g = eo[rows].astype(jnp.float32)                   # (T, max_k, D)
    out = (g * wrank[..., None].astype(jnp.float32)).sum(axis=1)
    return out.astype(eo.dtype)
