"""Sparse Mixture-of-Experts FFN with adaptive top-k (the FLAME substrate).

TPU-native, static-shape dispatch (GShard/Switch style):

  1. router logits -> softmax probabilities, top-``k_i`` selection where
     ``k_i`` is the *client budget* (FLAME Eq. 5: clients activate fewer
     experts than the server default ``k``);
  2. capacity-based one-hot dispatch tensors (tokens that overflow an
     expert's capacity fall back to the residual stream — standard GShard
     semantics, required because XLA needs static shapes);
  3. expert computation as stacked einsums over an expert-sharded weight
     tensor (expert parallelism on the ``model`` mesh axis — GSPMD emits
     all-to-alls around the dispatch/combine einsums);
  4. per-expert **activation counts** are returned so the federated server
     can form the activation-aware aggregation weights (Eq. 6);
  5. a learnable **rescaler** multiplies the combined expert output to
     re-calibrate magnitude under partial activation (Eq. 5's ``s_i``).

Compute genuinely scales with ``k_i`` via the capacity
``C = ceil(k_i * S / E * capacity_factor)`` — this is the paper's central
FLOPs-adaptivity claim, preserved in static-shape form.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..kernels import backend as kernel_backend
from .layers import dense_init, lora_expert_einsum


class MoEAux(NamedTuple):
    """Auxiliary routing stats threaded out of the forward pass."""

    activation_counts: jnp.ndarray   # (E,) float — # tokens routed to expert j
    total_tokens: jnp.ndarray        # () float — tokens processed (= S_i unit)
    load_balance_loss: jnp.ndarray   # () float — Switch aux loss (optional use)


def init_moe(key, cfg) -> dict:
    m = cfg.moe
    d = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(kr, (d, m.num_experts), dtype, scale=0.1),
        "experts": {
            "w1": dense_init(k1, (m.num_experts, d, m.d_expert), dtype),
            "w3": dense_init(k2, (m.num_experts, d, m.d_expert), dtype),
            "w2": dense_init(k3, (m.num_experts, m.d_expert, d), dtype),
        },
    }
    if m.num_shared_experts > 0:
        dsh = m.d_shared_expert or m.d_expert * m.num_shared_experts
        ka, kb, kc = jax.random.split(ks, 3)
        p["shared"] = {
            "w1": dense_init(ka, (d, dsh), dtype),
            "w3": dense_init(kb, (d, dsh), dtype),
            "w2": dense_init(kc, (dsh, d), dtype),
        }
    return p


def _capacity_from_assignments(n_assign: float, num_experts: int,
                               factor: float) -> int:
    """Expert-queue capacity for ``n_assign`` total (token, expert)
    assignments: ceil-ish with slack ``factor``, rounded up to a multiple
    of 8 for lane-friendly layouts."""
    c = int(n_assign * factor / num_experts) + 1
    return max(8, ((c + 7) // 8) * 8)


def _capacity(tokens: int, num_experts: int, k: int, factor: float) -> int:
    return _capacity_from_assignments(tokens * k, num_experts, factor)


def dense_capacity(tokens: int) -> int:
    """Per-expert queue capacity of the dense no-drop mode: one expert can
    receive at most one copy of each token, so C = the group's token count
    (rounded up for lane layouts) guarantees zero overflow."""
    return max(8, ((tokens + 7) // 8) * 8)


DISPATCH_MODES = ("capacity", "dense", "ragged")


def resolve_dispatch(dispatch, no_drop: bool) -> str:
    """Normalise the dispatch-mode spelling: an explicit ``dispatch``
    string wins; otherwise the legacy ``no_drop`` flag selects between
    GShard-capacity (False) and dense no-drop (True)."""
    if dispatch is None:
        return "dense" if no_drop else "capacity"
    assert dispatch in DISPATCH_MODES, dispatch
    return dispatch


def topk_routing(router_logits: jnp.ndarray, k: int):
    """Reference routing: softmax over experts then iterative top-k.

    router_logits: (T, E).  Returns (weights (T,E), mask (T,E)) where mask is
    the 0/1 selection and weights are the softmax probs of the selected
    experts renormalised to sum to 1 per token.

    Delegates to ``repro.kernels.ref.topk_router_ref`` — the single source
    of truth for routing semantics (the Pallas router kernel is validated
    against the same oracle).
    """
    from ..kernels.ref import topk_router_ref
    weights, mask, _ = topk_router_ref(router_logits, k)
    return weights, mask


def _one_hot_expert_ffn(p: dict, cfg, xg: jnp.ndarray, weights, mask, *,
                        dispatch: str, k: Optional[int],
                        n_assign: Optional[int], lora: dict,
                        lora_scale: float, shard_fns: dict):
    """GShard one-hot dispatch + expert FFN + combine.

    ``xg``: (G, Tg, D) grouped tokens; ``weights``/``mask``: (G, Tg, E).
    ``dispatch="capacity"`` drops tokens past
    ``C = ceil(assignments·cf / E)``; ``dispatch="dense"`` is the
    loss-free variant with ``C = Tg`` (worst-case padding).  Capacity
    scales with the TOTAL expert assignments: on the adaptive path a
    mixed batch's ``n_assign`` follows sum(k_i), so constrained slots
    genuinely shrink the expert workload (FLAME's FLOPs-adaptivity, per
    slot instead of per client)."""
    m = cfg.moe
    G, Tg, D = xg.shape
    E = m.num_experts
    sf = shard_fns
    if dispatch == "dense":
        C = dense_capacity(Tg)
    elif n_assign is not None:
        C = _capacity_from_assignments(n_assign, E, m.capacity_factor)
    else:
        C = _capacity(Tg, E, k, m.capacity_factor)
    # position of each token within its expert's per-group queue
    pos_in_expert = (jnp.cumsum(mask, axis=1) - 1.0) * mask       # (G, Tg, E)
    keep = (pos_in_expert < C) & (mask > 0)
    pos_oh = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), C,
                            dtype=xg.dtype)                       # (G,Tg,E,C)
    dispatch_oh = pos_oh * keep[..., None].astype(xg.dtype)
    combine = dispatch_oh * weights[..., None].astype(xg.dtype)
    if "dispatch" in sf:
        # keep the dispatch one-hot group-sharded with the FULL expert dim —
        # the E→model restriction happens on the (much smaller) slot tensor,
        # where it is a local slice.  Without this GSPMD all-gathers the
        # (G,Tg,E,C) one-hot per layer (EXPERIMENTS.md §Perf H1).
        dispatch_oh = sf["dispatch"](dispatch_oh)
    if "combine" in sf:
        # the combine one-hot IS E→model-sharded so the combine einsum
        # contracts the local expert slice and all-reduces the (G,Tg,D)
        # token output — 3.7× less traffic than gathering expert outputs
        combine = sf["combine"](combine)

    # gather token slots: (G, E, C, D) — the expert all-to-all boundary
    slots = jnp.einsum("gtec,gtd->gecd", dispatch_oh, xg)
    if "slots" in sf:
        slots = sf["slots"](slots)

    # ----- expert FFN (SwiGLU) with per-expert LoRA -----
    # kernels=cfg.kernels: on the pallas backend each matmul is the fused
    # base+bypass lora_matmul_experts kernel (docs/kernels.md)
    gate = lora_expert_einsum(slots, p["experts"]["w1"], lora.get("w1"),
                              lora_scale, kernels=cfg.kernels)
    up = lora_expert_einsum(slots, p["experts"]["w3"], lora.get("w3"),
                            lora_scale, kernels=cfg.kernels)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    eo = lora_expert_einsum(h, p["experts"]["w2"], lora.get("w2"),
                            lora_scale, kernels=cfg.kernels)

    eo = sf["slots"](eo) if "slots" in sf else eo
    out = jnp.einsum("gtec,gecd->gtd", combine, eo)               # (G, Tg, D)
    if "out" in sf:
        out = sf["out"](out)
    return out


def _ragged_expert_ffn(p: dict, cfg, x2d: jnp.ndarray, weights, mask, *,
                       budget: int, max_k: int, lora: dict,
                       lora_scale: float):
    """Sort-based ragged dispatch + expert FFN + combine (loss-free AND
    budget-proportional — kernels/ragged_dispatch.py).

    ``x2d``: (T, D) flat tokens; ``weights``/``mask``: (T, E);
    ``budget``: static worst-case assignment count; ``max_k``: static
    per-token selection cap.  The grouped matmul dispatches through the
    kernel backend (Pallas forward + reference backward on the pallas
    path); the gather and the combine are XLA gathers."""
    from ..kernels import ragged_dispatch as ragged_mod
    plan = ragged_mod.ragged_plan(mask, weights, budget=budget, max_k=max_k)
    xs = ragged_mod.ragged_gather(x2d, plan.src, plan.valid)

    def mm(inp, key):
        lp = lora.get(key)
        return kernel_backend.ragged_expert_matmul(
            cfg.kernels, inp, plan.block_expert, p["experts"][key],
            None if lp is None else lp["a"], None if lp is None else lp["b"],
            scale=lora_scale)

    gate = mm(xs, "w1")
    up = mm(xs, "w3")
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    eo = mm(h, "w2")
    return ragged_mod.ragged_combine(eo, plan.rows, plan.wrank)


def apply_moe(p: dict, cfg, x: jnp.ndarray, *, k,
              rescaler: Optional[jnp.ndarray] = None,
              lora: Optional[dict] = None, lora_scale: float = 0.0,
              deterministic: bool = True,
              rng: Optional[jax.Array] = None,
              num_groups: int = 1,
              shard_fns: Optional[dict] = None,
              slot_mask: Optional[jnp.ndarray] = None,
              no_drop: bool = False,
              dispatch: Optional[str] = None):
    """x: (B, S, D) -> (out (B,S,D), MoEAux).

    ``k`` is static (client budget k_i): an ``int`` applied to every token,
    or a length-``B`` tuple of per-row budgets (the serving engine's
    per-slot adaptive k — each row of the batch decodes at its own budget,
    see serving/engine.py).  A uniform tuple collapses to the int path, so
    the two spellings are bit-identical.  ``rescaler`` is the FLAME
    learnable scalar s_i (None => 1.0): a scalar, or a length-``B`` vector
    applied per row (the engine's per-slot rescaler).

    ``slot_mask``: optional dynamic (B,) 0/1 vector — rows at 0 route to
    ZERO experts (their budget is masked, not just their output), so they
    cannot occupy expert-queue capacity that real rows need.  The serving
    engine masks its free slots this way; without it, garbage rows in a
    slotted decode batch could evict real tokens under GShard capacity.
    A (B, S) mask applies per token — the suffix-prefill path masks
    ragged suffix-length padding columns the same way.

    ``dispatch`` selects among three token-dispatch strategies (see
    docs/kernels.md §MoE dispatch modes for the trade-off table):

    * ``"capacity"`` (the default) — GShard one-hot dispatch with
      ``C = ceil(assignments·cf / E)``; tokens past an expert's capacity
      fall back to the residual stream.  The training mode.
    * ``"dense"`` — the same one-hot dispatch with ``C = T_g``: loss-free
      (no token can EVER drop, so co-batched rows cannot change a row's
      result) but every expert pays worst-case padding — compute no
      longer follows the activated budget.
    * ``"ragged"`` — sort-based dispatch (kernels/ragged_dispatch.py):
      loss-free like ``"dense"`` AND compute-proportional to the
      activated budget (``T·k``, or ``S·sum(slot_k)`` per-slot) like
      ``"capacity"``.  Routes globally (requires ``num_groups == 1``,
      no grouped-sharding path yet) — the serving engine's default.

    ``no_drop`` is the legacy alias: ``True`` means ``dispatch="dense"``
    (an explicit ``dispatch`` wins).

    ``num_groups``: GShard routing groups.  Capacity and the dispatch/
    combine one-hots are *per-group* ``(G, T_g, E, C_g)`` so when the token
    dim is batch-sharded over the ``data`` mesh axis (G = a multiple of the
    data parallelism) the dispatch tensor stays shard-local and only the
    slot tensor crosses the mesh (the expert all-to-all).  G=1 reproduces
    the global-routing reference semantics used by the CPU tests.
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E = m.num_experts
    G = num_groups
    assert T % G == 0, (T, G)
    Tg = T // G
    xg = x.reshape(G, Tg, D)
    dispatch = resolve_dispatch(dispatch, no_drop)

    if isinstance(k, (tuple, list)):
        assert len(k) == B, (len(k), B)
        if len(set(k)) == 1 and slot_mask is None:
            k = int(k[0])                 # uniform budgets: static-int path
    adaptive = isinstance(k, (tuple, list)) or slot_mask is not None
    if adaptive:
        # per-row budgets need global routing: grouped dispatch would need
        # per-group capacities (the serving decode path runs G == 1)
        assert G == 1, "per-slot k requires num_groups == 1"
        k_slots = (tuple(int(v) for v in k)
                   if isinstance(k, (tuple, list)) else (int(k),) * B)
        max_k = max(k_slots)

    logits = jnp.einsum("gtd,de->gte", xg, p["router"])           # (G, Tg, E)
    if not deterministic and m.router_jitter > 0 and rng is not None:
        logits = logits + m.router_jitter * jax.random.normal(
            rng, logits.shape, logits.dtype)
    if adaptive:
        # per-token budgets have no fused kernel — route through the
        # reference adaptive router (the expert matmuls below still
        # dispatch per backend); row b's S tokens all use budget k[b],
        # zeroed where slot_mask marks the row inactive
        from ..kernels.ref import adaptive_topk_router_ref
        k_tok = jnp.repeat(jnp.asarray(k_slots, jnp.int32), S)
        if slot_mask is not None:
            if slot_mask.ndim == 2:        # per-token (B, S) validity
                k_tok = k_tok * slot_mask.reshape(T).astype(jnp.int32)
            else:
                k_tok = k_tok * jnp.repeat(slot_mask.astype(jnp.int32), S)
        weights, mask, counts = adaptive_topk_router_ref(
            logits.reshape(T, E), k_tok, max_k)                   # (T, E) fp32
    else:
        # backend-dispatched fused router (softmax + top-k + the FLAME Eq. 6
        # activation counts); reference path = ref.topk_router_ref, whose
        # routing semantics are identical to topk_routing below
        weights, mask, counts = kernel_backend.router(
            cfg.kernels, logits.reshape(T, E), k)                 # (T, E) fp32
    # Switch-style load-balance aux loss (kept for completeness; the paper
    # fine-tunes with the router frozen so this is usually unused).
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    lb = E * jnp.mean(probs.mean((0, 1))
                      * mask.reshape(G, Tg, E).mean((0, 1))) * E
    sf = shard_fns or {}
    le = (lora or {}).get("experts", {})

    if dispatch == "ragged":
        assert G == 1, "ragged dispatch routes globally (num_groups == 1)"
        assert not sf, "ragged dispatch has no grouped-sharding path yet"
        budget = S * sum(k_slots) if adaptive else T * k
        out = _ragged_expert_ffn(p, cfg, xg.reshape(T, D), weights, mask,
                                 budget=budget,
                                 max_k=(max_k if adaptive else k),
                                 lora=le, lora_scale=lora_scale)
        out = out.reshape(G, Tg, D)
    else:
        n_assign = S * sum(k_slots) if adaptive else None
        out = _one_hot_expert_ffn(p, cfg, xg, weights.reshape(G, Tg, E),
                                  mask.reshape(G, Tg, E), dispatch=dispatch,
                                  k=None if adaptive else k,
                                  n_assign=n_assign, lora=le,
                                  lora_scale=lora_scale, shard_fns=sf)

    if rescaler is not None:
        r = rescaler.astype(out.dtype)
        if r.ndim == 1 and r.shape[0] == B:
            # per-slot rescaler s_i (serving): row b's tokens scale by r[b]
            r = jnp.repeat(r, S).reshape(G, Tg, 1)
        out = out * r

    # ----- shared experts (always active; Qwen2-MoE style) -----
    if "shared" in p:
        from .layers import apply_ffn
        ls = (lora or {}).get("shared")
        out = out + apply_ffn(p["shared"], xg, ls, lora_scale,
                              kernels=cfg.kernels)

    aux = MoEAux(activation_counts=counts,
                 total_tokens=jnp.asarray(T, jnp.float32),
                 load_balance_loss=lb)
    return out.reshape(B, S, D), aux
