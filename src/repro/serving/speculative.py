"""Self-speculative decoding: the adaptive-k model drafts for itself.

FLAME's global SMoE weights serve any activation budget, so the model is
its own draft model: the engine drafts a window of W tokens per slot at
``draft_k`` (default 1, the cheapest budget on the ragged dispatch path),
then verifies the whole window in ONE full-k multi-token decode step
(models.decode_step with S = W+1, teacher-forcing the drafts against the
cache), and accepts a prefix via the standard speculative sampling
rejection rule:

  accept draft ``d_i`` with probability ``min(1, p_i[d_i] / q_i[d_i])``
  (``q`` = draft distribution, ``p`` = target distribution, both under
  the engine's sampler transform); on the first rejection resample from
  the corrected residual ``norm(max(p_i - q_i, 0))``; if all W drafts
  survive, emit a bonus token from ``p_W``.

This yields output *distributionally identical* to plain full-k decoding
(Leviathan et al.) — and for the greedy sampler the rule degenerates to
exact-match acceptance with an argmax resample, i.e. token-for-token
identity with plain greedy decode (tests/test_speculative.py).

KV correctness: the draft steps never write the cache at all — their
K/V live in a small per-round window buffer (models.draft_window), the
verify step attends the cache pre-write and deposits full-k K/V at the
window's positions (attention.verify_attention), and the engine then
rolls each row back to its first rejected position
(``pool.truncate_to``), so the cache after a round is exactly what a
straight decode of the accepted prefix would have produced.

Launch economics: a round is THREE device launches regardless of W —
the draft window is a single jitted ``lax.scan`` over W steps (sampling
in-graph, so no per-step host sync), verify is one multi-token step, and
the rejection rule is one vmapped call over all slots.  A plain decode
pass over the same W+1 tokens costs W+1 launches + host syncs.  Just as
important, each in-scan draft step skips the cache read-modify-write
that dominates a small-batch decode step: the prefix is gathered once
(paged) or read in place (slotted) and stays read-only, so a draft step
costs a fraction of a real decode step even before launch savings.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..models import model as model_lib
from .sampler import SamplerConfig, sample_from_probs, sampler_probs


@dataclass(frozen=True)
class SpeculativeConfig:
    """``window``: drafts per round (W); ``draft_k``: the draft budget."""
    window: int = 4
    draft_k: int = 1

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"speculative window must be >= 1, "
                             f"got {self.window}")
        if self.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {self.draft_k}")


def _verify_window(key: jax.Array, draft_tokens: jnp.ndarray,
                   draft_logits: jnp.ndarray, target_logits: jnp.ndarray,
                   sc: SamplerConfig):
    """The rejection rule for one slot's drafted window (pure jnp).

    ``draft_tokens``: (W,) int; ``draft_logits``: (W, V) — the draft
    model's logits each token was sampled from; ``target_logits``:
    (W+1, V) — the full-k logits at every window position (the last row
    conditions on all W drafts and feeds the bonus token).

    Returns ``(tokens (W+1,), n_emitted, n_accepted)``: the first
    ``n_emitted = n_accepted + 1`` entries of ``tokens`` are the round's
    output (accepted drafts + the resampled/bonus token).  Vmappable;
    with ``sc.kind == "greedy"`` the distributions are one-hot and the
    outcome is key-independent.
    """
    W = draft_tokens.shape[0]
    q = sampler_probs(draft_logits, sc)                   # (W, V)
    p = sampler_probs(target_logits, sc)                  # (W+1, V)
    iw = jnp.arange(W)
    p_d = p[iw, draft_tokens]
    q_d = q[iw, draft_tokens]
    key_u, key_last = jax.random.split(key)
    u = jax.random.uniform(key_u, (W,))
    # u < min(1, p/q)  <=>  u * q < p  (divide-free; q == 0 accepts iff
    # p > 0, the natural limit — a greedy draft mismatch has p_d == 0)
    accept = u * q_d < p_d
    n_acc = jnp.cumprod(accept.astype(jnp.int32)).sum()   # accepted prefix
    # corrected residual at the first rejected position (unused when all
    # accepted); the p-fallback guards the p <= q everywhere edge, which
    # is unreachable for a real rejection but keeps the math total
    ridx = jnp.minimum(n_acc, W - 1)
    resid = jnp.clip(p[ridx] - q[ridx], 0.0)
    rs = resid.sum()
    resid = jnp.where(rs > 0.0, resid / rs, p[ridx])
    last_probs = jnp.where(n_acc == W, p[W], resid)
    last = sample_from_probs(key_last, last_probs)
    out = jnp.concatenate(
        [draft_tokens, jnp.zeros((1,), draft_tokens.dtype)])
    out = out.at[n_acc].set(last.astype(out.dtype))
    return out, n_acc + 1, n_acc


verify_window = jax.jit(_verify_window, static_argnames=("sc",))


@partial(jax.jit, static_argnames=("W",))
def _fold_event_keys(base_keys: jnp.ndarray, events: jnp.ndarray,
                     W: int) -> jnp.ndarray:
    """keys[j, b] = fold_in(base_keys[b], events[b] + j) for j < W —
    the per-slot draw keys for a draft window, built in one launch."""
    def row(j):
        return jax.vmap(jax.random.fold_in)(base_keys, events + j)
    return jnp.stack([row(j) for j in range(W)])


class SpeculativeDecoder:
    """Draft/verify driver bound to one :class:`~.engine.ServingEngine`.

    Owns the extra compiled steps: the fused draft window (the engine's
    decode step recompiled with every slot at ``draft_k`` and scanned W
    times in-graph, sampling included), the verify step (full tier k,
    S = W+1 tokens), and the vmapped rejection rule.  One compile per
    distinct window width, mirroring the prefill buckets.  The engine
    calls :meth:`round` wherever it would have called ``_decode_once``.
    """

    def __init__(self, engine, spec: SpeculativeConfig):
        cfg = engine.cfg
        if not cfg.moe.enabled:
            raise ValueError(
                "self-speculation drafts the same weights at a reduced "
                "expert budget; a non-MoE model has no cheaper draft")
        if not spec.draft_k <= cfg.moe.num_experts:
            raise ValueError(f"draft_k={spec.draft_k} > "
                             f"{cfg.moe.num_experts} experts")
        if any(cfg.layer_kind(p) != "attn"
               for p in range(cfg.pattern_period)):
            raise ValueError(
                "speculative decoding requires attention-only models: "
                "SSM state is cumulative and cannot roll back to a "
                "rejected position")
        if engine.dispatch == "capacity":
            raise ValueError(
                "speculative verify requires a loss-free dispatch mode "
                "(ragged/dense): capacity dispatch makes the verify "
                "distribution depend on co-batched rows")
        if 0 < cfg.attention_window < engine.slot_len:
            raise ValueError(
                "speculative rollback requires a non-wrapping KV cache: "
                f"attention_window={cfg.attention_window} < slot_len="
                f"{engine.slot_len} would alias window positions")
        self.eng = engine
        self.window = spec.window
        self.draft_k = spec.draft_k
        self._np_keys = {}                 # rid -> host copy of base key
        # slot -> window base position (the last verified cache_pos)
        # while that slot's draft window is OPEN: set when the round
        # advances positions for the draft/verify, cleared as each
        # slot's window resolves (advance or truncate).  A preemption
        # swapping the slot out mid-window rolls back through
        # rollback_open so the swap state never carries draft positions.
        self._open: Dict[int, int] = {}
        self._draft_fn = self._build_draft_window_fn()
        self._verify_fn = engine._build_verify_fn()
        self._draft_trainable = engine._build_draft_trainable(spec.draft_k)
        sc = engine._sampler
        self._reject_fn = jax.jit(jax.vmap(
            lambda key, d, ql, tl: _verify_window(key, d, ql, tl, sc)))

    # ------------------------------------------------------- compiled pieces
    def _build_draft_window_fn(self):
        """W draft steps fused into one jitted ``lax.scan``
        (models.draft_window): each step decodes every slot at the scalar
        ``draft_k``, samples the next token in-graph (greedy argmax, or
        the engine's sampler with per-slot per-step keys), and feeds it
        back — so a whole draft window is ONE device launch + ONE host
        sync instead of W, and the cache is only ever READ (the window's
        K/V ride in a small scan-carried buffer; verify overwrites those
        positions with full-k K/V anyway).  One compile per distinct
        window width (``keys.shape[0]``).  Returns
        ``(draft_logits (W,B,V) fp32, draft_tokens (W,B) int32)``.
        """
        eng = self.eng
        cfg, dispatch, sc = eng.cfg, eng.dispatch, eng._sampler
        dk = self.draft_k
        page_span = eng.pool.attn_len if eng.paged else None

        def pick(logits, keys_j):
            if sc.kind == "greedy":
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            probs = sampler_probs(logits, sc)
            return jax.vmap(sample_from_probs)(keys_j,
                                               probs).astype(jnp.int32)

        if eng.paged:
            @jax.jit
            def _draft(params, trainable, cache, tok0, pos0, tables, keys):
                return model_lib.draft_window(
                    cfg, params, cache, tok0, pos0, keys, sample_fn=pick,
                    window=keys.shape[0], trainable=trainable, k=dk,
                    block_table=tables, page_span=page_span,
                    dispatch=dispatch)
        else:
            @jax.jit
            def _draft(params, trainable, cache, tok0, pos0, keys):
                return model_lib.draft_window(
                    cfg, params, cache, tok0, pos0, keys, sample_fn=pick,
                    window=keys.shape[0], trainable=trainable, k=dk,
                    dispatch=dispatch)
        return _draft

    def _base_key(self, rid: int) -> np.ndarray:
        """Host copy of the request's PRNG base key, memoized — pulling
        it from the device once per request instead of once per round
        (a per-slot sync every round would dominate the host budget)."""
        nk = self._np_keys.get(rid)
        if nk is None:
            nk = np.asarray(self.eng._req_key(rid))
            self._np_keys[rid] = nk
        return nk

    def _draw_keys(self, active: List[int], W: int) -> jnp.ndarray:
        """(W, B) draw keys for the draft window from each active slot's
        event counter (inactive rows get dummy keys; their draws steer
        only their own garbage tokens).  Greedy needs no randomness —
        a zero placeholder keeps the compiled signature uniform."""
        eng = self.eng
        B = eng.num_slots
        if eng._sampler.kind == "greedy":
            return jnp.zeros((W, B, 2), jnp.uint32)
        base = np.zeros((B, 2), np.uint32)
        events = np.zeros((B,), np.int32)
        for s in active:
            a = eng._active[s]
            base[s] = self._base_key(a.req.rid)
            events[s] = a.events
            a.events += W
        return _fold_event_keys(jnp.asarray(base), jnp.asarray(events), W)

    def _reject_keys(self, active: List[int]) -> jnp.ndarray:
        """(B, 2) keys for the rejection rule's accept/resample draws,
        batched into one fold launch.  Greedy verify is key-independent
        (one-hot p and q make every accept test and resample
        deterministic), so zeros suffice there."""
        eng = self.eng
        B = eng.num_slots
        if eng._sampler.kind == "greedy":
            return jnp.zeros((B, 2), jnp.uint32)
        base = np.zeros((B, 2), np.uint32)
        events = np.zeros((B,), np.int32)
        for s in active:
            a = eng._active[s]
            base[s] = self._base_key(a.req.rid)
            events[s] = a.events
            a.events += 1
        return _fold_event_keys(jnp.asarray(base), jnp.asarray(events), 1)[0]

    # ------------------------------------------------------------------
    def _round_window(self, active: List[int]) -> int:
        """Largest safe W this round: every active slot must have room
        for the verify step's top position (``pos + W <= attn_len - 1``)
        and for ``W + 1`` emitted tokens within its budget."""
        eng = self.eng
        W = self.window
        for s in active:
            a = eng._active[s]
            W = min(W,
                    eng.pool.attn_len - 1 - int(eng.pool.cache_pos[s]),
                    a.max_new - len(a.tokens) - 1)
        return W

    def round(self, report) -> None:
        """One draft/verify iteration over every active slot; falls back
        to a plain decode step when no window fits."""
        eng = self.eng
        pool = eng.pool
        active = [s for s, a in enumerate(eng._active) if a is not None]
        W = self._round_window(active)
        if W < 1:
            eng._decode_once(report)
            return
        active_mask = jnp.asarray(
            [a is not None for a in eng._active], jnp.float32)
        pos0 = pool.cache_pos.copy()                       # (B,)
        first = eng._last_tok.copy()                       # (B, 1)
        for s in active:
            self._open[s] = int(pos0[s])

        # ---- draft: one fused launch covering W cheap read-only steps ----
        tr = eng._tracer
        t0 = time.perf_counter()
        with tr.span("engine.draft", cat="engine",
                     args={"window": W, "active": len(active)}):
            if eng.paged:
                # the draft never writes pages — the tables are passed
                # only for the one-shot prefix gather — but the VERIFY
                # step writes positions pos0 .. pos0+W-1, so allocate
                # every window position's block up front (covered by the
                # admit-time reservation)
                for _ in range(W):
                    pool.prepare_decode(active)
                    pool.advance(active)
                extra = (pool.tables(),)
            else:
                for _ in range(W):
                    pool.advance(active)
                extra = ()
            qs, toks = self._draft_fn(
                eng.params, self._draft_trainable, pool.cache,
                jnp.asarray(first), jnp.asarray(pos0), *extra,
                self._draw_keys(active, W))
            d_toks = np.asarray(toks)                      # (W, B) — sync
        dt = time.perf_counter() - t0
        report.draft_step_s.append(dt)
        report.draft_hist.observe(dt * 1e3)

        # ---- verify + reject: one full-k step over the W+1 window
        # tokens, then the vmapped rejection rule over all slots ----
        t0 = time.perf_counter()
        with tr.span("engine.verify", cat="engine", args={"window": W}):
            extra = ()
            if eng.paged:
                pool.prepare_decode(active)                # pos0 + W
                extra = (pool.tables(),)
            verify_in = np.concatenate([first, d_toks.T], axis=1)
            lv, cache = self._verify_fn(
                eng.params, eng._decode_trainable, pool.cache,
                jnp.asarray(verify_in), jnp.asarray(pos0), active_mask,
                *extra)
            pool.cache = cache
            out_toks, n_emit, n_acc = self._reject_fn(
                self._reject_keys(active), jnp.asarray(d_toks.T),
                jnp.moveaxis(qs, 0, 1), lv)
            out_toks = np.asarray(out_toks)                # (B, W+1) — sync
            n_emit, n_acc = np.asarray(n_emit), np.asarray(n_acc)
        dt = time.perf_counter() - t0
        report.verify_step_s.append(dt)
        report.verify_hist.observe(dt * 1e3)

        for s in active:
            a = eng._active[s]
            acc = int(n_acc[s])
            emitted = [int(t) for t in out_toks[s, :int(n_emit[s])]]
            a.tokens.extend(emitted)
            a.token_t.extend([eng._now()] * len(emitted))
            eng._last_tok[s, 0] = emitted[-1]
            report.spec_drafted += W
            report.spec_accepted += acc
            self._open.pop(s, None)        # window resolved below
            if acc == W:
                # position pos0+W holds the ACCEPTED last draft's K/V —
                # keep it and advance past it (the bonus token's K/V is
                # written by the next step, exactly as in plain decode)
                pool.advance([s])
            else:
                pool.truncate_to(s, int(pos0[s]) + acc + 1)
            if len(a.tokens) >= a.max_new or pool.slot_full(s):
                eng._finish(s, report)
        report.spec_rounds += 1

    def rollback_open(self, slot: int) -> None:
        """Preemption safety: if ``slot`` is being swapped out while its
        draft window is open (cache positions advanced past the last
        verified token for the in-flight draft/verify), roll the row
        back to the window base and forget the draft state — swap_out
        then captures exactly the verified prefix, and the resumed
        request re-enters decoding as if the round never started.

        A no-op in the normal engine loop: :meth:`round` is atomic with
        respect to admission (``_admit`` runs between rounds), so every
        window it opens is resolved before a preemption can fire.  The
        hook is what makes that atomicity a guarantee rather than an
        accident of control flow."""
        base = self._open.pop(slot, None)
        if base is not None:
            self.eng.pool.truncate_to(slot, base)
