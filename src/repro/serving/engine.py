"""Continuous-batching serving engine with per-slot adaptive k.

The engine drives ONE jitted decode step over the whole slot pool every
iteration.  Requests at different depths coexist because the per-slot
``cache_pos`` vector is threaded into attention (scatter write + per-row
validity mask); requests of different tiers coexist because the MoE layer
takes a static per-slot expert-budget tuple (``slot_k``): premium slots
decode at full k, constrained slots at k=1–2, and the dispatch capacity —
hence the expert FLOPs — follows ``sum(slot_k)`` instead of
``num_slots * k_max`` (models/moe_layer.py).  The FLAME rescaler is
applied per slot the same way: each tier's trained ``s_i`` is stacked into
a ``(n_periods, num_slots)`` leaf that the scan slices per layer.

The KV cache behind the slots is block-paged by default
(``kv_layout="paged"``, kv_cache.BlockPool): attention K/V live in a
shared pool of fixed-size blocks, each row carries a block table, and
admission is gated on the request's projected block need — device KV
bytes follow tokens in flight instead of ``num_slots × slot_len``.  The
PR 3 monolithic pool survives as ``kv_layout="slotted"``, the
differential-test oracle (tests/test_paged_kv.py proves the two are
token-for-token identical).

Engine loop (one ``step()``):

  1. requests whose arrival time has passed join the scheduler queue;
  2. the scheduler packs waiting requests into free slots (FIFO per
     tier, block-availability predicate when paged); admitted requests
     are prefilled — batched by prompt length, padded to power-of-two
     batch buckets to bound recompiles — and their caches installed into
     the pool (``write``), emitting the first generated token (TTFT);
  3. one decode step advances every active slot by a token; finished
     sequences (budget reached / slot full) are evicted and their slots
     (and KV blocks) released.

Sampling is greedy (argmax) by default, or any :class:`SamplerConfig`
(temperature / top-p; serving/sampler.py) with per-request PRNG keys
folded from ``seed`` and the request id, so a request's draws are
independent of what shares its batch.  A request may instead carry
``forced`` continuation tokens, which the engine feeds back while
accumulating their NLL — teacher-forced quality evaluation through the
serving path.  ``speculative=SpeculativeConfig(...)`` switches decode to
self-speculative rounds: draft W tokens per slot at ``draft_k``, verify
in one full-k multi-token step, accept by the rejection rule and roll
rejected K/V back (serving/speculative.py).

Production traffic controls (docs/serving.md is the operations guide):

* ``prefix_cache=True`` (paged only) — prompts are content-matched
  against the pool's block index at admission
  (``BlockPool.attach_prefix``), so requests sharing a system prompt
  hold its KV blocks once (refcounts + copy-on-write in
  kv_cache.BlockPool) — and, for attention-only models, prefill runs
  over the UNMATCHED SUFFIX only (``model.prefill_suffix``), attending
  back into the attached prefix pages: prefill compute follows unseen
  tokens instead of prompt length, and admission buckets matched rows
  by suffix length, so a flash crowd of long shared-head prompts
  collapses into small buckets (cold misses run the plain exact-length
  prefill, identical to a cold engine's launches).  Token-for-token
  invisible: the suffix step reproduces
  the cold logits to float tolerance, and the greedy differential in
  tests/test_traffic.py locks exact token identity against a cold
  engine.
* ``slo_ms={tier_k: target_ms}`` — per-tier TTFT targets; the scheduler
  switches to earliest-deadline-first admission and ``summary()`` gains
  per-tier p50/p99 TTFT, tokens/s and SLO attainment.
* ``preemption=True`` (paged, needs ``slo_ms``) — when a waiter is past
  its TTFT deadline and blocked on blocks, the engine swaps out the
  active request with the most lenient deadline (host copy via
  ``BlockPool.swap_out``), frees its blocks AND reservation, and lets
  the victim resume later through normal re-admission — token-for-token
  identical to an uncontended run, because the swap round-trips the
  row's exact KV/SSM state and the per-request PRNG event counter lives
  in the preserved ``_ActiveSlot``.  Composes with speculative decoding:
  preemption fires between draft/verify rounds, and a swap-out of a slot
  with an open draft window first rolls the window back to the last
  verified token (``SpeculativeDecoder.rollback_open``), so the swapped
  state never carries unverified draft positions.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import model as model_lib
from ..obs.expert_load import ExpertLoadTracker
from ..obs.metrics import Histogram, MetricsRegistry
from ..obs.trace import NULL_TRACER, PID_ENGINE, PID_REQUESTS, Tracer
from .kv_cache import BlockPool, SlotPool
from .sampler import SamplerConfig, sample_token
from .scheduler import Completion, Request, Scheduler
from .speculative import SpeculativeConfig, SpeculativeDecoder
from .workload import percentile

PyTree = Any


def _log_softmax_np(x: np.ndarray) -> np.ndarray:
    m = x.max(axis=-1, keepdims=True)
    lse = np.log(np.exp(x - m).sum(axis=-1, keepdims=True)) + m
    return x - lse


def _bucket(n: int) -> int:
    """Next power of two >= n (prefill batch buckets)."""
    b = 1
    while b < n:
        b *= 2
    return b


def _tier_salt(k: Optional[int]) -> bytes:
    """Prefix-digest salt for an expert-budget tier.

    A block's K/V depends on the tokens AND on the MoE expert budget
    ``k`` the writer ran at — every layer's hidden states (hence K/V)
    change with the number of experts mixed in.  Salting the digest
    chain with the tier keeps equal prompts served at different ``k``
    from ever aliasing each other's numerically different pages.
    """
    return b"" if k is None else str(int(k)).encode()


@dataclass
class _ActiveSlot:
    req: Request
    tokens: List[int]
    nll: float
    admitted: float
    first_token: float
    max_new: int
    # per-request PRNG event counter: every sampler draw folds
    # (seed, rid, events) into its key, so draws are keyed by the
    # request's own draw order — independent of co-batched rows
    events: int = 0
    # times this request was swapped out mid-decode; capped by the
    # engine's max_preemptions so repeated preemption cannot livelock
    preemptions: int = 0
    # tracer time of the most recent swap-out (the swapped_out span runs
    # from here to the swap_in that resumes the request)
    swap_t: float = 0.0
    # engine time at which each token in ``tokens`` was drawn
    token_t: List[float] = field(default_factory=list)


def _pct_ms(xs: Sequence[float], q: float) -> Optional[float]:
    """Percentile in milliseconds, None (not NaN) on an empty sample —
    keeps ``summary()`` JSON-safe for zero-completion runs."""
    return percentile(list(xs), q) * 1e3 if xs else None


@dataclass
class ServingReport:
    """Everything a serving run produced, plus latency/throughput views."""
    completions: List[Completion]
    decode_step_s: List[float] = field(default_factory=list)
    prefill_s: List[float] = field(default_factory=list)
    wall_s: float = 0.0
    num_slots: int = 0
    slot_k: Tuple[Optional[int], ...] = ()
    # speculative-decode accounting (zero when speculation is off)
    draft_step_s: List[float] = field(default_factory=list)
    verify_step_s: List[float] = field(default_factory=list)
    spec_rounds: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    # production-traffic accounting
    preemptions: int = 0                     # swap-outs over the run
    # tokens the prefill steps actually computed: full prompts on a cold
    # engine, unmatched suffixes only under suffix-prefill — the bench's
    # direct measure of prefill compute saved by the prefix cache
    prefill_tokens: int = 0
    prefix: Dict[str, int] = field(default_factory=dict)
    slo_ms: Optional[Dict[Optional[int], float]] = None
    # step-time histograms (ms; repro.obs.metrics.Histogram) — always
    # populated (one bisect per step), the source for summary()'s
    # p50/p99 and for registry snapshots
    decode_hist: Histogram = field(default_factory=Histogram)
    prefill_hist: Histogram = field(default_factory=Histogram)
    draft_hist: Histogram = field(default_factory=Histogram)
    verify_hist: Histogram = field(default_factory=Histogram)
    # expert-load telemetry snapshot (engine expert_telemetry=True)
    expert_load: Optional[Dict[str, Any]] = None

    def tokens_by_rid(self) -> Dict[int, np.ndarray]:
        """Generated tokens keyed by request id."""
        return {c.rid: c.tokens for c in self.completions}

    def per_tier(self) -> Dict[str, Dict[str, float]]:
        """Per-tier latency/throughput: p50/p99 TTFT, tokens/s and (when
        ``slo_ms`` targets are set) the fraction of requests whose TTFT
        met the tier's target.  Keys are ``str(k)`` (``"0"`` = non-MoE)."""
        by_tier: Dict[int, List[Completion]] = {}
        for c in self.completions:
            by_tier.setdefault(c.k, []).append(c)
        out: Dict[str, Dict[str, float]] = {}
        for k, cs in sorted(by_tier.items()):
            ttfts = [c.ttft for c in cs]
            row = {
                "n_requests": len(cs),
                "ttft_p50_ms": percentile(ttfts, 50) * 1e3,
                "ttft_p99_ms": percentile(ttfts, 99) * 1e3,
                "gen_tokens_per_s": (sum(c.n_generated for c in cs)
                                     / max(self.wall_s, 1e-9)),
            }
            slo = (self.slo_ms or {}).get(k)
            if slo is not None:
                row["slo_attainment"] = (
                    sum(t * 1e3 <= slo for t in ttfts) / len(cs))
            out[str(k)] = row
        return out

    def summary(self) -> Dict[str, Any]:
        """Flat run summary (JSON-safe): aggregate latency/throughput,
        per-tier breakdown, and speculation / prefix-cache / preemption
        accounting when those features were on."""
        n = len(self.completions)
        gen = sum(c.n_generated for c in self.completions)
        ttfts = [c.ttft for c in self.completions]
        lats = [c.latency for c in self.completions]
        # zero-completion runs yield a well-formed summary: every
        # percentile/mean field is None (never NaN — json.dumps(nan)
        # emits invalid JSON), every count/rate field a real 0
        out = {
            "n_requests": n,
            "gen_tokens": gen,
            "wall_s": self.wall_s,
            "requests_per_s": n / max(self.wall_s, 1e-9),
            "gen_tokens_per_s": gen / max(self.wall_s, 1e-9),
            "ttft_p50_ms": _pct_ms(ttfts, 50),
            "ttft_p95_ms": _pct_ms(ttfts, 95),
            "ttft_p99_ms": _pct_ms(ttfts, 99),
            "latency_p50_ms": _pct_ms(lats, 50),
            "latency_p95_ms": _pct_ms(lats, 95),
            "decode_step_ms_mean": (float(np.mean(self.decode_step_s)) * 1e3
                                    if self.decode_step_s else None),
            "decode_step_ms_p50": self.decode_hist.percentile(50),
            "decode_step_ms_p99": self.decode_hist.percentile(99),
            "decode_steps": len(self.decode_step_s),
            "prefill_tokens": self.prefill_tokens,
            "truncated": sum(c.truncated for c in self.completions),
            "per_tier": self.per_tier(),
        }
        if self.preemptions:
            out["preemptions"] = self.preemptions
        if self.prefix:
            out["prefix_cache"] = dict(self.prefix)
        if self.spec_rounds:
            out.update({
                "spec_rounds": self.spec_rounds,
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted,
                "acceptance_rate": (self.spec_accepted
                                    / max(self.spec_drafted, 1)),
                "draft_step_ms_mean": float(np.mean(self.draft_step_s)) * 1e3,
                "draft_step_ms_p50": self.draft_hist.percentile(50),
                "draft_step_ms_p99": self.draft_hist.percentile(99),
                "verify_step_ms_mean": (float(np.mean(self.verify_step_s))
                                        * 1e3),
                "verify_step_ms_p50": self.verify_hist.percentile(50),
                "verify_step_ms_p99": self.verify_hist.percentile(99),
            })
        if self.expert_load is not None:
            out["expert_load"] = {
                k: self.expert_load[k]
                for k in ("steps", "gini", "entropy", "hot_expert",
                          "assignments_total")}
        return out


class ServingEngine:
    """Continuous batching over a :class:`SlotPool` with per-slot k.

    ``slot_k``: per-slot expert budgets (tuple of ints, len ``num_slots``);
    defaults to ``cfg.moe.top_k`` everywhere; ignored (None) for non-MoE
    models.  The tuple is STATIC — it fixes the compiled step's dispatch
    capacity — so tiers are a property of the pool, and the scheduler
    matches requests to slots of their tier.

    ``lora``: optional unmerged adapter tree (serving without merging);
    ``rescaler_by_k``: optional ``{k: rescaler tree}`` — each tier's
    trained FLAME ``s_i``, applied per slot during decode and per batch
    during prefill.

    ``kv_layout``: ``"paged"`` (default) backs the slots with a
    :class:`BlockPool` — attention K/V in ``num_blocks`` shared
    ``block_size``-token blocks, admission gated on each request's
    projected block need, so device KV bytes follow tokens in flight;
    ``"slotted"`` keeps the PR 3 monolithic :class:`SlotPool` (the
    differential-test oracle).  Both layouts are token-for-token
    identical (tests/test_paged_kv.py).  Models with no attention layers
    (pure SSM) have O(1)/request state and always use the slotted pool.

    ``dispatch`` (default ``"ragged"``): MoE token-dispatch mode.  With
    capacity-limited GShard dispatch (``"capacity"``), which tokens
    overflow an expert depends on which rows share a prefill bucket or
    decode step, so a request's OUTPUT would depend on the admission
    schedule — serving must not let batching change results (it is also
    what makes the paged-vs-slotted differential well-defined).  Both
    loss-free modes guarantee schedule-independence:

    * ``"ragged"`` — sort-based dispatch (kernels/ragged_dispatch.py):
      row-isolated by construction AND expert compute follows
      ``sum(slot_k)``, so constrained slots genuinely decode cheaper.
      The default.
    * ``"dense"`` — one-hot dispatch with capacity pinned to the token
      count (the pre-ragged loss-free mode, kept as the differential
      oracle): worst-case padding, compute flat in ``slot_k``.
    * ``"capacity"`` — the capacity-limited throughput mode the
      adaptive-k bench measures; batching MAY change results.

    ``no_drop`` is the legacy alias (``True`` -> ``"dense"``, ``False``
    -> ``"capacity"``); leave both unset for the ragged default.

    Production traffic knobs (see the module docstring and
    docs/serving.md): ``prefix_cache`` (paged-only block sharing for
    prompts, plus suffix-only prefill on attention-only models — only
    the unmatched prompt suffix is computed, attending into the attached
    prefix pages), ``slo_ms`` (per-tier TTFT targets in milliseconds,
    keyed by tier ``k`` — switches admission to
    earliest-deadline-first), ``preemption`` (paged-only decode swap-out
    under deadline pressure; requires ``slo_ms``; composes with
    ``speculative`` — an open draft window is rolled back before the
    swap) and ``max_preemptions`` (per-request swap-out cap — the
    anti-livelock bound).

    Observability knobs (repro.obs; docs/observability.md) — all
    opt-in-pay, the defaults cost one attribute check per event site:
    ``tracer`` (a :class:`repro.obs.Tracer`) records request-lifecycle
    spans (queued/prefill/decode + swap instants per rid) and live
    engine-loop spans (``engine.iteration`` > ``engine.admit`` >
    ``engine.prefill``, ``engine.decode``, ``engine.sample``, and
    ``engine.draft``/``engine.verify`` under speculation) that also land
    in a running ``jax.profiler`` trace, exports Chrome trace-event
    JSON, and is flight-dumped if ``run()`` raises;
    ``metrics`` (a :class:`repro.obs.MetricsRegistry`) receives engine
    counters and registers the pool and scheduler as snapshot-time
    sources; ``expert_telemetry=True`` (MoE, non-speculative) compiles
    the decode step to also return per-expert activation counts, which
    feed ``report.expert_load`` host-side — no kernel changes.
    """

    def __init__(self, cfg, params: PyTree, *, lora: Optional[PyTree] = None,
                 rescaler_by_k: Optional[Dict[int, PyTree]] = None,
                 num_slots: int = 8, slot_len: int = 64,
                 slot_k: Optional[Sequence[int]] = None,
                 kv_layout: str = "paged", block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 no_drop: Optional[bool] = None,
                 dispatch: Optional[str] = None,
                 sampler: Optional[SamplerConfig] = None,
                 speculative: Optional[SpeculativeConfig] = None,
                 prefix_cache: bool = False,
                 preemption: bool = False,
                 slo_ms: Optional[Dict[Optional[int], float]] = None,
                 max_preemptions: int = 4,
                 seed: int = 0,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 expert_telemetry: bool = False):
        assert cfg.num_codebooks == 0, "serving engine: text models only"
        assert kv_layout in ("paged", "slotted"), kv_layout
        if dispatch is None:
            dispatch = ("ragged" if no_drop is None
                        else ("dense" if no_drop else "capacity"))
        assert dispatch in ("ragged", "dense", "capacity"), dispatch
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.slot_len = slot_len
        has_attn = any(cfg.layer_kind(p) == "attn"
                       for p in range(cfg.pattern_period))
        self.paged = kv_layout == "paged" and has_attn
        if prefix_cache and not self.paged:
            raise ValueError(
                "prefix_cache needs the paged KV layout (block sharing "
                "has no meaning in the slotted pool)")
        if preemption:
            if not self.paged:
                raise ValueError(
                    "preemption needs the paged KV layout (swap-out "
                    "frees blocks, not whole slots)")
            if not slo_ms:
                raise ValueError(
                    "preemption needs slo_ms targets: victim selection "
                    "is driven by TTFT deadlines")
        if cfg.moe.enabled:
            resolved = tuple(int(v) for v in (
                slot_k if slot_k is not None
                else (cfg.moe.top_k,) * num_slots))
            assert len(resolved) == num_slots, (resolved, num_slots)
            assert all(1 <= v <= cfg.moe.num_experts for v in resolved)
            self.slot_k: Tuple[Optional[int], ...] = resolved
            self._moe_k: Optional[Tuple[int, ...]] = resolved
        else:
            assert slot_k is None, "slot_k is meaningless without MoE"
            self.slot_k = (None,) * num_slots
            self._moe_k = None

        self._lora = lora
        self._rescaler_by_k = rescaler_by_k
        self._decode_trainable = self._build_decode_trainable()

        if self.paged:
            self.pool = BlockPool(cfg, num_slots, slot_len,
                                  block_size=block_size,
                                  num_blocks=num_blocks,
                                  prefix_cache=prefix_cache)
            # per-tier block quotas (proportional to the tier's slot
            # share, floored at one full request): a tier may exceed its
            # quota only while no OTHER tier has requests waiting, so a
            # flood of long premium requests can saturate an idle pool
            # but can never starve economy admission once economy
            # traffic queues up — freed blocks then flow to the
            # under-quota tier (tests/test_serving.py adversarial traces)
            counts: Dict[Optional[int], int] = {}
            for t in self.slot_k:
                counts[t] = counts.get(t, 0) + 1
            self._tier_quota = {
                t: max(self.pool.blocks_per_slot,
                       self.pool.num_blocks * c // num_slots)
                for t, c in counts.items()}
            self._tier_reserved = {t: 0 for t in counts}
        else:
            self.pool = SlotPool(cfg, num_slots, slot_len)
        self.prefix_cache = prefix_cache
        # suffix-only cached prefill: prefill computes only the prompt
        # suffix past the matched prefix span, attending back into the
        # attached pages (model.prefill_suffix).  Attention-only models
        # only — an SSM layer's state is cumulative over the whole
        # prompt, so a mixed model falls back to full prefill (the
        # blocks still share; only the compute saving is lost).
        self._use_suffix = prefix_cache and all(
            cfg.layer_kind(p) == "attn" for p in range(cfg.pattern_period))
        self.slo_ms = dict(slo_ms) if slo_ms else None
        self._preemption = preemption
        self._max_preemptions = max_preemptions
        # rid -> (pool swap state, _ActiveSlot, last sampled token):
        # everything a preempted request needs to resume bit-exactly
        self._swapped: Dict[int, Tuple[Dict[str, Any], _ActiveSlot,
                                       int]] = {}
        if self.slo_ms:
            self.scheduler = Scheduler(
                policy="slo",
                tier_slo_s={t: ms / 1e3 for t, ms in self.slo_ms.items()})
        else:
            self.scheduler = Scheduler()
        self._active: List[Optional[_ActiveSlot]] = [None] * num_slots
        self._last_tok = np.zeros((num_slots, 1), np.int32)

        self.dispatch = dispatch
        self.no_drop = dispatch != "capacity"    # loss-free?
        self._sampler = sampler or SamplerConfig()
        self._seed = seed
        self._req_keys: Dict[int, jax.Array] = {}

        # ---- observability (all opt-in-pay; see repro.obs) ----
        self._tracer = tracer if tracer is not None else NULL_TRACER
        self._metrics = metrics
        if expert_telemetry and not cfg.moe.enabled:
            raise ValueError("expert_telemetry needs an MoE model: a "
                             "dense model routes nothing to observe")
        if expert_telemetry and speculative is not None:
            raise ValueError(
                "expert_telemetry under speculative decoding is not "
                "supported yet: the fused draft window does not surface "
                "activation counts")
        self._expert_telemetry = bool(expert_telemetry)
        self._expert_tracker = (ExpertLoadTracker(cfg.moe.num_experts)
                                if self._expert_telemetry else None)
        if metrics is not None:
            metrics.add_source(self.pool.publish)
            metrics.add_source(self.scheduler.publish)
            self._ctr_completions = metrics.counter("serving.completions")
            self._ctr_tokens = metrics.counter("serving.gen_tokens")
            self._ctr_admitted = metrics.counter("serving.admitted")
            self._ctr_preempt = metrics.counter("serving.preemptions")
            self._ctr_draws = metrics.counter("serving.sample.draws")
            self._ctr_prefills = metrics.counter("serving.prefill.calls")
            self._ctr_prefill_rows = metrics.counter("serving.prefill.rows")
            self._ctr_steps = metrics.counter("serving.decode.steps")
        if self._tracer.enabled:
            self._tracer.process_name(PID_ENGINE, "serving-engine")
            self._tracer.thread_name(PID_ENGINE, 0, "engine loop")
            self._tracer.process_name(PID_REQUESTS, "requests")

        @partial(jax.jit, static_argnames=("k",))
        def _prefill_fn(params, trainable, prompts, real, k):
            if dispatch == "ragged" and cfg.moe.enabled:
                # ragged dispatch is row-isolated by construction (each
                # token's output depends only on its own assignments), so
                # prefill runs ONE routing group per bucket — no per-row
                # group workaround, and bucket-padding rows cannot touch
                # real rows
                logits, cache = model_lib.prefill(
                    cfg, params, prompts, trainable=trainable, k=k,
                    cache_len=slot_len, dispatch="ragged")
            elif dispatch == "dense" and cfg.moe.enabled:
                # loss-free one-hot prefill, one routing group PER ROW
                # with capacity = the row's own token count: a row's
                # result cannot depend on co-batched rows (bucket-padding
                # rows isolate themselves), and dispatch cost stays
                # linear in the bucket instead of quadratic (C would
                # otherwise be the whole bucket's token count)
                logits, cache = model_lib.prefill(
                    cfg, params, prompts, trainable=trainable, k=k,
                    cache_len=slot_len, num_groups=prompts.shape[0],
                    dispatch="dense")
            else:
                logits, cache = model_lib.prefill(
                    cfg, params, prompts, trainable=trainable, k=k,
                    cache_len=slot_len,
                    slot_mask=real if cfg.moe.enabled else None)
            return logits[:, 0].astype(jnp.float32), cache

        suffix_attn = self.pool.attn_len if self.paged else 0
        suffix_bs = self.pool.block_size if self.paged else 1

        @partial(jax.jit, static_argnames=("k",))
        def _suffix_prefill_fn(params, trainable, cache, tokens, tables,
                               prefix_len, suffix_len, real, k):
            # cache is READ-ONLY here (not donated): the suffix step
            # gathers the attached prefix pages per row and returns the
            # new K/V as a contiguous piece — BlockPool.write scatters
            # it host-side, exactly like the cold path.  Shapes depend
            # only on (batch bucket, suffix bucket, page-span bucket, k):
            # prefix_len and suffix_len are traced per-row vectors.
            # ``tables`` arrives SLICED to the pow-2 block span covering
            # the group's live prefix+suffix — the page gather (the
            # launch-dominating cost at short suffixes) follows the data
            # actually attended, not the slot's full capacity; every
            # live prefix position is < the span by construction and
            # anything past it was masked invalid anyway.
            suffix_span = min(tables.shape[1] * suffix_bs, suffix_attn)
            if dispatch == "ragged" and cfg.moe.enabled:
                # row-isolated by construction — one routing group, and
                # dispatch cost follows sum(suffix_len · k), the
                # resource-proportionality point of suffix prefill
                logits, piece = model_lib.prefill_suffix(
                    cfg, params, tokens, prefix_len, suffix_len, cache,
                    tables, page_span=suffix_span, trainable=trainable,
                    k=k, dispatch="ragged")
            elif dispatch == "dense" and cfg.moe.enabled:
                logits, piece = model_lib.prefill_suffix(
                    cfg, params, tokens, prefix_len, suffix_len, cache,
                    tables, page_span=suffix_span, trainable=trainable,
                    k=k, num_groups=tokens.shape[0], dispatch="dense")
            else:
                # capacity dispatch: per-TOKEN validity, not per-row —
                # bucket-padding columns inside real rows must not
                # consume expert capacity or a request's output would
                # depend on what shares its bucket
                mask = None
                if cfg.moe.enabled:
                    S = tokens.shape[1]
                    mask = (real[:, None] *
                            (jnp.arange(S)[None, :]
                             < suffix_len[:, None]).astype(jnp.float32))
                logits, piece = model_lib.prefill_suffix(
                    cfg, params, tokens, prefix_len, suffix_len, cache,
                    tables, page_span=suffix_span, trainable=trainable,
                    k=k, slot_mask=mask, dispatch=dispatch)
            return logits[:, 0].astype(jnp.float32), piece

        self._decode_fn = self._build_decode_fn(
            self._moe_k, return_counts=self._expert_telemetry)
        self._prefill_fn = _prefill_fn
        self._suffix_prefill_fn = _suffix_prefill_fn
        self._spec = (SpeculativeDecoder(self, speculative)
                      if speculative is not None else None)

    # -------------------------------------------------------- compiled steps
    def _build_decode_fn(self, moe_k: Optional[Tuple[int, ...]],
                         return_counts: bool = False):
        """One jitted single-token decode step over the whole pool.

        The pool cache is donated: the engine replaces its reference with
        the returned cache every step, and donation lets XLA update the
        slot arrays in place instead of copying the whole pool per token.
        ``active`` masks free slots out of MoE routing (budget 0), so
        garbage rows can never consume expert capacity a real request
        needs.  ``moe_k`` is baked in — the speculative decoder compiles
        its own fused draft window with every slot at ``draft_k``.

        ``return_counts`` (expert telemetry) additionally returns the
        step's per-expert activation counts ``{posN: (n_periods, E)}`` —
        a distinct compiled executable, built only when the engine was
        constructed with ``expert_telemetry=True`` so the default step
        pays nothing.
        """
        cfg, dispatch = self.cfg, self.dispatch
        page_span = self.pool.attn_len if self.paged else None
        if self.paged:
            @partial(jax.jit, donate_argnums=(2,))
            def _decode_fn(params, trainable, cache, tokens, pos, active,
                           tables):
                out = model_lib.decode_step(
                    cfg, params, cache, tokens, pos, trainable=trainable,
                    k=moe_k, slot_mask=active if cfg.moe.enabled else None,
                    block_table=tables, page_span=page_span,
                    dispatch=dispatch, return_counts=return_counts)
                return (out[0][:, 0].astype(jnp.float32),) + out[1:]
        else:
            @partial(jax.jit, donate_argnums=(2,))
            def _decode_fn(params, trainable, cache, tokens, pos, active):
                out = model_lib.decode_step(
                    cfg, params, cache, tokens, pos, trainable=trainable,
                    k=moe_k, slot_mask=active if cfg.moe.enabled else None,
                    dispatch=dispatch, return_counts=return_counts)
                return (out[0][:, 0].astype(jnp.float32),) + out[1:]
        return _decode_fn

    def _build_verify_fn(self):
        """The speculative verify step: full tier k over ``(B, W+1)``
        teacher-forced window tokens, returning logits at EVERY window
        position.  Shape-driven: one compile per distinct window width
        (bounded by the speculative window, like the prefill buckets)."""
        cfg, dispatch, moe_k = self.cfg, self.dispatch, self._moe_k
        page_span = self.pool.attn_len if self.paged else None
        if self.paged:
            @partial(jax.jit, donate_argnums=(2,))
            def _verify_fn(params, trainable, cache, tokens, pos, active,
                           tables):
                logits, new_cache = model_lib.decode_step(
                    cfg, params, cache, tokens, pos, trainable=trainable,
                    k=moe_k, slot_mask=active if cfg.moe.enabled else None,
                    block_table=tables, page_span=page_span,
                    dispatch=dispatch)
                return logits.astype(jnp.float32), new_cache
        else:
            @partial(jax.jit, donate_argnums=(2,))
            def _verify_fn(params, trainable, cache, tokens, pos, active):
                logits, new_cache = model_lib.decode_step(
                    cfg, params, cache, tokens, pos, trainable=trainable,
                    k=moe_k, slot_mask=active if cfg.moe.enabled else None,
                    dispatch=dispatch)
                return logits.astype(jnp.float32), new_cache
        return _verify_fn

    # ------------------------------------------------------------- trainables
    def _build_decode_trainable(self) -> Optional[PyTree]:
        tr: dict = {}
        if self._lora is not None:
            tr["lora"] = self._lora
        if self._rescaler_by_k:
            ks = [k for k in self.slot_k if k is not None]
            missing = sorted(set(ks) - set(self._rescaler_by_k))
            assert not missing, f"rescaler_by_k missing tiers {missing}"
            # stack tiers per slot: leaf (n_periods,) -> (n_periods, S);
            # the stack scan slices the leading axis, so each MoE layer
            # sees a (num_slots,) vector — the per-slot rescaler path in
            # moe_layer.apply_moe
            tr["rescaler"] = jax.tree.map(
                lambda *leaves: jnp.stack(leaves, axis=-1),
                *[self._rescaler_by_k[k] for k in ks])
        return tr or None

    def _build_draft_trainable(self, draft_k: int) -> Optional[PyTree]:
        """Trainable tree for the speculative draft window: every slot at
        the same scalar ``draft_k``, so the per-period rescaler tree is
        used as-is (no per-slot stacking).  Uses the ``draft_k`` tier's
        trained rescaler when one was provided; otherwise the draft runs
        unrescaled — the draft distribution q may be anything without
        breaking the rejection rule's exactness, only the acceptance
        rate."""
        tr: dict = {}
        if self._lora is not None:
            tr["lora"] = self._lora
        if self._rescaler_by_k and draft_k in self._rescaler_by_k:
            tr["rescaler"] = self._rescaler_by_k[draft_k]
        return tr or None

    def _prefill_trainable(self, k: Optional[int]) -> Optional[PyTree]:
        tr: dict = {}
        if self._lora is not None:
            tr["lora"] = self._lora
        if self._rescaler_by_k and k is not None:
            tr["rescaler"] = self._rescaler_by_k[k]
        return tr or None

    # ------------------------------------------------------------------ admit
    @staticmethod
    def _max_new(req: Request) -> int:
        if req.forced is not None:
            return min(req.max_new_tokens, len(req.forced))
        return req.max_new_tokens

    def _projected_tokens(self, req: Request) -> int:
        """Cache positions the request will write over its lifetime: the
        prompt plus one decode write per generated token after the first
        (the prefill token costs no extra position).  Floored at the
        prompt length: prefill installs all L positions even when
        ``max_new`` is 0 (the engine still emits the prefill token)."""
        return req.prompt_len + max(self._max_new(req), 1) - 1

    def _admit(self, report: ServingReport) -> int:
        """One admission round: a normal packing pass, then — with
        preemption on — swap out lenient-deadline victims while a waiter
        is past its TTFT deadline and another pass can seat it."""
        with self._tracer.span("engine.admit", cat="engine") as sp:
            n = self._admit_pass(report)
            if self._preemption:
                for _ in range(self.num_slots):
                    victim = self._pick_victim()
                    if victim is None:
                        break
                    self._preempt(victim, report)
                    got = self._admit_pass(report)
                    n += got
                    if got == 0:
                        # the freed blocks alone didn't seat the waiter
                        # (quota-bound, or it needs more than one victim);
                        # stop rather than strip the pool in one round —
                        # the next engine iteration tries again
                        break
            sp.set(admitted=n)
        if n and self._metrics is not None:
            self._ctr_admitted.inc(n)
        return n

    def _pick_victim(self) -> Optional[int]:
        """SLO-driven victim selection: when the most urgent waiter is
        already past its TTFT deadline, choose the active request with
        the most lenient (latest) deadline — strictly later than the
        waiter's, so preemption always moves urgency forward and a
        same-tier earlier arrival can never be evicted for a later one —
        breaking ties toward the most recently admitted (least sunk
        work).  Requests already preempted ``max_preemptions`` times are
        exempt."""
        if not len(self.scheduler):
            return None
        sch = self.scheduler
        now = self._now()
        urgent = [sch.deadline(r) for r in sch.queue
                  if sch.deadline(r) <= now]
        if not urgent:
            return None
        w_deadline = min(urgent)
        best: Optional[int] = None
        best_key: Optional[Tuple[float, float]] = None
        for s, a in enumerate(self._active):
            if a is None or a.preemptions >= self._max_preemptions:
                continue
            v_deadline = sch.deadline(a.req)
            if v_deadline <= w_deadline:
                continue
            key = (v_deadline, a.admitted)
            if best_key is None or key > best_key:
                best, best_key = s, key
        return best

    def _preempt(self, slot: int, report: ServingReport) -> None:
        """Swap ``slot``'s request out to host and hand it back to the
        scheduler: blocks and reservation are freed (BlockPool.swap_out),
        so the waiter's admission sees real headroom; the request's tier
        is pinned to its slot's ``k`` so re-admission resumes it at the
        budget it started decoding with."""
        a = self._active[slot]
        if self._spec is not None:
            # an open draft window (positions advanced past the last
            # verified token) must not leak into the swap state — roll
            # the row back to its window base and drop the draft buffer
            self._spec.rollback_open(slot)
        tier = self.slot_k[slot]
        self._tier_reserved[tier] -= self.pool.reserved_for(slot)
        state = self.pool.swap_out(slot)
        a.preemptions += 1
        a.req.k = tier
        self._swapped[a.req.rid] = (state, a, int(self._last_tok[slot, 0]))
        self._active[slot] = None
        self.scheduler.add(a.req)
        report.preemptions += 1
        if self._tracer.enabled:
            a.swap_t = self._tracer.now()
            self._tracer.instant("swap_out", a.swap_t, pid=PID_REQUESTS,
                                 tid=a.req.rid, cat="preempt",
                                 args={"slot": slot})
        if self._metrics is not None:
            self._ctr_preempt.inc()

    def _admit_pass(self, report: ServingReport) -> int:
        free = self.pool.free_slots
        if not free or not len(self.scheduler):
            return 0
        can_admit = None
        if self.paged:
            # account blocks as the scheduler accepts: each accepted
            # request is guaranteed a slot, so its projected need comes
            # off the headroom before the next request is considered.
            # The tier quota binds only under cross-tier contention
            # (another tier waiting) — work-conserving when the pool is
            # otherwise idle, starvation-free when it is not.
            booked = 0
            booked_by_tier: Dict[Optional[int], int] = {}
            # slot tiers contended by the waiting queue: a wildcard
            # (k=None) waiter can sit in any tier, so it contends with
            # all of them
            waiting_tiers: set = set()
            for r in self.scheduler.queue:
                if r.k is None:
                    waiting_tiers.update(self._tier_quota)
                    break
                waiting_tiers.add(r.k)

            # escrow for the oldest starved waiter: the FIRST request of
            # the FIFO scan rejected for block AVAILABILITY (not quota)
            # gets its need earmarked — younger requests may only book
            # blocks beyond it, so freed blocks accumulate for it
            # instead of being re-consumed forever by a cross-tier
            # stream of small requests (its wait is bounded by in-flight
            # request lifetimes)
            escrow = 0
            escrow_rid: Optional[int] = None

            def can_admit(req: Request, slot: int) -> bool:
                nonlocal booked, escrow, escrow_rid
                tier = self.slot_k[slot]
                need = self.pool.blocks_needed(self._projected_tokens(req))
                avail = self.pool.available_blocks - booked
                if escrow_rid is not None and req.rid != escrow_rid:
                    avail -= escrow
                if need > avail:
                    if escrow_rid is None or escrow_rid == req.rid:
                        escrow, escrow_rid = need, req.rid
                    return False
                held = (self._tier_reserved[tier]
                        + booked_by_tier.get(tier, 0) + need)
                if held > self._tier_quota[tier] and waiting_tiers - {tier}:
                    return False
                booked += need
                booked_by_tier[tier] = booked_by_tier.get(tier, 0) + need
                return True
        assignments = self.scheduler.admit(free, self.slot_k, can_admit)
        # group rows into (kind, bucket key, tier) prefill batches.
        # "full" groups key on exact prompt length and run the plain
        # prefill.  Under suffix prefill the match runs NOW
        # (attach_prefix, in admission order so same-pass duplicates
        # share); rows with a usable matched head form "suffix" groups
        # keyed by the power-of-two SUFFIX bucket — a flash crowd of
        # long shared-head prompts collapses into small buckets and
        # O(log max_suffix) compiled variants — while cold misses
        # (suffix == whole prompt) take the exact-length full-prefill
        # path, identical launches to a cold engine's.  Items carry
        # (request, slot, suffix start, matched tokens).
        groups: Dict[Tuple[str, int, Optional[int]],
                     List[Tuple[Request, int, int, int]]] = {}
        for req, slot in assignments:
            self.pool.take(slot)
            if self.paged:
                need = self.pool.blocks_needed(self._projected_tokens(req))
                self.pool.reserve(slot, self._projected_tokens(req))
                self._tier_reserved[self.slot_k[slot]] += need
            if req.rid in self._swapped:
                # resume a preempted request: restore its exact KV/SSM
                # state and bookkeeping instead of prefilling — its
                # admitted/first_token timestamps and PRNG event counter
                # continue from where the swap-out left them
                state, a, last = self._swapped.pop(req.rid)
                self.pool.swap_in(slot, state)
                self._active[slot] = a
                self._last_tok[slot, 0] = last
                if self._tracer.enabled:
                    now = self._tracer.now()
                    self._tracer.complete(
                        "swapped_out", a.swap_t, now, pid=PID_REQUESTS,
                        tid=req.rid, cat="preempt")
                    self._tracer.instant("swap_in", now, pid=PID_REQUESTS,
                                         tid=req.rid, cat="preempt",
                                         args={"slot": slot})
                continue
            assert req.prompt_len + 1 <= self.slot_len, \
                f"request {req.rid}: prompt {req.prompt_len} leaves no room" \
                f" in a {self.slot_len}-token slot"
            if self._use_suffix:
                L = req.prompt_len
                covered, ready = self.pool.attach_prefix(
                    slot, req.prompt, L, _tier_salt(self.slot_k[slot]))
                # suffix start: round the READY span (pages written and
                # readable in-graph) down to block granularity — the
                # per-row cache-validity mask is idx < sstart, so it must
                # not admit a partially matched block's foreign tail.
                # Floored at L-1: a full-match prompt still runs a
                # 1-token suffix step, so its first sampled token comes
                # from real logits, never a skipped sample.
                bs = self.pool.block_size
                sstart = min((ready // bs) * bs, L - 1)
                key = (("suffix", _bucket(L - sstart))
                       if sstart > 0 else ("full", L))
                groups.setdefault(
                    key + (self.slot_k[slot],),
                    []).append((req, slot, sstart, covered))
            else:
                groups.setdefault(
                    ("full", req.prompt_len, self.slot_k[slot]),
                    []).append((req, slot, 0, 0))

        tr = self._tracer
        for (kind, width, kk), items in groups.items():
            nb = len(items)
            bucket = _bucket(nb)
            admitted = self._now()
            targs = {"batch": nb, "bucket": bucket,
                     "suffix_bucket" if kind == "suffix" else "prompt_len":
                     width, "k": kk if kk is not None else 0}
            with tr.span("engine.prefill", cat="engine", args=targs):
                real = jnp.asarray(np.arange(bucket) < nb, jnp.float32)
                if kind == "suffix":
                    # width == the group's suffix bucket; prompt lengths
                    # may differ within it.  Pad rows: empty table, prefix
                    # 0, suffix 1 — their gathers are fully masked (pos 0)
                    # and their outputs discarded.
                    toks = np.zeros((bucket, width), np.int32)
                    pref = np.zeros((bucket,), np.int32)
                    suf = np.ones((bucket,), np.int32)
                    # page-span bucket: only the blocks covering the
                    # group's deepest live prefix + the suffix are gathered
                    # in-graph (pow-2 to bound compile variants) — a short
                    # suffix on a short prefix must not pay a full-slot
                    # gather
                    bs = self.pool.block_size
                    span_b = min(_bucket(-(-(max(
                        st for _, _, st, _ in items) + width) // bs)),
                        self.pool.blocks_per_slot)
                    tbl = np.zeros((bucket, span_b), np.int32)
                    for j, (req, slot, sstart, _cov) in enumerate(items):
                        n = req.prompt_len - sstart
                        toks[j, :n] = np.asarray(req.prompt[sstart:],
                                                 np.int32)
                        pref[j], suf[j] = sstart, n
                        tbl[j] = self.pool.block_table[slot][:span_b]
                    logits, piece = self._suffix_prefill_fn(
                        self.params, self._prefill_trainable(kk),
                        self.pool.cache, jnp.asarray(toks),
                        jnp.asarray(tbl), jnp.asarray(pref),
                        jnp.asarray(suf), real, k=kk)
                    logits_np = np.asarray(logits)  # blocks until ready
                    self.pool.write(
                        [s for _, s, _, _ in items], piece,
                        [r.prompt_len for r, _, _, _ in items],
                        starts=[cov for _, _, _, cov in items],
                        piece_col0=[st for _, _, st, _ in items])
                    report.prefill_tokens += int(suf[:nb].sum())
                else:
                    prompts = np.stack(
                        [r.prompt for r, _, _, _ in items]
                        + [items[0][0].prompt] * (bucket - nb))
                    logits, cache = self._prefill_fn(
                        self.params, self._prefill_trainable(kk),
                        jnp.asarray(prompts), real, k=kk)
                    logits_np = np.asarray(logits)  # blocks until ready
                    if self._use_suffix:
                        # match/attach/alloc already ran at assignment
                        # time: scatter past the matched span only (a
                        # same-batch duplicate recomputes its whole prompt
                        # — pending pages aren't readable in-graph — but
                        # must not rewrite the shared blocks it attached)
                        self.pool.write(
                            [s for _, s, _, _ in items], cache,
                            [width] * nb,
                            starts=[cov for _, _, _, cov in items])
                    else:
                        self.pool.write(
                            [s for _, s, _, _ in items], cache,
                            [width] * nb,
                            tokens=[r.prompt for r, _, _, _ in items],
                            salt=_tier_salt(kk))
                    report.prefill_tokens += nb * width
            tft = self._now()
            report.prefill_s.append(tft - admitted)
            report.prefill_hist.observe((tft - admitted) * 1e3)
            if self._metrics is not None:
                self._ctr_prefills.inc()
                self._ctr_prefill_rows.inc(nb)
                self._ctr_draws.inc(nb)

            with tr.span("engine.sample", cat="engine",
                         args={"draws": nb, "phase": "prefill"}):
                for j, (req, slot, _st, _cov) in enumerate(items):
                    max_new = self._max_new(req)
                    a = _ActiveSlot(
                        req=req, tokens=[], nll=0.0, admitted=admitted,
                        first_token=tft, max_new=max_new)
                    self._active[slot] = a
                    tok, nll = self._pick(logits_np[j], a)
                    a.tokens.append(tok)
                    a.token_t.append(self._now())
                    a.nll += nll
                    self._last_tok[slot, 0] = tok
                    if len(a.tokens) >= max_new or self.pool.slot_full(slot):
                        self._finish(slot, report)
        return len(assignments)

    # --------------------------------------------------------------- sampling
    def _req_key(self, rid: int) -> jax.Array:
        """The request's PRNG base key, fold_in(seed key, rid), memoized —
        every draw key folds an event counter into this."""
        key = self._req_keys.get(rid)
        if key is None:
            key = jax.random.fold_in(jax.random.PRNGKey(self._seed), rid)
            self._req_keys[rid] = key
        return key

    def _event_key(self, a: _ActiveSlot) -> jax.Array:
        """Next PRNG key for one request: fold (seed, rid, event counter).
        Keys depend only on the request's own draw order, so sampled
        output is independent of what shares the batch."""
        key = jax.random.fold_in(self._req_key(a.req.rid), a.events)
        a.events += 1
        return key

    def _sample(self, logits_row: np.ndarray, a: _ActiveSlot) -> int:
        """One sampler draw for one slot (no forced/NLL handling)."""
        if self._sampler.kind == "greedy":
            return int(np.argmax(logits_row))
        return int(sample_token(self._event_key(a), jnp.asarray(logits_row),
                                self._sampler))

    def _pick(self, logits_row: np.ndarray,
              a: _ActiveSlot) -> Tuple[int, float]:
        """Next token for one slot: the engine's sampler, or the request's
        forced token (accumulating its NLL)."""
        if a.req.forced is not None:
            tok = int(a.req.forced[len(a.tokens)])
            return tok, float(-_log_softmax_np(logits_row)[tok])
        return self._sample(logits_row, a), 0.0

    # ----------------------------------------------------------------- decode
    def _decode_once(self, report: ServingReport) -> None:
        t_start = time.perf_counter()
        tr = self._tracer
        with tr.span("engine.decode", cat="engine") as sp:
            active = [s for s, a in enumerate(self._active) if a is not None]
            sp.set(active=len(active))
            active_mask = jnp.asarray(
                [a is not None for a in self._active], jnp.float32)
            extra = ()
            if self.paged:
                # allocate each active row's next write block (guaranteed
                # to succeed: covered by the reservation made at admit)
                self.pool.prepare_decode(active)
                extra = (self.pool.tables(),)
            out = self._decode_fn(
                self.params, self._decode_trainable, self.pool.cache,
                jnp.asarray(self._last_tok), self.pool.positions(),
                active_mask, *extra)
            logits, new_cache = out[0], out[1]
            logits_np = np.asarray(logits)          # blocks until ready
            self.pool.cache = new_cache
            dt = time.perf_counter() - t_start
        report.decode_step_s.append(dt)
        report.decode_hist.observe(dt * 1e3)
        if self._metrics is not None:
            self._ctr_steps.inc()
            self._ctr_draws.inc(len(active))
        if self._expert_telemetry:
            self._expert_tracker.observe_step(
                {p: np.asarray(c) for p, c in out[2].items()})

        self.pool.advance(active)
        with tr.span("engine.sample", cat="engine",
                     args={"draws": len(active), "phase": "decode"}):
            for slot in active:
                a = self._active[slot]
                tok, nll = self._pick(logits_np[slot], a)
                a.tokens.append(tok)
                a.token_t.append(self._now())
                a.nll += nll
                self._last_tok[slot, 0] = tok
                if len(a.tokens) >= a.max_new or self.pool.slot_full(slot):
                    self._finish(slot, report)

    def _finish(self, slot: int, report: ServingReport) -> None:
        a = self._active[slot]
        c = Completion(
            rid=a.req.rid, prompt_len=a.req.prompt_len,
            tokens=np.asarray(a.tokens, np.int32),
            k=self.slot_k[slot] or 0, arrival=a.req.arrival,
            admitted=a.admitted, first_token=a.first_token,
            finished=self._now(), nll_sum=a.nll,
            truncated=len(a.tokens) < a.max_new,
            preemptions=a.preemptions,
            token_t=np.asarray(a.token_t, np.float64))
        report.completions.append(c)
        if self._tracer.enabled:
            # the request's lifecycle track, emitted retrospectively from
            # the completion's timestamps (engine time, shifted by the
            # run's start on the tracer's clock): an enclosing span plus
            # the queued → prefill → decode phases (swap events were
            # emitted live as the preemptions happened)
            tr, off = self._tracer, self._trace_off
            tid = c.rid
            tr.thread_name(PID_REQUESTS, tid, f"req {tid}")
            args = {"rid": c.rid, "k": c.k, "prompt_len": c.prompt_len,
                    "gen_tokens": c.n_generated,
                    "preemptions": c.preemptions}
            tr.complete("request", c.arrival + off, c.finished + off,
                        pid=PID_REQUESTS, tid=tid, cat="request", args=args)
            tr.complete("queued", c.arrival + off, c.admitted + off,
                        pid=PID_REQUESTS, tid=tid, cat="request")
            tr.complete("prefill", c.admitted + off, c.first_token + off,
                        pid=PID_REQUESTS, tid=tid, cat="request")
            tr.complete("decode", c.first_token + off, c.finished + off,
                        pid=PID_REQUESTS, tid=tid, cat="request")
        if self._metrics is not None:
            self._ctr_completions.inc()
            self._ctr_tokens.inc(c.n_generated)
        self._active[slot] = None
        if self.paged:
            self._tier_reserved[self.slot_k[slot]] -= \
                self.pool.reserved_for(slot)
        self.pool.release(slot)

    # ------------------------------------------------------------------- loop
    def _now(self) -> float:
        return time.perf_counter() - self._t0

    @property
    def n_active(self) -> int:
        return sum(a is not None for a in self._active)

    def run(self, requests: Sequence[Request],
            max_steps: Optional[int] = None) -> ServingReport:
        """Serve an open-loop trace to completion.

        Arrival times are interpreted on the engine's wall clock starting
        at call time; ``arrival=0.0`` everywhere makes the run a
        deterministic closed batch.
        """
        assert self.n_active == 0 and not len(self.scheduler), \
            "engine already mid-run"
        # fail fast: reject unservable requests BEFORE any work starts, so
        # a malformed trace can't abort a run mid-flight and discard the
        # in-flight requests' results
        too_long = [r.rid for r in requests
                    if r.prompt_len + 1 > self.slot_len]
        if too_long:
            raise ValueError(
                f"requests {too_long}: prompt leaves no room for a "
                f"generated token in a {self.slot_len}-token slot")
        if self._spec is not None:
            forced = [r.rid for r in requests if r.forced is not None]
            if forced:
                raise ValueError(
                    f"requests {forced}: teacher-forced (NLL) requests "
                    "cannot run under speculative decoding — the drafts "
                    "would diverge from the forced continuation")
        # (no block-capacity fail-fast needed: blocks_needed caps at the
        # per-request span and the pool holds >= one span by construction,
        # so an empty pool can always admit any slot-length-valid request)
        pending = sorted(requests, key=lambda r: r.arrival)
        report = ServingReport(completions=[], num_slots=self.num_slots,
                               slot_k=self.slot_k, slo_ms=self.slo_ms)
        if self._metrics is not None:
            # expose this run's step histograms through the registry
            # (rebound every run; externally owned, so no copying)
            self._metrics.register("serving.decode_step_ms",
                                   report.decode_hist)
            self._metrics.register("serving.prefill_ms", report.prefill_hist)
            if self._spec is not None:
                self._metrics.register("serving.draft_step_ms",
                                       report.draft_hist)
                self._metrics.register("serving.verify_step_ms",
                                       report.verify_hist)
        if self._expert_tracker is not None:
            self._expert_tracker.reset()
        self._t0 = time.perf_counter()
        tr = self._tracer
        # tracer time at the run's engine time 0: the tracer keeps one
        # clock across runs, the engine's restarts with each
        self._trace_off = tr.at(self._t0) if tr.enabled else 0.0
        steps = 0
        try:
            while pending or len(self.scheduler) or self.n_active:
                with tr.span("engine.iteration", cat="engine") as it:
                    now = self._now()
                    while pending and pending[0].arrival <= now:
                        self.scheduler.add(pending.pop(0))
                    admitted = self._admit(report)
                    if tr.enabled:
                        load = {"active_slots": self.n_active,
                                "queue_depth": len(self.scheduler)}
                        tr.counter("engine", tr.now(), load)
                        it.set(**load)
                    if self.n_active:
                        if self._spec is not None:
                            self._spec.round(report)
                        else:
                            self._decode_once(report)
                        steps += 1
                        if max_steps is not None and steps >= max_steps:
                            break
                    elif not admitted:
                        if pending:          # idle until the next arrival
                            time.sleep(max(0.0, min(
                                pending[0].arrival - self._now(), 0.01)))
                        elif len(self.scheduler):
                            stuck = [r.rid for r in self.scheduler.queue]
                            raise RuntimeError(
                                f"requests {stuck} match no slot tier "
                                f"(slot_k={self.slot_k})")
        except Exception:
            # flight recorder: leave the last `ring` trace events on disk
            # for a postmortem of the stuck/crashed run, then re-raise
            path = tr.flight_dump()
            if path is not None:
                import sys
                print(f"serving engine: exception — flight recorder "
                      f"dumped to {path}", file=sys.stderr)
            raise
        report.wall_s = self._now()
        report.completions.sort(key=lambda c: c.rid)
        assert not self._swapped or max_steps is not None, \
            "swapped-out requests left behind after a full run"
        if self.prefix_cache:
            report.prefix = self.pool.prefix_stats()
        if self._expert_tracker is not None:
            report.expert_load = self._expert_tracker.snapshot()
            if self._metrics is not None:
                self._expert_tracker.publish(self._metrics)
        return report
