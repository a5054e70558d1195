"""Serving cells: ``ServingEngine.run`` on a traffic file's requests.

Set-up makes the weights from the seed, builds one engine and runs the
cell's own trace once, untimed: that forms exactly the window's batches,
so every prefill, cache-write and decode program of the window is
compiled and run before it.  The window then runs whole ``run`` calls of
the same seeded trace until ``--seconds`` have passed.  After the window
the engine is freed and the benchmark's own float32 reference
(harness/reference.py) re-reads two seeded samples of the last call's
finished requests (``check_sample``):

* greedy requests: the engine's logit rows, recorded where they reach
  the sampler, against the reference's rows at the same positions
  (``logit_rel_err``), and how far each served token's logit lies below
  the reference's best (``mean_logit_gap``);
* sampled requests: the share of served tokens outside the reference's
  nucleus at the traffic's temperature and top-p (``nucleus_miss_share``).

The engine is the program's, with host-side additions in a subclass that
overrides private methods of ``ServingEngine``: ``_sample`` (greedy
decoding and a record of the logit rows for the requests the traffic
marks greedy; the others go through the configured sampler),
``_prefill_fn`` (a record of each prefill call's prompt length, real rows
and k, for the kernel roofline), and ``_admit``/``_decode_once``/
``_sample`` again for the harness's spans.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from . import clock, reference, traffic, weights
from .chip import log


def engine_class(ServingEngine, spans):
    class BenchEngine(ServingEngine):
        """The program's engine with the harness's host-side hooks."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.greedy: set = set()
            self.rows: Dict[int, list] = {}
            self.prefills: List[tuple] = []
            inner = self._prefill_fn

            def prefill(params, trainable, prompts, real, k):
                self.prefills.append((prompts.shape[1],
                                      int(np.asarray(real).sum()), k))
                return inner(params, trainable, prompts, real, k=k)
            self._prefill_fn = prefill

        def _sample(self, logits_row, a):
            with spans("sample"):
                if a.req.rid in self.greedy:
                    self.rows.setdefault(a.req.rid, []).append(
                        np.array(logits_row, np.float32))
                    return int(np.argmax(logits_row))
                return super()._sample(logits_row, a)

        def _decode_once(self, report):
            with spans("decode_step"):
                super()._decode_once(report)

        def _admit(self, report):
            with spans("admit"):
                return super()._admit(report)

    return BenchEngine


def _requests(Request, reqs):
    return [Request(rid=r.rid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens, k=r.k) for r in reqs]


def run(ctx) -> None:
    jax, jnp = ctx.jax, ctx.jnp
    from repro.configs.registry import get_config
    from repro.serving import Request, ServingEngine
    from repro.serving.sampler import SamplerConfig

    s, tp = ctx.sizes, ctx.traffic
    cfg = program_config(get_config, s)
    slot_k = traffic.slot_k(tp)
    tiers = sorted(set(slot_k), reverse=True)
    slot_len = max(tp["prompt_lens"]) + max(tp["new_tokens"])

    key, word = weights.seed_key(jax, ctx.seed)
    params, lora, resc = weights.make(jax, jnp, s, tiers, key)
    jax.block_until_ready((params, lora, resc))
    weights.check_layout(jax, cfg, params, lora)
    log(f"weights: {ctx.n_params} params, tiers {tiers}, slot_len "
        f"{slot_len}; {clock.process_start_s():.1f} s since process start")

    Engine = engine_class(ServingEngine, ctx.spans)
    sampler = SamplerConfig(**tp["sampler"])
    eng = Engine(cfg, params, lora=lora, rescaler_by_k=resc,
                 num_slots=len(slot_k), slot_len=slot_len, slot_k=slot_k,
                 sampler=sampler, block_size=int(tp.get("block_size", 16)),
                 seed=word)
    reqs = traffic.make_trace(tp, s.vocab, word)
    eng.greedy = {r.rid for r in reqs if r.greedy}

    def call():
        eng.rows.clear()
        return eng.run(_requests(Request, reqs))

    # ---- warm-up: the window's own trace, once
    call()
    log(f"warm-up: {ctx.clock.programs} programs compiled or loaded, "
        f"{ctx.clock.hits} from the persistent cache, "
        f"{ctx.clock.seconds:.3f} s in the compiler; "
        f"{clock.process_start_s():.1f} s since process start")

    # ---- window: whole calls of the same trace
    eng.prefills.clear()
    reports = ctx.window(call)
    last = reports[-1]
    ctx.device_record()
    comps = [c for rep in reports for c in rep.completions]
    want = {r.rid: r for r in reqs}
    failed = sum(1 for c in comps if c.truncated
                 or c.n_generated != want[c.rid].max_new_tokens)
    failed += len(reports) * len(reqs) - len(comps)
    ctx.result.update(attempted=len(reports) * len(reqs), failed=failed)
    ctx.check("requests_failed", failed, 0, "==")
    ctx.data.update(completions=comps, seed_word=word, prefills=eng.prefills,
                    decode_steps=sum(len(r.decode_step_s) for r in reports),
                    decode_s=sum(sum(r.decode_step_s) for r in reports))

    # ---- correctness: the engine is freed first, then the reference
    greedy, sampled = check_sample(last.completions, want, ctx.cell["check"],
                                   word)
    rows = {c.rid: eng.rows.get(c.rid, []) for c in greedy}
    del eng, reports, last
    gc.collect()
    reference_check(ctx, greedy, sampled, rows, want, params, lora, resc,
                    slot_len, max(tp["new_tokens"]), sampler, word)


def greedy_numbers(gaps, errs) -> dict:
    """The numbers of the greedy sample: per served token its gap below
    the reference's best logit, per position the relative L2 distance of
    the logit row from the reference's."""
    g, e = np.concatenate(gaps), np.concatenate(errs)
    return {"mean_logit_gap": float(g.mean()),
            "widest_logit_gap": float(g.max()),
            "logit_rel_err": float(e.mean()),
            "widest_logit_rel_err": float(e.max())}


def sampled_numbers(misses) -> dict:
    m = np.concatenate(misses)
    return {"nucleus_miss_share": float(m.mean())}


def reference_check(ctx, greedy, sampled, rows, want, params, lora, resc,
                    length, window, sampler, word):
    """The two samples against the float32 reference.  With
    ``ctx.control`` also, at the same positions, the control (the float8
    reference in the program's place: its logit rows, its first-placed
    token, its own top-p draws) and a sampler fault (draws from the float32
    reference with the nucleus left out), each judged by the cell's limits
    as the program is."""
    jax, jnp = ctx.jax, ctx.jnp
    ref = reference.Reference(jax, jnp, ctx.sizes, params, lora, length,
                              window)
    temp, top_p = sampler.temperature, sampler.top_p
    key = jax.random.PRNGKey(word)
    gaps = {"program": [], "fp8": []}
    errs = {"program": [], "fp8": []}
    misses = {"program": [], "fp8": [], "no_nucleus": []}
    missing = 0
    t0 = time.perf_counter()

    def served_of(c):
        served = np.full((window,), -1, np.int64)
        served[:c.n_generated] = c.tokens
        return served

    def forward(c, quant=None):
        r = want[c.rid]
        seq = np.concatenate([r.prompt, c.tokens[:-1]])
        return ref.logits(seq, len(r.prompt) - 1, c.k, resc[c.k],
                          quant=quant)

    for c in greedy:
        lg, served, n = forward(c), served_of(c), c.n_generated
        got = rows[c.rid]
        missing += abs(len(got) - n)
        prog = np.zeros((n, lg.shape[-1]), np.float32)
        prog[:min(n, len(got))] = got[:n]
        gaps["program"].append(reference.greedy_gaps(jax, jnp, lg, served))
        errs["program"].append(reference.row_errors(jnp, lg[:n], prog))
        one = greedy_numbers(gaps["program"][-1:], errs["program"][-1:])
        msg = (f"reference: greedy request {c.rid} (k={c.k}, prompt "
               f"{len(want[c.rid].prompt)}, {n} tokens): {one}")
        if ctx.control:
            lq = forward(c, "fp8")
            top = np.asarray(jnp.argmax(lq, axis=-1))
            gaps["fp8"].append(reference.greedy_gaps(
                jax, jnp, lg, np.where(served >= 0, top, -1)))
            errs["fp8"].append(reference.row_errors(jnp, lg[:n], lq[:n]))
            one = greedy_numbers(gaps["fp8"][-1:], errs["fp8"][-1:])
            msg += f"; fp8: {one}"
        log(msg)
    for i, c in enumerate(sampled):
        lg, served, n = forward(c), served_of(c), c.n_generated
        misses["program"].append(reference.nucleus_miss(
            jnp, lg, served, temp, top_p))
        msg = (f"reference: sampled request {c.rid} (k={c.k}, {n} tokens): "
               f"{misses['program'][-1].sum()} outside the nucleus")
        if ctx.control:
            kq, kf = jax.random.split(jax.random.fold_in(key, i))
            lq = forward(c, "fp8")
            for v, kv, src, p in (("fp8", kq, lq, top_p),
                                  ("no_nucleus", kf, lg, 1.0)):
                drawn = np.asarray(reference.draw(jax, jnp, kv, src, temp, p))
                misses[v].append(reference.nucleus_miss(
                    jnp, lg, np.where(served >= 0, drawn, -1), temp, top_p))
                msg += f"; {v}: {misses[v][-1].sum()}"
        log(msg)
    log(f"reference check: {len(greedy)} greedy and {len(sampled)} sampled "
        f"requests in {time.perf_counter() - t0:.1f} s")

    nums = {"program": {**greedy_numbers(gaps["program"], errs["program"]),
                        **sampled_numbers(misses["program"])}}
    if ctx.control:
        nums["fp8"] = {**greedy_numbers(gaps["fp8"], errs["fp8"]),
                       **sampled_numbers(misses["fp8"])}
        nums["no_nucleus"] = sampled_numbers(misses["no_nucleus"])
    ctx.data["numbers"] = nums
    chk = ctx.cell["check"]
    ctx.check("logit_rows_missing", missing, 0, "==")
    ctx.check("greedy_tokens_checked", sum(len(g) for g in gaps["program"]),
              chk["greedy_tokens"], ">=")
    ctx.check("sampled_tokens_checked",
              sum(len(m) for m in misses["program"]), chk["sampled_tokens"],
              ">=")
    limits = ctx.cell["limits"]
    for name, limit in limits.items():
        ctx.check(name, nums["program"][name], limit, "<=")
    # the control and the fault, judged by the same limits
    ctx.data["judged"] = {
        v: ctx.judge({n: (x, limits[n], "<=") for n, x in got.items()
                      if n in limits})
        for v, got in nums.items() if v != "program"}


def check_sample(comps, want, chk: dict, word: int):
    """Two seeded samples of the finished requests: ``greedy_requests``
    greedy ones (the longest, then one tier after another, a random one
    of each) and ``sampled_requests`` sampled ones of the longest output
    (one tier after another, a random one of each)."""
    rng = np.random.default_rng(word)
    done = [c for c in comps if not c.truncated]
    greedy = [c for c in done if want[c.rid].greedy]
    longest = max(greedy, key=lambda c: c.prompt_len + c.n_generated)
    top = max(c.n_generated for c in done)
    sampled = [c for c in done if not want[c.rid].greedy
               and c.n_generated == top]
    return (_by_tier(greedy, [longest], chk["greedy_requests"], rng),
            _by_tier(sampled, [], chk["sampled_requests"], rng))


def _by_tier(pool, first, n, rng):
    chosen = {c.rid: c for c in first}
    pools = {k: [c for c in pool if c.k == k and c.rid not in chosen]
             for k in sorted({c.k for c in pool}, reverse=True)}
    while len(chosen) < n and any(pools.values()):
        for p in pools.values():
            if p and len(chosen) < n:
                c = p.pop(int(rng.integers(len(p))))
                chosen[c.rid] = c
    return list(chosen.values())


def program_config(get_config, s):
    """The program's ModelConfig with every size from the configuration
    file (the registry entry gives the family); the fields the reference
    takes as given are checked."""
    import dataclasses
    from repro.configs.base import LoRAConfig
    cfg = get_config(s.registry_id, "full")
    moe = dataclasses.replace(
        cfg.moe, num_experts=s.n_experts, top_k=s.top_k,
        d_expert=s.d_expert, num_shared_experts=cfg.moe.num_shared_experts
        if s.d_shared else 0, d_shared_expert=s.d_shared)
    cfg = cfg.replace(
        num_layers=s.layers, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads, head_dim=s.head_dim, vocab_size=s.vocab,
        rope_theta=s.rope_theta, qk_norm=s.qk_norm, rms_eps=s.rms_eps,
        moe=moe, lora=LoRAConfig(rank=s.lora_rank, alpha=s.lora_alpha))
    fixed = dict(dtype=cfg.dtype, tie=cfg.tie_embeddings,
                 window=cfg.attention_window, softcap=cfg.attn_logit_softcap,
                 pattern=cfg.layer_pattern, moe_every=cfg.moe.moe_every,
                 d_ff=cfg.d_ff, codebooks=cfg.num_codebooks)
    want = dict(dtype="bfloat16", tie=False, window=0, softcap=0.0,
                pattern=None, moe_every=1, d_ff=0, codebooks=0)
    if fixed != want:
        raise ValueError(f"program config {fixed} is not what the "
                         f"reference computes: {want}")
    cfg.validate()
    return cfg


SPANS = ("window", "call", "admit", "decode_step", "sample")
