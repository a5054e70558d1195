#!/usr/bin/env python3
"""Bring-up smoke run: FLAME's main path on one TPU at OLMoE-1.3B/6.9B widths.

    python chip_smoke.py [--seed N]

All phases run in this one process, in order:

1. **train** — ``build_experiment`` + ``FederatedServer.run`` with the device
   round driver: 4 clients, one per FLAME budget k in {8, 4, 2, 1} (paper
   A1.2), 2 rounds of a few local Adam steps each.  Checks finite losses,
   non-zero expert activation counts and a changed global LoRA tree.
2. **serve** — ``ServingEngine`` with its defaults (paged KV, ragged
   dispatch, ``backend="auto"``) serving the trained adapter: 8 slots split
   between k=8 and k=1, 16 greedy requests with 128/256/512-token prompts
   and 16 new tokens each.  Every request must complete with its tokens.
3. **reference** — one request's prefill logits from the engine's own
   compiled prefill against an fp32 ``backend="reference"`` forward of the
   same weights at "highest" matmul precision.
4. **kernels** — the compiled prefill, decode and cohort programs must hold
   the Mosaic custom calls of the Pallas kernels the TPU path should run:
   a silent fallback to the jnp reference fails here.

The model is the published configuration cut in depth only (16 -> 4
layers); weights are random, made from ``--seed``.  The script exits
non-zero, and prints no result line, unless JAX's first device is a TPU.
Its last line of stdout is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_LAYERS = 4            # depth cut: 16 -> 4 layers, every width published
N_CLIENTS, N_ROUNDS = 4, 2
SLOT_K = (8, 8, 8, 8, 1, 1, 1, 1)
PROMPT_LENS = (128, 256, 512, 512)      # per tier, per admission pass
N_REQUESTS, NEW_TOKENS = 16, 16
# engine prefill (bf16 weights and activations, Pallas kernels) against an
# fp32 reference of the same weights: bf16 keeps 8 mantissa bits, so each
# rounded activation carries up to 2^-9 relative error, and four layers of
# attention + 8-of-64 MoE compound it to about 1e-2 of the logits' norm.
# A wrong kernel (bad expert, bad row, bad mask) is off by O(1).
LOGIT_REL_TOL = 5e-2
EXPECTED_KERNELS = {
    "prefill": {"flash_attention", "lora_matmul", "topk_router",
                "ragged_expert_matmul"},
    # mixed per-slot k routes through the jnp adaptive router and
    # single-token attention has no flash structure
    "decode": {"lora_matmul", "ragged_expert_matmul"},
    "cohort": {"flash_attention", "lora_matmul", "lora_matmul_experts",
               "topk_router"},
}


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds spent in XLA's backend compiler (persistent-cache reads
    included) and the persistent cache's hits, from JAX's own monitoring
    events — so each phase can report compile and the rest apart, and say
    whether the cache was warm."""

    def __init__(self, jax):
        self.total, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def phase(name: str, clock: CompileClock, fn):
    c0, h0 = clock.total, clock.hits
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    comp = clock.total - c0
    print(f"[{name}] wall {wall:.3f} s = XLA compile {comp:.3f} s "
          f"+ the rest {wall - comp:.3f} s, {clock.hits - h0} programs "
          f"from the persistent cache", flush=True)
    return out


def kernels_in(lowered) -> set:
    """Names of the Pallas kernels a lowered program calls."""
    text = lowered.as_text()
    return {m.group(1) for line in text.splitlines()
            if "tpu_custom_call" in line
            for m in re.finditer(r'kernel_name = "(\w+)"', line)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind}) — nothing run", file=sys.stderr)
        return 1

    from repro.configs.base import FederatedConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.core import lora as lora_lib
    from repro.data.synthetic import DataConfig
    from repro.federated import client as client_lib
    from repro.federated.simulation import build_experiment
    from repro.kernels import backend as kb
    from repro.models import model as model_lib
    from repro.serving import Request, ServingEngine

    clock = CompileClock(jax)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}", flush=True)

    cfg = get_config("olmoe-1.3b-6.9b", "full").replace(num_layers=N_LAYERS)
    check(kb.resolve(cfg.kernels) == "pallas",
          f"backend resolves to {kb.resolve(cfg.kernels)!r} on the chip")
    check(not kb.resolve_interpret(cfg.kernels),
          "Pallas kernels would run under the interpreter on the chip")
    m = cfg.moe
    print(f"config: {cfg.name} cut 16 -> {cfg.num_layers} layers (depth "
          f"only); d_model {cfg.d_model}, {cfg.n_heads} heads x "
          f"{cfg.head_dim_}, qk_norm {cfg.qk_norm}, {m.num_experts} experts "
          f"x {m.d_expert} top-{m.top_k}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}, LoRA rank {cfg.lora.rank}; backend "
          f"{kb.resolve(cfg.kernels)}, seed {args.seed}", flush=True)

    key = jax.random.PRNGKey(args.seed)
    params = phase("init", clock, lambda: jax.block_until_ready(
        jax.jit(lambda k: model_lib.init_params(k, cfg))(key)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params} ({n_params * 2 / 1e9:.3f} GB bf16)",
          flush=True)

    # ------------------------------------------------------------ 1. train
    fed = FederatedConfig(num_clients=N_CLIENTS, rounds=N_ROUNDS,
                          round_driver="device", seed=args.seed)
    tc = TrainConfig(batch_size=4, local_epochs=1, seq_len=128)
    # the synthetic corpus draws tokens from a small vocabulary (it keeps a
    # vocab x vocab Markov table per cluster); the ids index the full
    # 50,304-row embedding like any others
    data = DataConfig(vocab_size=512, n_examples=80, seq_len=128,
                      prompt_len=32, n_clusters=4, seed=args.seed)
    exp = build_experiment(cfg, fed=fed, tc=tc, data=data,
                           base_params=params)
    server = exp.server
    lora0 = server.global_lora
    print("clients: " + ", ".join(
        f"k={c.k} ({c.dataset_size} examples)" for c in server.clients),
        flush=True)
    results = phase("train", clock, server.run)
    check(len(results) == N_ROUNDS, f"{len(results)} rounds ran")
    for res in results:
        losses = [float(x) for x in res.client_losses]
        print(f"round {res.round_idx}: client losses {losses}", flush=True)
        check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
        for f in res.client_freqs:
            check(f and all(float(np.sum(v)) > 0 for v in f.values()),
                  "a client activated no experts")
    delta = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(server.global_lora),
                                jax.tree.leaves(lora0)))
    print(f"global LoRA max |change| over {N_ROUNDS} rounds: {delta}",
          flush=True)
    check(delta > 0, "the global LoRA tree did not change")
    del lora0

    # ------------------------------------------------------------ 2. serve
    rescaler_by_k = {c.k: c.rescaler for c in server.clients
                     if c.k in set(SLOT_K)}
    slot_len = max(PROMPT_LENS) + NEW_TOKENS + 16
    engine = ServingEngine(cfg, params, lora=server.global_lora,
                           rescaler_by_k=rescaler_by_k,
                           num_slots=len(SLOT_K), slot_len=slot_len,
                           slot_k=SLOT_K, seed=args.seed)
    check(engine.paged and engine.dispatch == "ragged",
          f"engine defaults changed: paged={engine.paged} "
          f"dispatch={engine.dispatch}")
    rng = np.random.default_rng(args.seed)
    tiers = sorted(set(SLOT_K), reverse=True)
    requests = []
    for i in range(N_REQUESTS):
        k, j = tiers[i % len(tiers)], i // len(tiers)
        L = PROMPT_LENS[j % len(PROMPT_LENS)]
        requests.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, L, dtype=np.int32),
            max_new_tokens=NEW_TOKENS, k=k))
    report = phase("serve", clock, lambda: engine.run(requests))
    done = {c.rid: c for c in report.completions}
    check(sorted(done) == list(range(N_REQUESTS)),
          f"completed {sorted(done)} of {N_REQUESTS} requests")
    for c in done.values():
        check(len(c.tokens) == NEW_TOKENS and not c.truncated,
              f"request {c.rid} ended with {len(c.tokens)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in c.tokens),
              f"request {c.rid} emitted an out-of-vocabulary token")
    n_tok = sum(len(c.tokens) for c in done.values())
    print(f"served {len(done)} requests (k per slot {SLOT_K}), {n_tok} "
          f"tokens, {report.prefill_tokens} prefill tokens, "
          f"{len(report.decode_step_s)} decode steps", flush=True)

    # -------------------------------------------------------- 3. reference
    req = requests[0]
    prompt = jnp.asarray(req.prompt[None])
    real = jnp.ones((1,), jnp.float32)
    got = phase("prefill", clock, lambda: np.asarray(engine._prefill_fn(
        params, engine._prefill_trainable(req.k), prompt, real,
        k=req.k)[0])[0])
    want = phase("reference", clock, lambda: reference_logits(
        cfg, params, engine._prefill_trainable(req.k), prompt, req.k))
    err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"prefill logits, request {req.rid} (k={req.k}, {req.prompt_len} "
          f"tokens): |engine - fp32 reference| / |reference| = {err}, "
          f"max abs diff {float(np.max(np.abs(got - want)))}, argmax "
          f"{int(np.argmax(got))} vs {int(np.argmax(want))} "
          f"(tolerance {LOGIT_REL_TOL})", flush=True)
    check(np.all(np.isfinite(got)), "engine prefill logits not finite")
    check(err <= LOGIT_REL_TOL, f"logit error {err} > {LOGIT_REL_TOL}")

    # ---------------------------------------------------------- 4. kernels
    def lower_all():
        pool = engine.pool
        lowered = {
            "prefill": engine._prefill_fn.lower(
                params, engine._prefill_trainable(req.k), prompt, real,
                k=req.k),
            "decode": engine._decode_fn.lower(
                params, engine._decode_trainable, pool.cache,
                jnp.asarray(engine._last_tok), pool.positions(),
                jnp.ones((len(SLOT_K),), jnp.float32), pool.tables()),
        }
        c = server.clients[0]
        plan = client_lib.stack_plans(
            [client_lib.make_batch_plan(c, tc, round_seed=0)])
        stacked = lora_lib.stack_adapters(
            [lora_lib.make_trainable(server.global_lora, c.rescaler)])
        lowered["cohort"] = client_lib.cohort_update.lower(
            cfg, params, stacked, jnp.asarray(plan.tokens),
            jnp.asarray(plan.labels), jnp.asarray(plan.mask),
            jnp.asarray(plan.valid), k=c.k, tc=tc, rescaler_trainable=True)
        return {name: kernels_in(low) for name, low in lowered.items()}

    found = phase("lower", clock, lower_all)
    for name, want_k in EXPECTED_KERNELS.items():
        print(f"{name} program kernels: {sorted(found[name])}", flush=True)
        check(want_k <= found[name],
              f"{name} program lacks {sorted(want_k - found[name])}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


def reference_logits(cfg, params, trainable, tokens, k):
    """Last-position logits of an fp32 ``backend="reference"`` forward at
    "highest" matmul precision, one layer at a time.  Each layer's bf16
    weights are upcast inside that layer's own call, so the fp32 copy never
    exceeds one layer beside the engine's bf16 weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import KernelConfig
    from repro.models import model as model_lib
    from repro.models.layers import rms_norm

    f32 = jnp.float32
    rcfg = cfg.replace(dtype="float32",
                       kernels=KernelConfig(backend="reference"))
    up = partial(jax.tree.map, lambda x: x.astype(f32))
    blocks = params["blocks"]["pos0"]
    lora = trainable["lora"]["blocks"]["pos0"]
    resc = trainable.get("rescaler", {}).get("pos0")
    positions = jnp.arange(tokens.shape[1])

    @jax.jit
    def layer(p, lp, r, h):
        h, _, _ = model_lib._apply_block(
            rcfg, "attn", True, up(p), h, positions, lora=up(lp),
            rescaler=r, lora_scale=cfg.lora.scale, k=k, dispatch="ragged")
        return h

    @jax.jit
    def head(params, h):
        h = rms_norm(params["final_norm"].astype(f32), h[:, -1], cfg.rms_eps)
        return h @ params["lm_head"].astype(f32)

    with jax.default_matmul_precision("highest"):
        h = params["embed"]["tokens"][tokens].astype(f32)
        for i in range(cfg.num_layers):
            sl = partial(jax.tree.map, lambda x: x[i])
            h = layer(sl(blocks), sl(lora),
                      None if resc is None else resc[i], h)
        return np.asarray(head(params, h))[0]


if __name__ == "__main__":
    sys.exit(main())
