"""Serving launcher: the continuous-batching engine on this host's devices.

``--local`` runs the adaptive-k serving engine (repro.serving) over a
synthetic open-loop workload on a reduced config — a real request queue,
block-paged KV pool (``--kv-layout paged``, sized by ``--block-size`` /
``--num-blocks``; ``--kv-layout slotted`` for the legacy fixed-slot
pool), batched prefill and one compiled mixed-k decode step, reporting
throughput and TTFT/latency percentiles; without ``--local`` it builds
the sharded serve step for the production mesh (use repro.launch.dryrun
in this offline container).

Production-traffic knobs (docs/serving.md): ``--prefix-cache`` (shared
prompt blocks pay KV once), ``--preemption`` + ``--slo-ms K:MS,...``
(per-tier TTFT targets driving EDF admission and decode swap-out), and
trace shaping via ``--arrival {poisson,diurnal,burst}``,
``--length-dist {categorical,zipf}``, ``--shared-prefix N``.

  PYTHONPATH=src python -m repro.launch.serve --local \
      --arch olmoe-1.3b-6.9b --slots 8 --mix 8:0.5,1:0.5 \
      --requests 16 --rate 20 --new-tokens 16 --block-size 16 \
      --prefix-cache --shared-prefix 4 --slo-ms 8:250,1:2000 --preemption
"""
from __future__ import annotations

import argparse

import jax

from ..configs.base import INPUT_SHAPES
from ..configs.registry import get_config
from ..models import model as model_lib
from ..obs import MetricsRegistry, Tracer
from ..serving import (ServingEngine, SpeculativeConfig, WorkloadConfig,
                       make_trace)
from . import steps as steps_lib
from .compile_cache import use_compile_cache
from .mesh import make_production_mesh


def parse_mix(spec: str, top_k: int):
    """``"8:0.5,1:0.5"`` -> tier mix tuple; ``""`` -> uniform top_k."""
    if not spec:
        return ((top_k, 1.0),)
    out = []
    for part in spec.split(","):
        k, frac = part.split(":")
        out.append((int(k), float(frac)))
    return tuple(out)


def slot_k_for_mix(mix, num_slots: int):
    """Partition the slot pool proportionally to the tier mix.

    Every tier keeps >= 1 slot — a tier with zero slots but nonzero
    traffic would strand its requests in the queue (the engine raises once
    nothing else is runnable)."""
    if num_slots < len(mix):
        raise SystemExit(f"--slots {num_slots} < {len(mix)} tiers in --mix;"
                         " every tier needs at least one slot")
    total = sum(f for _, f in mix)
    counts = [max(1, round(num_slots * f / total)) for _, f in mix]
    while sum(counts) > num_slots:
        counts[counts.index(max(counts))] -= 1   # > 1: len(mix) <= num_slots
    while sum(counts) < num_slots:
        counts[counts.index(min(counts))] += 1
    slot_k = []
    for (k, _), n in zip(mix, counts):
        slot_k.extend([k] * n)
    return tuple(slot_k)


def parse_slo(spec: str):
    """``"8:150,1:1000"`` -> per-tier TTFT targets {k: ms}; ``""`` -> None.

    A single bare number (``"250"``) has no tier to attach to — require
    the k:ms form so the target unambiguously names a tier."""
    if not spec:
        return None
    out = {}
    for part in spec.split(","):
        try:
            k, ms = part.split(":")
            out[int(k)] = float(ms)
        except ValueError:
            raise SystemExit(f"--slo-ms: bad entry {part!r} "
                             "(expected K:MILLISECONDS[,K:MS...])")
    return out


def build_parser() -> argparse.ArgumentParser:
    """The serving launcher's CLI (kept separate from :func:`main` so
    tools/docs_check.py can verify every flag docs/serving.md names)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1.3b-6.9b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--k", type=int, default=None,
                    help="uniform serving budget (all slots / production "
                         "step); shorthand for --mix K:1.0 with --local")
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--slot-len", type=int, default=48)
    ap.add_argument("--kv-layout", choices=("paged", "slotted"),
                    default="paged",
                    help="paged: block-paged KV pool (admission follows "
                         "block availability); slotted: one fixed-capacity "
                         "slot per request (the PR 3 layout)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV tokens per page block (paged layout)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="usable KV blocks in the pool; default sizes the "
                         "pool so every slot can hold a max-length request "
                         "— set lower to make blocks the scarce resource")
    ap.add_argument("--dispatch", choices=("ragged", "dense", "capacity"),
                    default="ragged",
                    help="MoE token dispatch: ragged = sort-based, "
                         "loss-free AND sum(slot_k)-proportional (default); "
                         "dense = loss-free one-hot at worst-case padding; "
                         "capacity = GShard capacity-limited throughput "
                         "mode (batching may change results)")
    ap.add_argument("--speculate", action="store_true",
                    help="self-speculative decoding: draft a window of "
                         "tokens per slot at --draft-k through the same "
                         "weights, verify it in one full-k step, accept "
                         "by the exact rejection rule "
                         "(serving/speculative.py)")
    ap.add_argument("--window", type=int, default=4,
                    help="speculative draft window W (tokens drafted per "
                         "round and verified in one step)")
    ap.add_argument("--draft-k", type=int, default=1,
                    help="expert budget for the draft pass (the cheap k)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed prompt block sharing in the "
                         "paged pool (refcounts + copy-on-write): requests "
                         "with a common system prompt pay its KV once")
    ap.add_argument("--preemption", action="store_true",
                    help="SLO-driven decode preemption: swap the most "
                         "lenient-deadline active request out to host when "
                         "a waiter misses its TTFT target (needs --slo-ms "
                         "and the paged layout)")
    ap.add_argument("--slo-ms", default="",
                    help="per-tier TTFT targets K:MS[,K:MS...] — switches "
                         "admission to earliest-deadline-first and adds "
                         "per-tier SLO attainment to the report")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=float("inf"),
                    help="mean arrival rate (req/s); inf = closed batch")
    ap.add_argument("--arrival", choices=("poisson", "diurnal", "burst"),
                    default="poisson",
                    help="arrival process around --rate: homogeneous "
                         "Poisson, sinusoidal day/night modulation, or "
                         "periodic flash-crowd bursts (serving/workload.py)")
    ap.add_argument("--length-dist", choices=("categorical", "zipf"),
                    default="categorical",
                    help="output-length distribution: fixed --new-tokens, "
                         "or a heavy Zipf tail capped at 64")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="shared system-prompt tokens at the head of every "
                         "prompt (exercises --prefix-cache)")
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--mix", default="",
                    help="tier mix k:frac[,k:frac...] (FLAME adaptive-k); "
                         "empty = full top_k everywhere")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON of the run "
                         "(request lifecycle + engine-loop spans; open in "
                         "Perfetto).  Also arms the flight recorder: on an "
                         "engine exception the trace ring is dumped to "
                         "PATH.crash.json (repro.obs.trace)")
    ap.add_argument("--metrics-out", default="",
                    help="write a metrics-registry JSON snapshot after the "
                         "run: engine counters, step-time histograms, KV "
                         "pool / scheduler gauges (repro.obs.metrics)")
    ap.add_argument("--expert-telemetry", action="store_true",
                    help="per-decode-step expert activation counts, "
                         "host-side: occupancy histogram, gini/entropy, "
                         "hot expert in the report (MoE archs, not with "
                         "--speculate)")
    ap.add_argument("--multi-pod", action="store_true")
    return ap


def main() -> None:
    use_compile_cache()
    args = build_parser().parse_args()

    if not args.local:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cfg = get_config(args.arch, "full")
        shape = INPUT_SHAPES[args.shape]
        with mesh:
            bundle = steps_lib.build_serve(cfg, shape, mesh, k=args.k)
            print(f"serve_step for {cfg.name} × {shape.name} on "
                  f"{mesh.devices.shape}: cache "
                  f"{bundle.meta['cache_bytes'] / 2 ** 30:.1f} GiB global, "
                  f"k={bundle.meta['k']}")
            print("lowering...")
            compiled = bundle.fn.lower(*bundle.args).compile()
            mem = compiled.memory_analysis()
            print(f"compiled; {mem.temp_size_in_bytes / 2 ** 30:.2f} GiB "
                  f"temp/device — ready for real hardware")
        return

    # ---- local: the continuous-batching engine over a synthetic trace ----
    cfg = get_config(args.arch, "smoke")
    if cfg.num_codebooks > 0:
        raise SystemExit(f"{cfg.name}: the serving engine is text-only; "
                         "codebook (audio) archs have no engine path yet")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    top_k = cfg.moe.top_k if cfg.moe.enabled else 0
    if args.k is not None and top_k:
        if args.mix:
            raise SystemExit("--k and --mix are mutually exclusive; "
                             "--k N is shorthand for --mix N:1.0")
        args.mix = f"{args.k}:1.0"       # uniform reduced-k pool
    mix = parse_mix(args.mix, top_k) if top_k else ()
    bad = [k for k, _ in mix if not 1 <= k <= cfg.moe.num_experts]
    if bad:
        raise SystemExit(
            f"--mix tiers {bad} out of range: {cfg.name} (the --local "
            f"reduced config) has {cfg.moe.num_experts} experts")
    slot_k = slot_k_for_mix(mix, args.slots) if mix else None

    # prompts must leave room for at least one generated token in a slot
    prompt_lens = tuple(L for L in (8, 16) if L + 1 <= args.slot_len)
    if not prompt_lens:
        raise SystemExit(f"--slot-len {args.slot_len} too small for the "
                         "workload's 8-token prompts (need >= 9)")
    if args.shared_prefix and args.shared_prefix >= min(prompt_lens):
        raise SystemExit(f"--shared-prefix {args.shared_prefix} must be "
                         f"shorter than the shortest prompt "
                         f"({min(prompt_lens)} tokens)")
    wl = WorkloadConfig(
        n_requests=args.requests, rate=args.rate,
        prompt_lens=prompt_lens, new_tokens=(args.new_tokens,),
        tier_mix=mix, vocab_size=cfg.vocab_size,
        arrival=args.arrival, length_dist=args.length_dist,
        shared_prefix_len=args.shared_prefix)
    slo = parse_slo(args.slo_ms)
    spec = None
    if args.speculate:
        if not cfg.moe.enabled:
            raise SystemExit(f"--speculate needs an MoE arch: {cfg.name} "
                             "has no cheaper draft budget")
        spec = SpeculativeConfig(window=args.window, draft_k=args.draft_k)
    tracer = (Tracer(flight_path=f"{args.trace_out}.crash.json")
              if args.trace_out else None)
    registry = MetricsRegistry() if args.metrics_out else None
    if args.expert_telemetry and not cfg.moe.enabled:
        raise SystemExit(f"--expert-telemetry needs an MoE arch: "
                         f"{cfg.name} routes nothing to observe")
    engine = ServingEngine(cfg, params, num_slots=args.slots,
                           slot_len=args.slot_len, slot_k=slot_k,
                           kv_layout=args.kv_layout,
                           block_size=args.block_size,
                           num_blocks=args.num_blocks,
                           dispatch=args.dispatch,
                           speculative=spec,
                           prefix_cache=args.prefix_cache,
                           preemption=args.preemption,
                           slo_ms=slo,
                           tracer=tracer, metrics=registry,
                           expert_telemetry=args.expert_telemetry)
    pool_desc = (f"{engine.pool.num_blocks} x {engine.pool.block_size}"
                 f"-token KV blocks" if engine.paged
                 else "slotted KV pool")
    spec_desc = (f", speculative W={args.window} draft_k={args.draft_k}"
                 if spec else "")
    traffic = [flag for flag, on in
               (("prefix-cache", args.prefix_cache),
                ("preemption", args.preemption),
                (f"slo={args.slo_ms}", bool(slo))) if on]
    traffic_desc = f", {' '.join(traffic)}" if traffic else ""
    print(f"{cfg.name}: {args.slots} slots × {args.slot_len} tokens "
          f"({pool_desc}), slot_k={engine.slot_k}, "
          f"dispatch={engine.dispatch}{spec_desc}{traffic_desc}")
    report = engine.run(make_trace(wl))
    for key, val in report.summary().items():
        print(f"  {key}: {val:.2f}" if isinstance(val, float)
              else f"  {key}: {val}")
    if tracer is not None:
        tracer.close()
        print(f"trace: {tracer.dump(args.trace_out)} "
              f"({len(tracer.events)} events — open in Perfetto)")
    if registry is not None:
        registry.dump(args.metrics_out)
        print(f"metrics: {args.metrics_out}")


if __name__ == "__main__":
    main()
