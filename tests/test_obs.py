"""Observability layer: metrics registry, span tracer, expert-load
telemetry — unit behaviour plus engine/federated integration.

Integration evidence mirrors the ISSUE acceptance bar: a mixed-tier
serving run with a tracer attached yields a schema-valid Chrome trace
with queued/prefill/decode spans for every completed request (and
balanced swap_out/swap_in pairs under preemption); a 3-round federated
run emits per-round activation-frequency drift with ``l1_drift`` None
on the first round and finite after.
"""
import gc
import glob
import json

import jax
import numpy as np
import pytest

from conftest import tiny_dense, tiny_moe
from repro.models import model as M
from repro.obs import (ActivationDriftTracker, Counter, ExpertLoadTracker,
                       Gauge, Histogram, MetricsRegistry, NULL_TRACER,
                       Tracer, entropy, exp_buckets, gini,
                       validate_chrome_trace)
from repro.obs.trace import PID_ENGINE, PID_REQUESTS, TID_GC
from repro.serving import Request, ServingEngine, SpeculativeConfig
from repro.serving.engine import ServingReport

CFG = tiny_moe()
PARAMS = M.init_params(jax.random.PRNGKey(0), CFG)
RNG = np.random.default_rng(3)


# ==========================================================================
# metrics primitives
# ==========================================================================

def test_counter_gauge_and_kind_mismatch():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)              # get-or-create returns the same
    reg.gauge("g").set(7.5)
    snap = reg.snapshot()
    assert snap["c"] == {"type": "counter", "value": 3.0}
    assert snap["g"] == {"type": "gauge", "value": 7.5}
    with pytest.raises(TypeError):
        reg.gauge("c")


def test_histogram_percentiles_track_exact():
    xs = RNG.uniform(0.1, 50.0, 2000)
    h = Histogram()
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs)
    assert h.min == pytest.approx(xs.min()) and h.max == pytest.approx(
        xs.max())
    # 15%-growth buckets: interpolated percentiles land within one
    # bucket (~7.5% relative) of the exact order statistic
    for q in (50, 90, 99):
        exact = float(np.percentile(xs, q))
        assert h.percentile(q) == pytest.approx(exact, rel=0.08)
    assert h.min <= h.percentile(0) <= h.percentile(100) <= h.max


def test_histogram_empty_and_single():
    h = Histogram()
    assert h.percentile(50) is None and h.mean is None
    s = h.snapshot()
    assert s["count"] == 0 and s["p50"] is None and s["buckets"] == []
    h.observe(3.0)
    assert h.percentile(50) == pytest.approx(3.0)
    assert h.percentile(99) == pytest.approx(3.0)


def test_registry_snapshot_json_safe_and_sources():
    reg = MetricsRegistry()
    reg.gauge("bad").set(float("inf"))   # non-finite becomes None
    reg.add_source(lambda r: r.gauge("live").set(11))
    ext = Histogram()
    ext.observe(1.0)
    reg.register("ext", ext)
    snap = reg.snapshot()
    json.dumps(snap, allow_nan=False)
    assert snap["bad"]["value"] is None
    assert snap["live"]["value"] == 11.0
    assert snap["ext"]["count"] == 1


def test_registry_dump_round_trips(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc(4)
    p = tmp_path / "m.json"
    reg.dump(str(p))
    assert json.loads(p.read_text())["n"]["value"] == 4.0


# ==========================================================================
# tracer primitives
# ==========================================================================

def test_tracer_ring_bound_and_dropped():
    tr = Tracer(ring=4)
    for i in range(10):
        tr.instant(f"e{i}", i * 1e-3)
    assert len(tr.events) == 4 and tr.dropped == 6
    assert tr.to_dict()["otherData"]["dropped_events"] == 6


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("x"):
        pass
    NULL_TRACER.complete("x", 0.0, 1.0)
    assert len(NULL_TRACER.events) == 0
    assert NULL_TRACER.flight_dump() is None
    with pytest.raises(RuntimeError):
        NULL_TRACER.dump("/dev/null")


def test_span_nesting_validates_and_dump(tmp_path):
    tr = Tracer()
    tr.process_name(PID_ENGINE, "engine")
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.counter("load", tr.now(), {"q": 2})
    assert validate_chrome_trace(tr.to_dict()) == []
    p = tmp_path / "t.json"
    tr.dump(str(p))
    loaded = json.loads(p.read_text())
    assert validate_chrome_trace(loaded) == []
    names = [e["name"] for e in loaded["traceEvents"]]
    assert "outer" in names and "inner" in names


def test_validator_flags_partial_overlap_and_bad_events():
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 0},
        {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 0},
        {"ph": "X", "ts": 0, "dur": 1, "pid": 1, "tid": 0},
        {"name": "c", "ph": "i", "ts": -2, "pid": 1, "tid": 0},
    ]}
    errs = validate_chrome_trace(bad)
    assert any("overlaps" in e for e in errs)
    assert any("missing" in e for e in errs)
    assert any("bad ts" in e for e in errs)
    assert validate_chrome_trace({}) == ["missing traceEvents list"]


def test_flight_recorder_dump(tmp_path):
    crash = tmp_path / "crash.json"
    tr = Tracer(ring=8, flight_path=str(crash))
    for i in range(20):
        tr.instant(f"e{i}", i * 1e-3)
    assert tr.flight_dump() == str(crash)
    loaded = json.loads(crash.read_text())
    assert validate_chrome_trace(loaded) == []
    kept = [e["name"] for e in loaded["traceEvents"] if e["ph"] == "i"]
    assert kept == [f"e{i}" for i in range(12, 20)]   # newest 8 survive


def test_null_tracer_span_is_one_shared_context():
    a, b = NULL_TRACER.span("a"), NULL_TRACER.span("b", args={"x": 1})
    assert a is b
    with a as sp:
        sp.set(y=2)
    assert len(NULL_TRACER.events) == 0


def test_gc_hook_records_collections_until_closed():
    before = len(gc.callbacks)
    tr = Tracer()
    assert len(gc.callbacks) == before + 1
    gc.collect()
    evs = [e for e in tr.events if e["name"] == "gc"]
    assert evs and evs[-1]["args"]["generation"] == 2
    assert evs[-1]["args"]["collected"] >= 0
    assert (evs[-1]["pid"], evs[-1]["tid"]) == (PID_ENGINE, TID_GC)
    tr.close()
    tr.close()                                 # idempotent
    assert len(gc.callbacks) == before
    n = len(tr.events)
    gc.collect()
    assert len(tr.events) == n
    dropped = Tracer()                         # never closed: unhooked
    del dropped                                # when it is collected
    gc.collect()
    assert len(gc.callbacks) == before


# ==========================================================================
# expert-load + drift primitives
# ==========================================================================

def test_gini_entropy_extremes():
    assert gini(np.ones(8)) == pytest.approx(0.0, abs=1e-12)
    assert entropy(np.ones(8)) == pytest.approx(1.0)
    hot = np.zeros(8)
    hot[3] = 10.0
    assert gini(hot) == pytest.approx(7 / 8)
    assert entropy(hot) == pytest.approx(0.0)
    assert gini([]) == 0.0 and entropy(np.zeros(4)) == 0.0


def test_expert_tracker_accumulates_and_publishes():
    t = ExpertLoadTracker(num_experts=4)
    t.observe_step({"pos0": np.array([[2, 1, 0, 1]])})
    t.observe_step({"pos0": np.array([[0, 1, 1, 0]])})
    snap = t.snapshot()
    assert snap["steps"] == 2 and snap["assignments_total"] == 6.0
    assert snap["totals"]["pos0"] == [[2.0, 2.0, 1.0, 1.0]]
    assert snap["hot_expert"] in (0, 1)
    json.dumps(snap, allow_nan=False)
    reg = MetricsRegistry()
    t.publish(reg)
    s = reg.snapshot()
    assert s["serving.experts.assignments_total"]["value"] == 6.0
    assert s["serving.experts.step_occupancy"]["count"] == 2


def test_activation_drift_tracker():
    d = ActivationDriftTracker()
    a = {"pos0": np.array([[0.5, 0.5, 0.0, 0.0]])}
    r0 = d.update(a)
    assert r0["pos0"]["l1_drift"] is None
    r1 = d.update(a)                           # identical -> zero drift
    assert r1["pos0"]["l1_drift"] == pytest.approx(0.0)
    b = {"pos0": np.array([[0.0, 0.5, 0.5, 0.0]])}
    r2 = d.update(b)
    assert r2["pos0"]["l1_drift"] == pytest.approx(1.0)   # 0.5+0.5 moved
    assert 0.0 <= r2["pos0"]["entropy_mean"] <= 1.0


# ==========================================================================
# serving integration: one instrumented mixed-tier run shared below
# ==========================================================================

@pytest.fixture(scope="module")
def traced_run():
    tracer = Tracer()
    reg = MetricsRegistry()
    eng = ServingEngine(CFG, PARAMS, num_slots=4, slot_len=16,
                        slot_k=(2, 2, 1, 1), tracer=tracer, metrics=reg,
                        expert_telemetry=True)
    reqs = [Request(rid=i,
                    prompt=RNG.integers(0, CFG.vocab_size, (8,))
                    .astype(np.int32),
                    max_new_tokens=6, k=(2 if i % 2 == 0 else 1))
            for i in range(6)]
    rep = eng.run(reqs)
    return eng, tracer, reg, rep


def test_trace_request_lifecycle_spans(traced_run, tmp_path):
    _, tracer, _, rep = traced_run
    trace = tracer.to_dict()
    assert validate_chrome_trace(trace) == []
    by_rid = {}
    for e in trace["traceEvents"]:
        if e["pid"] == PID_REQUESTS and e["ph"] == "X":
            by_rid.setdefault(e["tid"], set()).add(e["name"])
    for c in rep.completions:                  # every completed request
        assert {"request", "queued", "prefill", "decode"} <= by_rid[c.rid]
    engine_names = {e["name"] for e in trace["traceEvents"]
                    if e["pid"] == PID_ENGINE and e["ph"] == "X"}
    assert {"engine.iteration", "engine.admit", "engine.prefill",
            "engine.decode", "engine.sample"} <= engine_names
    p = tmp_path / "serve-trace.json"
    tracer.dump(str(p))                        # strict-JSON round trip
    assert validate_chrome_trace(json.loads(p.read_text())) == []


def _reqs(n, new_tokens=6, plen=8):
    return [Request(rid=i, prompt=RNG.integers(0, CFG.vocab_size, (plen,))
                    .astype(np.int32),
                    max_new_tokens=new_tokens, k=(2 if i % 2 == 0 else 1))
            for i in range(n)]


def _engine_spans(events):
    return [e for e in events
            if e["ph"] == "X" and e["name"].startswith("engine.")]


def test_engine_spans_reach_profiler_trace(tmp_path):
    """The engine's live spans land in a jax.profiler trace's host plane,
    args as event stats, with the tracer's own durations."""
    from jax.profiler import ProfileData
    tracer = Tracer()
    eng = ServingEngine(CFG, PARAMS, num_slots=4, slot_len=16,
                        slot_k=(2, 2, 1, 1), tracer=tracer)
    eng.run(_reqs(4))                          # compile outside the trace
    tracer.events.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run(_reqs(6))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    prof = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        prof.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns / 1e3,
                             dict(e.stats)))
    tracer.close()
    mem = {}
    for e in _engine_spans(tracer.events):
        mem.setdefault(e["name"], []).append((e["ts"], e["dur"],
                                              e.get("args", {})))
    assert set(prof) == set(mem) == {
        "engine.iteration", "engine.admit", "engine.prefill",
        "engine.decode", "engine.sample"}
    for name, got in prof.items():
        want = mem[name]
        assert len(got) == len(want), name
        for (_, dur_p, stats), (_, dur_m, args) in zip(sorted(got),
                                                       sorted(want)):
            assert abs(dur_p - dur_m) < 100.0, (name, dur_p, dur_m)
            assert stats == args, name
    phases = {a["phase"] for _, _, a in mem["engine.sample"]}
    assert phases == {"decode", "prefill"}


def test_tracer_shared_across_runs_keeps_one_clock():
    tracer = Tracer()
    eng = ServingEngine(CFG, PARAMS, num_slots=4, slot_len=16,
                        slot_k=(2, 2, 1, 1), tracer=tracer)
    eng.run(_reqs(4))
    first = len(_engine_spans(tracer.events))
    eng.run(_reqs(4))
    tracer.close()
    events = list(tracer.events)
    its = sorted((e["ts"], e["ts"] + e["dur"])
                 for e in events if e["name"] == "engine.iteration")
    assert len(_engine_spans(events)) > first
    for (_, end), (start, _) in zip(its, its[1:]):
        assert start >= end
    reqs = [e for e in events
            if e["pid"] == PID_REQUESTS and e["name"] == "request"]
    by_tid = {}
    for e in reqs:
        by_tid.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
    for spans in by_tid.values():             # each rid once per run
        assert len(spans) == 2 and spans[0][1] <= spans[1][0]
    assert validate_chrome_trace(tracer.to_dict()) == []


@pytest.mark.parametrize("spec", [None, SpeculativeConfig(window=3,
                                                          draft_k=1)],
                         ids=["decode", "speculative"])
def test_completion_token_times(spec):
    eng = ServingEngine(CFG, PARAMS, num_slots=4, slot_len=24,
                        slot_k=(2, 2, 2, 2), speculative=spec)
    rep = eng.run([Request(rid=i, prompt=RNG.integers(
        0, CFG.vocab_size, (6,)).astype(np.int32), max_new_tokens=3 + i,
        k=2) for i in range(6)])
    for c in rep.completions:
        t = c.token_t
        assert t.dtype == np.float64 and t.shape == (c.n_generated,)
        assert np.all(np.diff(t) >= 0)
        assert c.first_token <= t[0] and t[-1] <= c.finished


def test_engine_step_counters(traced_run):
    _, _, reg, rep = traced_run
    snap = reg.snapshot()
    assert snap["serving.sample.draws"]["value"] == sum(
        c.n_generated for c in rep.completions)
    assert snap["serving.decode.steps"]["value"] == len(rep.decode_step_s)
    assert snap["serving.prefill.calls"]["value"] == len(rep.prefill_s)
    assert snap["serving.prefill.rows"]["value"] == len(rep.completions)


def test_engine_registry_snapshot(traced_run):
    _, _, reg, rep = traced_run
    snap = reg.snapshot()
    json.dumps(snap, allow_nan=False)
    assert snap["serving.completions"]["value"] == len(rep.completions)
    assert snap["serving.gen_tokens"]["value"] == sum(
        c.n_generated for c in rep.completions)
    assert snap["serving.decode_step_ms"]["count"] == len(rep.decode_step_s)
    assert snap["serving.kv.num_slots"]["value"] == 4.0
    assert snap["serving.scheduler.enqueued_total"]["value"] >= 6
    assert snap["serving.experts.assignments_total"]["value"] > 0


def test_summary_decode_step_percentiles(traced_run):
    _, _, _, rep = traced_run
    s = rep.summary()
    lo = min(rep.decode_step_s) * 1e3
    hi = max(rep.decode_step_s) * 1e3
    assert lo * 0.999 <= s["decode_step_ms_p50"] <= s["decode_step_ms_p99"]
    assert s["decode_step_ms_p99"] <= hi * 1.001
    json.dumps(s, allow_nan=False)


def test_engine_expert_load_snapshot(traced_run):
    _, _, _, rep = traced_run
    el = rep.expert_load
    assert el["steps"] == len(rep.decode_step_s)
    assert el["num_experts"] == CFG.moe.num_experts
    assert el["assignments_total"] > 0
    assert 0.0 <= el["gini"] <= 1.0 and 0.0 <= el["entropy"] <= 1.0
    total = sum(sum(sum(row) for row in t) for t in el["totals"].values())
    assert total == pytest.approx(el["assignments_total"])
    assert rep.summary()["expert_load"]["hot_expert"] == el["hot_expert"]


def test_preemption_swap_spans_balanced():
    """The test_traffic preemption scenario, traced: every swap-out has
    a matching swap-in instant and a ``swapped_out`` span covering the
    off-device interval."""
    tracer = Tracer()
    eng = ServingEngine(CFG, PARAMS, num_slots=2, slot_len=48,
                        slot_k=(2, 1), kv_layout="paged", block_size=4,
                        num_blocks=14, preemption=True,
                        slo_ms={2: 0.0, 1: 60000.0}, tracer=tracer)
    rep = eng.run([
        Request(rid=0, prompt=RNG.integers(0, CFG.vocab_size, (8,))
                .astype(np.int32), max_new_tokens=40, k=1, arrival=0.0),
        Request(rid=1, prompt=RNG.integers(0, CFG.vocab_size, (8,))
                .astype(np.int32), max_new_tokens=4, k=2, arrival=0.02),
    ])
    assert rep.preemptions >= 1
    evs = list(tracer.events)
    outs = [e for e in evs if e["name"] == "swap_out"]
    ins = [e for e in evs if e["name"] == "swap_in"]
    gaps = [e for e in evs if e["name"] == "swapped_out"]
    assert len(outs) == len(ins) == len(gaps) == rep.preemptions
    for o, g in zip(outs, gaps):               # gap starts at its swap-out
        assert g["ts"] == pytest.approx(o["ts"], abs=1.0)
        assert g["dur"] > 0
    assert validate_chrome_trace(tracer.to_dict()) == []


def test_speculative_summary_percentiles():
    eng = ServingEngine(CFG, PARAMS, num_slots=3, slot_len=16,
                        slot_k=(2, 2, 2), kv_layout="paged", block_size=4,
                        speculative=SpeculativeConfig(window=3, draft_k=1))
    reqs = [Request(rid=i, prompt=RNG.integers(0, CFG.vocab_size, (6,))
                    .astype(np.int32), max_new_tokens=6, k=2)
            for i in range(3)]
    s = eng.run(reqs).summary()
    for key in ("draft_step_ms_p50", "draft_step_ms_p99",
                "verify_step_ms_p50", "verify_step_ms_p99"):
        assert s[key] is not None and s[key] > 0.0
    assert s["draft_step_ms_p50"] <= s["draft_step_ms_p99"]
    assert s["verify_step_ms_p50"] <= s["verify_step_ms_p99"]
    json.dumps(s, allow_nan=False)


def test_zero_completion_summary_is_json_safe():
    """Regression: summary()/per_tier() on a run with no completions
    must return None fields, never NaN (json.dumps(nan) emits invalid
    JSON) and never raise on empty percentile input."""
    rep = ServingReport(completions=[])
    s = rep.summary()
    assert s["n_requests"] == 0 and s["gen_tokens"] == 0
    for key in ("ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
                "latency_p50_ms", "latency_p95_ms",
                "decode_step_ms_mean", "decode_step_ms_p50",
                "decode_step_ms_p99"):
        assert s[key] is None, key
    assert rep.per_tier() == {}
    assert "NaN" not in json.dumps(s, allow_nan=False)


def test_expert_telemetry_rejects_bad_combos():
    dense = tiny_dense()
    dparams = M.init_params(jax.random.PRNGKey(1), dense)
    with pytest.raises(ValueError, match="MoE"):
        ServingEngine(dense, dparams, num_slots=2, slot_len=16,
                      expert_telemetry=True)
    with pytest.raises(ValueError, match="speculative"):
        ServingEngine(CFG, PARAMS, num_slots=2, slot_len=16,
                      slot_k=(2, 2), expert_telemetry=True,
                      speculative=SpeculativeConfig(window=3, draft_k=1))


# ==========================================================================
# federated integration: 3 rounds -> drift series + metrics/trace files
# ==========================================================================

def test_federated_round_drift_metrics_and_trace(tmp_path):
    from repro.configs.base import FederatedConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.data.synthetic import DataConfig
    from repro.federated.simulation import build_experiment

    cfg = get_config("olmoe-1.3b-6.9b", "smoke")
    fed = FederatedConfig(num_clients=2, rounds=3, method="flame",
                          temperature=2)
    exp = build_experiment(
        cfg, fed=fed, tc=TrainConfig(batch_size=8, local_epochs=1),
        data=DataConfig(vocab_size=cfg.vocab_size, n_examples=96,
                        seq_len=64, n_clusters=4))
    mpath = tmp_path / "fed-metrics.json"
    tpath = tmp_path / "fed-trace.json"
    history = exp.server.run(metrics_to=str(mpath), trace_to=str(tpath))
    assert len(history) == 3

    # drift: None on the first observed round, finite after
    for r, res in enumerate(history):
        assert res.activation_drift, f"round {r} recorded no drift"
        for pos, d in res.activation_drift.items():
            assert 0.0 <= d["entropy_mean"] <= 1.0
            if r == 0:
                assert d["l1_drift"] is None
            else:
                assert d["l1_drift"] is not None
                assert 0.0 <= d["l1_drift"] <= 2.0

    snap = json.loads(mpath.read_text())
    assert snap["fed.rounds"]["value"] == 3.0
    assert snap["fed.participants"]["value"] == 2.0
    assert any(k.startswith("fed.activation.entropy.") for k in snap)
    assert any(k.startswith("fed.activation.l1_drift.") for k in snap)

    trace = json.loads(tpath.read_text())
    assert validate_chrome_trace(trace) == []
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
    for r in range(3):
        assert f"round {r}" in names
    for phase in ("distribute", "cohort_update", "aggregate"):
        assert names.count(phase) >= 3        # once per round at least
