"""The program's own spans beside the harness's: with the engine's tracer
on, the trace loader returns the same harness events as with it off, so
the breakdown cannot move; and the per-token gap reader ``itl_p99_ms`` on
hand-built completions, on a program without per-token times, and on a
toy cell's completions."""
import glob
from types import SimpleNamespace

import numpy as np
import pytest

import bench_path  # noqa: F401
import run as R
from harness import clock, serve, trace as tr


def _tiny_engine_run(tmp_path, tracer):
    import jax
    from repro.configs.base import LoRAConfig, ModelConfig, MoEConfig
    from repro.models import model as M
    from repro.serving import Request, ServingEngine
    cfg = ModelConfig(
        name="tiny-moe", family="moe", num_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=0, vocab_size=128, head_dim=16,
        moe=MoEConfig(num_experts=4, top_k=2, d_expert=64),
        lora=LoRAConfig(rank=4), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    Engine = serve.engine_class(ServingEngine, clock.Spans(jax, True))
    eng = Engine(cfg, params, num_slots=4, slot_len=16, slot_k=(2, 2, 1, 1),
                 tracer=tracer)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(0, 128, (6,)).astype(
        np.int32), max_new_tokens=4, k=(2, 1)[i % 2]) for i in range(6)]
    eng.run(reqs)                                   # compile first
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    return tr.load(path, serve.SPANS)


def test_breakdown_reads_the_same_spans_with_engine_tracing(tmp_path):
    from repro.obs import Tracer
    off = _tiny_engine_run(tmp_path / "off", None)
    tracer = Tracer()
    on = _tiny_engine_run(tmp_path / "on", tracer)
    tracer.close()
    assert any(e["name"].startswith("engine.") for e in tracer.events)
    assert [e[0] for e in on.host] == [e[0] for e in off.host]
    assert {"admit", "decode_step", "sample"} <= {e[0] for e in on.host}


def _comp(rid, times):
    return SimpleNamespace(rid=rid, token_t=np.asarray(times, np.float64))


def test_itl_reader_exact_percentile():
    read = R.reader("itl_p99_ms")
    gaps = np.arange(1, 201, dtype=np.float64) * 1e-3      # 1..200 ms
    comps = [_comp(0, np.concatenate([[0.0], np.cumsum(gaps[:150])])),
             _comp(1, np.concatenate([[5.0], 5.0 + np.cumsum(gaps[150:])])),
             _comp(2, [9.0])]                              # one token
    ctx = SimpleNamespace(data={"completions": comps})
    want = float(np.percentile(gaps * 1e3, 99))
    assert read(ctx) == pytest.approx(want, rel=1e-9)


def test_itl_reader_without_token_times():
    read = R.reader("itl_p99_ms")
    ctx = SimpleNamespace(data={"completions": [SimpleNamespace(rid=0)]})
    assert read(ctx) is None
    ctx = SimpleNamespace(data={"completions": [_comp(0, [1.0])]})
    assert read(ctx) is None


def test_itl_reader_on_a_toy_cell():
    from serve_cells import tiny_cell
    ctx, out = tiny_cell(2 ** 31 + 23)
    assert out["correct"], out["checks"]
    comps = ctx.data["completions"]
    v = R.reader("itl_p99_ms")(ctx)
    gaps = np.concatenate([np.diff(c.token_t) for c in comps])
    assert gaps.size == sum(c.n_generated - 1 for c in comps) > 0
    assert v == pytest.approx(float(np.percentile(gaps, 99)) * 1e3)
