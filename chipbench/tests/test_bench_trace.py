"""Trace reduction: busy union, idle gaps, kernel time by program, idle
time by host span — on hand-built events and on a trace recorded on one
TPU v5e by tests/data/record_trace.py."""
from pathlib import Path

import pytest

import bench_path  # noqa: F401
from harness import trace as tr

DATA = Path(__file__).resolve().parent / "data"

OPS = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50), ("a", 45, 60),
       ("d", 70, 75)]


def test_union_and_busy():
    assert tr.union([(s, e) for _, s, e in OPS]) == [(10, 30), (40, 60),
                                                    (70, 75)]
    assert tr.busy_ns(OPS, 0, 100) == 20 + 20 + 5
    assert tr.busy_ns(OPS, 25, 72) == 5 + 20 + 2


def test_gaps():
    assert tr.gaps(OPS, 0, 100) == [(0, 10), (30, 40), (60, 70), (75, 100)]
    assert tr.gaps(OPS, 12, 65) == [(30, 40), (60, 65)]


def test_top_ops_and_within():
    mods = [("m1", 5, 32), ("m2", 38, 62)]
    got = tr.within(OPS, mods)
    assert [[e[0] for e in g] for g in got] == [["a", "b"], ["c", "a"]]
    assert tr.top_ops(OPS, mods, 0, 100, n=2) == [("m1:b", 15),
                                                  ("m2:a", 15)]
    loop = [("while.3", 5, 30)] + OPS
    assert tr.top_ops(loop, mods, 0, 100, n=1) == [("m1:b", 15)]


def test_names():
    hlo = ("%ragged_expert_matmul.12 = bf16[8,1024]{1,0} custom-call(bf16"
           "[8,2048]{1,0} %fusion.3), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(hlo) == "ragged_expert_matmul.12"
    assert tr.is_kernel("ragged_expert_matmul.12", "ragged_expert_matmul")
    assert not tr.is_kernel("ragged_expert_matmul_x.1",
                            "ragged_expert_matmul")
    assert tr.family("fusion.31") == "fusion"
    assert tr.family("copy-start") == "copy-start"
    assert tr.module_name("jit__decode_fn(123)") == "jit__decode_fn"


def test_idle_by_host_innermost_span():
    host = [("window", 0, 100), ("call", 0, 35), ("sample", 28, 33),
            ("call", 38, 80)]
    got = dict(tr.idle_by_host(OPS, host, 0, 100, outside="harness"))
    # idle: (0,10) call; (30,40): 30-33 sample, 33-35 call, 35-38 window,
    # 38-40 call; (60,70) call; (75,100): 75-80 call, 80-100 window
    assert got == {"call": 10 + 2 + 2 + 10 + 5, "sample": 3,
                   "window": 3 + 20}
    assert dict(tr.idle_by_host(OPS, [], 0, 100)) == {"no_span": 55}


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "small.xplane.pb"
    if not path.is_file():
        pytest.fail(f"{path} missing: record it with record_trace.py")
    return tr.load(str(path), ("window", "call"))


def test_recorded_trace(recorded):
    t = recorded
    win = [e for e in t.host if e[0] == "window"]
    calls = [e for e in t.host if e[0] == "call"]
    assert len(win) == 1 and len(calls) == 5
    lo, hi = win[0][1], win[0][2]
    chip = min(t.ops)
    busy = tr.busy_ns(t.ops[chip], lo, hi)
    assert 0 < busy < hi - lo
    steps = [m for m in t.modules[chip] if m[0] == "jit_step"]
    assert len(steps) == 5
    kern = [e for e in t.ops[chip] if tr.is_kernel(e[0], "probe_kernel")]
    per_step = tr.within(kern, steps)
    assert [len(g) for g in per_step] == [2] * 5
    # every program ran inside a call span: the profiler's device and host
    # timelines agree to about a millisecond (here the device's is 1.0 to
    # 1.3 ms early), so allow 5 ms
    skew = 5_000_000
    for _, s, e in steps:
        assert any(cs - skew <= s and e <= ce + skew for _, cs, ce in calls)
    top = dict(tr.top_ops(t.ops[chip], t.modules[chip], lo, hi))
    assert top["jit_step:probe_kernel"] > 0
    # the host slept 20 ms after each call, inside the window span only
    idle = dict(tr.idle_by_host(t.ops[chip], t.host, lo, hi))
    assert idle["window"] >= 5 * 20e6 * 0.9
