"""BENCHMARK.json and every file it names: present, well formed, and
within the limits the benchmark format sets; the entry fails without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

import bench_path  # noqa: F401
import run as R
from harness import model_cfg, traffic

BENCH = R.load_bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"] for m in BENCH["end_to_end"]}
CELLS = {w["name"] for w in BENCH["workloads"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in E2E
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_one_line_fields():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", CELLS)) <= CELLS


def test_per_layer_metrics_name_their_cells_and_readers():
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in E2E and set(m["workloads"]) <= CELLS
        assert (R.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert callable(R.reader(m["name"]))
    for m in BENCH["end_to_end"]:
        assert callable(R.reader(m["name"]))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_load(w):
    assert w["chips"] in (1, 4)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    s = model_cfg.sizes(model_cfg.load(R.ROOT / conf["file"]))
    tp = json.loads((R.BENCH / "traffic" / f"{w['traffic']}.json")
                    .read_text())
    cell = json.loads((R.BENCH / "cells" / f"{w['name']}.json").read_text())
    assert cell["limits"]
    assert (R.BENCH / "harness" / f"{tp['runner']}.py").is_file()
    if tp["runner"] == "serve":
        reqs = traffic.make_trace(tp, s.vocab, 2 ** 31 + 1)
        assert len(reqs) == tp["requests_per_call"]
        assert set(traffic.slot_k(tp)) == {r.k for r in reqs}
    # every cell reports set-up, another end-to-end metric and a
    # per-layer one
    e2e = [m["name"] for m in R.cell_metrics(BENCH, w["name"],
                                             "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert R.cell_metrics(BENCH, w["name"], "per_layer")


def test_configs_used_and_reduced_keys_listed():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        conf = model_cfg.load(R.ROOT / c["file"])
        assert set(c["reduced"]) == set(conf["reduced"])
        assert c["source"] == conf["source"]
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size"))


def test_exits_nonzero_without_a_tpu():
    w = BENCH["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(R.BENCH / "run.py"),
                        "--workload", w, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=R.ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 1 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
