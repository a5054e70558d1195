"""Distribution-layer tests that run on the real (single-CPU) device:
sharding rules are structurally valid, step builders execute end-to-end on
a 1×1 mesh with the production axis names, FLOPs model reproduces Table 1."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import tiny_moe
from repro.configs.base import INPUT_SHAPES, ShapeConfig, TrainConfig
from repro.configs.registry import ASSIGNED_ARCHS, get_config
from repro.core import flops as F
from repro.launch import sharding as shd
from repro.launch import specs as specs_lib
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_local_mesh


# ---------------------------------------------------------------- specs

@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_rank_matches_leaves(arch):
    cfg = get_config(arch, "full")
    mesh = make_local_mesh()
    a = specs_lib.abstract_params(cfg)
    spec = shd.param_specs(cfg, a, mesh)
    flat_a = jax.tree_util.tree_leaves_with_path(a)
    flat_s = jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_a) == len(flat_s)
    for (path, leaf), s in zip(flat_a, flat_s):
        assert len(s) <= len(leaf.shape), (path, leaf.shape, s)


def test_input_specs_shapes():
    cfg = get_config("qwen3-1.7b", "full")
    sp = specs_lib.input_specs(cfg, INPUT_SHAPES["train_4k"])
    assert sp["tokens"].shape == (256, 4096)
    assert sp["mask"].dtype == jnp.float32
    dec = specs_lib.input_specs(cfg, INPUT_SHAPES["decode_32k"])
    assert dec["tokens"].shape == (128, 1)       # ONE token per request
    audio = get_config("musicgen-large", "full")
    sp_a = specs_lib.input_specs(audio, INPUT_SHAPES["train_4k"])
    assert sp_a["tokens"].shape == (256, 4096, 4)     # EnCodec codebooks


def test_abstract_state_is_allocation_free():
    cfg = get_config("llama3-405b", "full")
    a = specs_lib.abstract_params(cfg)
    total = specs_lib.state_bytes(a)
    assert total > 700e9                 # 405B bf16 ≈ 810 GB
    for leaf in jax.tree.leaves(a):
        assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_batch_spec_divisibility():
    mesh = make_local_mesh()
    # PartitionSpec normalises the 1-tuple ("data",) to "data"
    assert shd.batch_spec(8, mesh)[0] in ("data", ("data",))
    s = shd.batch_spec(1, mesh)
    assert s[0] in ("data", ("data",), None)  # 1 % 1 == 0 -> still shardable


# ---------------------------------------------------------------- steps

SMOKE_SHAPE_TRAIN = ShapeConfig("smoke_train", seq_len=32, global_batch=4,
                                kind="train")
SMOKE_SHAPE_DECODE = ShapeConfig("smoke_decode", seq_len=64, global_batch=2,
                                 kind="decode")
SMOKE_SHAPE_PREFILL = ShapeConfig("smoke_prefill", seq_len=32,
                                  global_batch=2, kind="prefill")


def _concrete(tree, key=0):
    k = jax.random.PRNGKey(key)
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            out.append(jnp.zeros(leaf.shape, leaf.dtype))
        else:
            out.append(0.01 * jax.random.normal(
                jax.random.fold_in(k, i), leaf.shape).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


def test_train_step_runs_on_local_mesh():
    cfg = tiny_moe()
    mesh = make_local_mesh()
    with mesh:
        bundle = steps_lib.build_train(cfg, SMOKE_SHAPE_TRAIN, mesh,
                                       n_micro=2, tc=TrainConfig())
        args = [_concrete(a, i) for i, a in enumerate(bundle.args)]
        new_tr, new_opt, metrics = bundle.fn(*args)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_opt.step) == 1
    # MoE arch reports activation counts for FLAME aggregation
    assert metrics["counts"], "train step must surface expert counts"
    total = sum(float(v.sum()) for v in metrics["counts"].values())
    # 2 layers MoE × (B·S tokens) × k=2
    assert total == 2 * 4 * 32 * 2


def test_serve_step_runs_on_local_mesh():
    cfg = tiny_moe()
    mesh = make_local_mesh()
    with mesh:
        bundle = steps_lib.build_serve(cfg, SMOKE_SHAPE_DECODE, mesh, k=1)
        args = [_concrete(a, i) for i, a in enumerate(bundle.args)]
        args[4] = jnp.asarray(5, jnp.int32)
        logits, cache = bundle.fn(*args)
    assert logits.shape[:2] == (2, 1)
    assert not bool(jnp.isnan(logits).any())


def test_prefill_step_runs_on_local_mesh():
    cfg = tiny_moe()
    mesh = make_local_mesh()
    with mesh:
        bundle = steps_lib.build_prefill(cfg, SMOKE_SHAPE_PREFILL, mesh)
        args = [_concrete(a, i) for i, a in enumerate(bundle.args)]
        logits, cache = bundle.fn(*args)
    assert logits.shape[:2] == (2, 1)
    leaves = jax.tree.leaves(cache)
    assert leaves and all(l.shape[1] == 2 for l in leaves)


def test_knob_autoselection_scales_with_model():
    mesh = make_local_mesh()
    small = get_config("qwen3-1.7b", "full")
    big = get_config("llama3-405b", "full")
    shape = INPUT_SHAPES["train_4k"]
    k_small = steps_lib.choose_train_knobs(small, shape, mesh)
    k_big = steps_lib.choose_train_knobs(big, shape, mesh)
    assert k_big["n_micro"] >= k_small["n_micro"] or \
        k_big["act_mode"] != "batch"


# ---------------------------------------------------------------- flops

def test_table1_flame_grid_matches_paper():
    """Paper Table 1 / §3.2: FLAME β-grid = {153.6, 179.2, 230.4, 332.8} B
    FLOPs for k = {1, 2, 4, 8} (2·P_a·T convention, T = 128·batch...);
    our analytic model must land within 5% of every row."""
    cfg = get_config("olmoe-1.3b-6.9b", "full")
    paper = {1: 153.6e9, 2: 179.2e9, 4: 230.4e9, 8: 332.8e9}
    for k, want in paper.items():
        got = F.flops_paper_convention(cfg, tokens=128, k=k)
        assert abs(got - want) / want < 0.05, (k, got / 1e9, want / 1e9)


def test_table1_rank_compression_barely_moves_flops():
    """The paper's central negative finding: rank compression changes FLOPs
    by <2% across the full β1→β4 range."""
    cfg = get_config("olmoe-1.3b-6.9b", "full")
    f_hi = F.flops_paper_convention(cfg, 128, k=8, lora_rank=20)
    f_lo = F.flops_paper_convention(cfg, 128, k=8, lora_rank=6)
    assert (f_hi - f_lo) / f_hi < 0.02
    # while FLAME's expert reduction halves it
    f_flame = F.flops_paper_convention(cfg, 128, k=1, lora_rank=20)
    assert f_flame / f_hi < 0.55


def test_active_params_match_paper():
    """OLMoE: P=6.9B total / P_a=1.3B at k=8 (±10%)."""
    cfg = get_config("olmoe-1.3b-6.9b", "full")
    p = F.count_params(cfg, k=8)
    assert abs(p["total"] - 6.9e9) / 6.9e9 < 0.10
    assert abs(p["active"] - 1.3e9) / 1.3e9 < 0.10


# --------------------------------------------------------- compile cache

@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_fixed_dir_in_checkout(monkeypatch,
                                                         restore_cache_dir):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    repo = compile_cache.CACHE_DIR.parent
    assert got == str(repo / ".jax_cache")
    assert (repo / "src" / "repro" / "launch" / "compile_cache.py").is_file()
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_follows_env_and_sets_nothing(monkeypatch, tmp_path,
                                                    restore_cache_dir):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
