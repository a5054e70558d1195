"""Compile the main path's kernels for a TPU v5e at OLMoE-1.3B/6.9B widths.

Nothing runs here: each test lowers and compiles one op for one chip of a
described (not attached) ``v5e:2x2`` topology, which raises what the chip's
compiler would raise — unaligned slices, VMEM over budget, unsupported
shapes — at the widths the serving engine and the round driver use
(d_model 2048, 16 heads x 128, 64 experts of width 1024, top-8, LoRA rank
20).  The interpreter-mode tests in test_kernels.py cannot see any of that.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test collection happens in
every worker.  Where it cannot be described, every test here skips.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ragged_dispatch
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lora_matmul import lora_matmul, lora_matmul_experts
from repro.kernels.topk_router import topk_router

D, HEADS, HEAD_DIM = 2048, 16, 128
E, TOP_K, D_EXPERT, RANK = 64, 8, 1024, 20
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described compiles land in the persistent cache but cannot be read
    # back without a chip; keep the cache out of this module
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes, kernel=None):
    """Compile ``fn`` for one v5e chip; with ``kernel``, check that the
    named Pallas kernel is in the program as a Mosaic custom call."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    if kernel is not None:
        assert 'custom_call_target="tpu_custom_call"' in text
        assert f"/{kernel}/pallas_call" in text


def _n_rows(tokens: int) -> int:
    return ragged_dispatch.ragged_rows(tokens * TOP_K, E)


@pytest.mark.parametrize("tokens", [64, 4096])
@pytest.mark.parametrize("k_in,h_out", [(D, D_EXPERT), (D_EXPERT, D)])
def test_ragged_expert_matmul_lora(one_chip, tokens, k_in, h_out):
    n = _n_rows(tokens)
    bm = ragged_dispatch.BLOCK_M
    fn = partial(ragged_dispatch.ragged_expert_matmul, scale=0.8,
                 interpret=False)
    _compile(fn, one_chip, ((n, k_in), BF16), ((n // bm,), I32),
             ((E, k_in, h_out), BF16), ((E, k_in, RANK), BF16),
             ((E, RANK, h_out), BF16), kernel="ragged_expert_matmul")


@pytest.mark.parametrize("tokens", [64, 4096])
def test_ragged_gather_and_combine(one_chip, tokens):
    n = _n_rows(tokens)
    _compile(ragged_dispatch.ragged_gather, one_chip,
             ((tokens, D), BF16), ((n,), I32), ((n,), I32))
    _compile(ragged_dispatch.ragged_combine, one_chip,
             ((n, D), BF16), ((tokens, TOP_K), I32), ((tokens, TOP_K), F32))


@pytest.mark.parametrize("capacity", [128, 640])
@pytest.mark.parametrize("k_in,n_out", [(D, D_EXPERT), (D_EXPERT, D)])
def test_lora_matmul_experts(one_chip, capacity, k_in, n_out):
    fn = partial(lora_matmul_experts, scale=0.8, block_m=128, block_n=256,
                 block_k=256, interpret=False)
    _compile(fn, one_chip, ((E, capacity, k_in), BF16),
             ((E, k_in, n_out), BF16), ((E, k_in, RANK), BF16),
             ((E, RANK, n_out), BF16), kernel="lora_matmul_experts")


@pytest.mark.parametrize("m", [8, 4096])
def test_lora_matmul_attention_projection(one_chip, m):
    fn = partial(lora_matmul, scale=0.8, block_m=min(m, 256), block_n=256,
                 block_k=256, interpret=False)
    _compile(fn, one_chip, ((m, D), BF16), ((D, D), BF16),
             ((D, RANK), BF16), ((RANK, D), BF16), kernel="lora_matmul")


@pytest.mark.parametrize("tokens", [512, 4096])
def test_topk_router(one_chip, tokens):
    fn = partial(topk_router, k=TOP_K, block_t=1024, interpret=False)
    _compile(fn, one_chip, ((tokens, E), F32), kernel="topk_router")


@pytest.mark.parametrize("seq", [128, 2048])
def test_flash_attention(one_chip, seq):
    fn = partial(flash_attention, causal=True, block_q=128, block_k=128,
                 interpret=False)
    shape = ((1, HEADS, seq, HEAD_DIM), BF16)
    _compile(fn, one_chip, shape, shape, shape, kernel="flash_attention")
