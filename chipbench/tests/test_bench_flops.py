"""FLOP and byte arithmetic against hand counts."""
import pytest

import bench_path  # noqa: F401
from harness import flops, model_cfg

ROOT = bench_path.BENCH


def olmoe():
    c = model_cfg.load(ROOT / "configs" / "olmoe-1.3b-6.9b-4L.json")
    return model_cfg.sizes(c)


def tiny():
    return model_cfg.Sizes(
        layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
        n_experts=4, top_k=2, d_expert=6, d_shared=0, vocab=10,
        rope_theta=1e4, rms_eps=1e-6, qk_norm=True, norm_topk=True,
        lora_rank=1, lora_alpha=1.0, registry_id="x")


def test_params_match_the_chip_count():
    # the 4-layer OLMoE build printed 1,884,310,528 parameters on the chip
    assert model_cfg.count_params(olmoe()) == 1_884_310_528


def test_token_flops_by_hand():
    s = tiny()
    # per layer: q,o 2*8*8*2=256; k,v 2*8*4*2=128; lora q,o 2*1*16*2=64,
    # k,v 2*1*12*2=48; attention 2*2*2*4*ctx=32*ctx; router 2*8*4=64;
    # experts k*3*(2*8*6 + 2*1*14)=k*372
    per_layer = 256 + 128 + 64 + 48 + 64
    assert flops.token_flops(s, 1, 0, False) == 2 * (per_layer + 372)
    assert flops.token_flops(s, 2, 3, True) == (
        2 * (per_layer + 744 + 32 * 3) + 2 * 8 * 10)


@pytest.mark.parametrize("k,L,n,start", [(1, 5, 1, 0), (2, 7, 4, 0),
                                         (2, 9, 3, 4)])
def test_request_sums_equal_token_loop(k, L, n, start):
    s = tiny()
    want = sum(flops.token_flops(s, k, p + 1, False) for p in range(start, L))
    want += 2 * s.d_model * s.vocab
    want += sum(flops.token_flops(s, k, L + j, True) for j in range(1, n))
    assert flops.request_flops(s, k, L, n, start) == want


def test_expert_matmul_by_hand():
    s = tiny()
    fl, by = flops.expert_matmul(s, rows=10, touched=3, d_in=8, d_out=6)
    assert fl == 2 * 10 * (48 + 1 * 14)
    assert by == 2 * (3 * (48 + 14) + 10 * 14)
    mats = flops.moe_layer_matmuls(s, 10, 3)
    assert mats[0] == mats[1] == (fl, by)
    assert mats[2] == flops.expert_matmul(s, 10, 3, 6, 8)
