"""The serving runner on a toy model of the Qwen1.5-MoE family (shared
experts, no qk-norm): served tokens agree with the float32 reference."""
from serve_cells import SHARED, tiny_cell


def test_shared_expert_family_matches_reference():
    _, out = tiny_cell(17, sizes=SHARED, family="qwen2-moe-a2.7b")
    assert out["correct"], out["checks"]
