"""The serving runner end to end on the CPU at a toy size: the engine's
logits and served tokens agree with the benchmark's float32 reference,
the window compiles nothing, and the float8 control, judged by the same
limits, is not correct."""
import pytest

from serve_cells import tiny_cell


@pytest.fixture(scope="module")
def sound():
    return tiny_cell(2 ** 31 + 11, control=True)


def test_served_tokens_match_reference(sound):
    ctx, out = sound
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 12
    assert ctx.data["numbers"]["program"]["widest_logit_gap"] <= 1e-3
    assert out["checks"]["logit_rows_missing"]["value"] == 0
    assert set(out["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_warm_up_leaves_nothing_to_compile_in_the_window(sound):
    ctx, _ = sound
    assert ctx.data["prefills"]
    assert ctx.window_compiles == 0


def test_control_is_not_correct(sound):
    ctx, _ = sound
    nums, judged = ctx.data["numbers"], ctx.data["judged"]
    assert not judged["fp8"]["correct"], judged["fp8"]
    assert nums["fp8"]["mean_logit_gap"] > 10 * max(
        nums["program"]["mean_logit_gap"], 1e-4)
    assert nums["fp8"]["logit_rel_err"] > 5 * nums["program"]["logit_rel_err"]
