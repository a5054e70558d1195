"""A token altered where the engine produces it makes ``correct`` false:
the serving runner runs with the timed path broken underneath."""
from repro.serving import engine as engine_mod
from serve_cells import tiny_cell


def test_altered_token_fails(monkeypatch):
    pick = engine_mod.ServingEngine._pick

    def altered(self, logits_row, a):
        tok, nll = pick(self, logits_row, a)
        return (tok + 1) % logits_row.shape[-1], nll
    monkeypatch.setattr(engine_mod.ServingEngine, "_pick", altered)
    _, out = tiny_cell(5)
    assert not out["correct"]
    assert out["checks"]["mean_logit_gap"]["value"] > 1e-4
    assert out["checks"]["nucleus_miss_share"]["value"] > 0.1
