"""The benchmark's own sampler arithmetic: the nucleus it checks served
tokens against, and the draws of its control and sampler fault."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_path  # noqa: F401
from harness import reference

T, P = 0.8, 0.95


def logits(seed, rows=256, vocab=512):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(rows, vocab)).astype(np.float32))


@pytest.mark.parametrize("temperature,top_p", [(0.8, 0.95), (1.0, 0.5),
                                               (0.3, 0.9)])
def test_nucleus_is_the_programs(temperature, top_p):
    from repro.serving.sampler import SamplerConfig, sampler_probs
    lg = logits(1)
    _, keep = reference.nucleus(jnp, lg, temperature, top_p)
    want = sampler_probs(lg, SamplerConfig("top_p", temperature, top_p)) > 0
    assert np.array_equal(np.asarray(keep), np.asarray(want))


def test_nucleus_by_hand():
    lg = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    _, keep = reference.nucleus(jnp, lg, 1.0, 0.9)
    assert np.asarray(keep).tolist() == [[True, True, True, False]]
    _, keep = reference.nucleus(jnp, lg, 1.0, 0.5)
    assert np.asarray(keep).tolist() == [[True, False, False, False]]


def test_draws_in_the_nucleus_never_miss():
    lg = logits(2)
    tok = np.asarray(reference.draw(jax, jnp, jax.random.PRNGKey(3), lg,
                                    T, P))
    assert not reference.nucleus_miss(jnp, lg, tok, T, P).any()


def test_draws_without_the_nucleus_miss_its_left_out_mass():
    lg = logits(4, rows=2048)
    tok = np.asarray(reference.draw(jax, jnp, jax.random.PRNGKey(5), lg,
                                    T, 1.0))
    share = reference.nucleus_miss(jnp, lg, tok, T, P).mean()
    assert 0.03 < share < 0.06


def test_miss_skips_positions_past_the_end():
    lg = logits(6, rows=4)
    served = np.array([0, 1, -1, -1])
    assert reference.nucleus_miss(jnp, lg, served, T, P).shape == (2,)


def test_row_errors():
    r = np.array([[3.0, 4.0], [1.0, 0.0]], np.float32)
    p = np.array([[3.0, 4.5], [1.0, 0.0]], np.float32)
    np.testing.assert_allclose(reference.row_errors(jnp, r, p), [0.1, 0.0],
                               rtol=1e-6)
