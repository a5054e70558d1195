"""Random weights from the seed, made on the device in one jitted call, in
the type they are served in and in the program's tree layout.

The benchmark makes them, not the program, so the reference can use them
without taking anything the program made.  Scales keep every part of a
block in play: unit-scale embeddings, fan-in projections, norm scales and
adapter factors that are not the identity or zero, and FLAME's rescaler
at its initial value ``top_k / k`` (paper Eq. 5) per tier.
"""
from __future__ import annotations

import zlib
from typing import Sequence

from .model_cfg import Sizes

LORA_B_STD = 0.03     # adapter output about a tenth of the base output


def _leaf(jax, jnp, key, path: str, shape, std, dtype, mean=0.0):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32) * std + mean
    return x.astype(dtype)


def make(jax, jnp, s: Sizes, tiers: Sequence[int], key):
    """(params, lora, {k: rescaler}) for config ``s``; jit-compiled once."""
    bf16 = jnp.bfloat16
    L, D, H, KV, hd = s.layers, s.d_model, s.n_heads, s.n_kv_heads, s.head_dim
    E, F, Fs, V, r = s.n_experts, s.d_expert, s.d_shared, s.vocab, s.lora_rank

    def build(key):
        def w(path, shape, std, dtype=bf16, mean=0.0):
            return _leaf(jax, jnp, key, path, shape, std, dtype, mean)

        def lora_pair(path, lead, d_in, d_out):
            return {"a": w(path + ".a", lead + (d_in, r), d_in ** -0.5),
                    "b": w(path + ".b", lead + (r, d_out), LORA_B_STD)}

        attn = {"wq": w("wq", (L, D, H * hd), D ** -0.5),
                "wk": w("wk", (L, D, KV * hd), D ** -0.5),
                "wv": w("wv", (L, D, KV * hd), D ** -0.5),
                "wo": w("wo", (L, H * hd, D), (H * hd) ** -0.5)}
        if s.qk_norm:
            attn["q_norm"] = w("q_norm", (L, hd), 0.1, mean=1.0)
            attn["k_norm"] = w("k_norm", (L, hd), 0.1, mean=1.0)
        moe = {"router": w("router", (L, D, E), D ** -0.5),
               "experts": {"w1": w("e.w1", (L, E, D, F), D ** -0.5),
                           "w3": w("e.w3", (L, E, D, F), D ** -0.5),
                           "w2": w("e.w2", (L, E, F, D), F ** -0.5)}}
        lmoe = {"experts": {
            "w1": lora_pair("le.w1", (L, E), D, F),
            "w3": lora_pair("le.w3", (L, E), D, F),
            "w2": lora_pair("le.w2", (L, E), F, D)}}
        if Fs:
            moe["shared"] = {"w1": w("s.w1", (L, D, Fs), D ** -0.5),
                             "w3": w("s.w3", (L, D, Fs), D ** -0.5),
                             "w2": w("s.w2", (L, Fs, D), Fs ** -0.5)}
            lmoe["shared"] = {"w1": lora_pair("ls.w1", (L,), D, Fs),
                              "w3": lora_pair("ls.w3", (L,), D, Fs),
                              "w2": lora_pair("ls.w2", (L,), Fs, D)}
        block = {"mixer_norm": w("mixer_norm", (L, D), 0.1, mean=1.0),
                 "attn": attn,
                 "ffn_norm": w("ffn_norm", (L, D), 0.1, mean=1.0),
                 "moe": moe}
        params = {"embed": {"tokens": w("embed", (V, D), 1.0)},
                  "blocks": {"pos0": block},
                  "final_norm": w("final_norm", (D,), 0.1, mean=1.0),
                  "lm_head": w("lm_head", (D, V), D ** -0.5)}
        lattn = {n: lora_pair("la." + n, (L,), *shape) for n, shape in (
            ("wq", (D, H * hd)), ("wk", (D, KV * hd)), ("wv", (D, KV * hd)),
            ("wo", (H * hd, D)))}
        lora = {"blocks": {"pos0": {"attn": lattn, "moe": lmoe}}}
        resc = {int(k): {"pos0": w(f"rescaler.{k}", (L,), 0.05 * s.top_k / k,
                                   jnp.float32, mean=s.top_k / k)}
                for k in tiers}
        return params, lora, resc

    return jax.jit(build)(key)


def seed_key(jax, seed: int):
    """A PRNG key from any whole ``--seed``, also one past 32 bits."""
    import numpy as np
    word = int(np.random.SeedSequence(seed).generate_state(1)[0])
    return jax.random.PRNGKey(word & 0x7FFFFFFF), word & 0x7FFFFFFF


def check_layout(jax, cfg, params, lora) -> None:
    """The trees match what the program builds for ``cfg`` (shapes and
    structure), so the engine takes these weights as its own."""
    from repro.core import lora as lora_lib
    from repro.models import model as model_lib
    want_p = jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    want_l = jax.eval_shape(lambda p: lora_lib.init_lora(
        jax.random.PRNGKey(0), cfg, p), want_p)
    for name, got, want in (("params", params, want_p),
                            ("lora", lora, want_l)):
        gs = jax.tree.map(lambda x: (x.shape, str(x.dtype)), got)
        ws = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
        if gs != ws:
            raise ValueError(f"{name} layout differs from the program's:\n"
                             f"{gs}\n!=\n{ws}")

