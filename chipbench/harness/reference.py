"""Plain float32 forward pass of the benchmark's MoE decoders.

Written from the model's equations in plain ``jax.numpy``, one layer at a
time, with every matrix product at ``"highest"`` precision.  It imports
nothing of the program and takes nothing the program made: the weights are
the benchmark's own (harness/weights.py).  No kernels, no cache, no
batching, no dispatch buffer: attention is the full causal softmax over
the whole sequence and the MoE layer evaluates every expert for every
token, weighting those outside the token's top-k by zero.

Where the program departs from the published model the reference follows
the program and the configuration file says so (``run.departures``):
RMSNorm of q and k over each head, router weights renormalised over the
top-k, no q/k/v biases, no shared-expert gate.

``quant="fp8"`` is the control: every matrix product takes its two
operands rounded to float8 e4m3 (one scale per tensor, and per expert),
accumulating in float32 — the reference computed one precision below the
configuration's bfloat16.

The sampler's arithmetic is the benchmark's own too (``nucleus``,
``draw``): temperature softmax, then the smallest prefix of tokens by
probability whose mass reaches ``top_p``.
"""
from __future__ import annotations

from functools import partial

import numpy as np

ROW_CHUNK = 256       # token rows per dense-expert block


def _fp8(jnp, x):
    """x rounded to float8 e4m3 with a per-tensor scale over its last two
    axes (per expert for a stacked expert tensor)."""
    f8 = jnp.float8_e4m3fn
    amax = jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True)
    sc = jnp.maximum(amax, 1e-30) / 448.0
    return (x / sc).astype(f8).astype(jnp.float32) * sc


def _ops(jax, jnp, quant):
    f32 = jnp.float32

    def q(x):
        x = x.astype(f32)
        return _fp8(jnp, x) if quant == "fp8" else x

    def mm(x, w):
        return jnp.matmul(q(x), q(w), precision="highest")

    def ein(spec, x, w):
        return jnp.einsum(spec, q(x), q(w), precision="highest")

    def lin(x, w, lp, scale):
        y = mm(x, w)
        if lp is not None:
            y = y + mm(mm(x, lp["a"]), lp["b"]) * scale
        return y

    def rms(x, g, eps):
        x = x.astype(f32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * g.astype(f32)

    return q, mm, ein, lin, rms


def _rope(jnp, x, theta):
    """Rotary embedding over the last axis split in halves; x (T, H, hd)."""
    T, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(jax, jnp, s, quant, k, h, p, lp, resc):
    """One decoder block on h (T, D) in float32."""
    q_, mm, ein, lin, rms = _ops(jax, jnp, quant)
    f32 = jnp.float32
    T = h.shape[0]
    H, KV, hd, ls = s.n_heads, s.n_kv_heads, s.head_dim, s.lora_scale
    a, la = p["attn"], lp["attn"]
    x = rms(h, p["mixer_norm"], s.rms_eps)
    qh = lin(x, a["wq"], la["wq"], ls).reshape(T, H, hd)
    kh = lin(x, a["wk"], la["wk"], ls).reshape(T, KV, hd)
    vh = lin(x, a["wv"], la["wv"], ls).reshape(T, KV, hd)
    if s.qk_norm:
        qh = rms(qh, a["q_norm"], s.rms_eps)
        kh = rms(kh, a["k_norm"], s.rms_eps)
    qh, kh = _rope(jnp, qh, s.rope_theta), _rope(jnp, kh, s.rope_theta)
    kh = jnp.repeat(kh, H // KV, axis=1)
    vh = jnp.repeat(vh, H // KV, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q_(qh), q_(kh),
                    precision="highest") * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", q_(pr), q_(vh), precision="highest")
    h = h + lin(o.reshape(T, H * hd), a["wo"], la["wo"], ls)

    m, lm = p["moe"], lp["moe"]
    x = rms(h, p["ffn_norm"], s.rms_eps)
    rl = mm(x, m["router"])
    probs = jax.nn.softmax(rl, axis=-1)                           # (T, E)
    top_p, top_i = jax.lax.top_k(probs, k)
    if s.norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], top_i].set(top_p)

    ex, le = m["experts"], lm["experts"]

    def elin(xc, name):           # xc (C, d_in) -> (C, E, d_out)
        y = ein("cd,edf->cef", xc, ex[name])
        ab = le[name]
        xa = ein("cd,edr->cer", xc, ab["a"])
        return y + jnp.einsum("cer,erf->cef", q_(xa), q_(ab["b"]),
                              precision="highest") * ls

    def chunk(args):
        xc, gc = args
        g = elin(xc, "w1")
        u = elin(xc, "w3")
        hm = jax.nn.silu(g) * u                                   # (C, E, F)
        y = jnp.einsum("cef,efd->ced", q_(hm), q_(ex["w2"]),
                       precision="highest")
        ya = jnp.einsum("cef,efr->cer", q_(hm), q_(le["w2"]["a"]),
                        precision="highest")
        y = y + jnp.einsum("cer,erd->ced", q_(ya), q_(le["w2"]["b"]),
                           precision="highest") * ls
        return jnp.einsum("ce,ced->cd", gc, y)

    C = min(ROW_CHUNK, T)
    routed = jax.lax.map(chunk, (x.reshape(T // C, C, -1),
                                 gate.reshape(T // C, C, -1)))
    out = routed.reshape(T, -1) * resc.astype(f32)
    if "shared" in m:
        sh, lsh = m["shared"], lm["shared"]
        g = lin(x, sh["w1"], lsh["w1"], ls)
        u = lin(x, sh["w3"], lsh["w3"], ls)
        out = out + lin(jax.nn.silu(g) * u, sh["w2"], lsh["w2"], ls)
    return h + out


class Reference:
    """The float32 forward of one configuration over padded sequences of
    ``length`` tokens, returning the logits at a window of positions."""

    def __init__(self, jax, jnp, s, params, lora, length: int,
                 window: int):
        self.jax, self.jnp, self.s = jax, jnp, s
        self.params, self.lora = params, lora
        chunk = min(ROW_CHUNK, length)
        self.length = -(-length // chunk) * chunk
        self.window = window
        self._layer = {}

    def _layer_fn(self, k, quant):
        key = (k, quant)
        if key not in self._layer:
            self._layer[key] = self.jax.jit(partial(
                _layer, self.jax, self.jnp, self.s, quant, k))
        return self._layer[key]

    def logits(self, tokens: np.ndarray, first: int, k: int, rescaler,
               quant=None):
        """Logits (window, V) in float32 at positions ``first ..
        first + window - 1`` of ``tokens`` (padded to ``length``)."""
        jax, jnp, s = self.jax, self.jnp, self.s
        toks = np.zeros((self.length,), np.int32)
        toks[:len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            h = self._embed(self.params["embed"]["tokens"],
                            jnp.asarray(toks))
            blocks = self.params["blocks"]["pos0"]
            lblocks = self.lora["blocks"]["pos0"]
            f = self._layer_fn(k, quant)
            for i in range(s.layers):
                sl = partial(jax.tree.map, lambda x: x[i])
                h = f(h, sl(blocks), sl(lblocks), rescaler["pos0"][i])
            return self._head(h, self.params["final_norm"],
                              self.params["lm_head"], first, quant)

    def _embed(self, table, toks):
        if not hasattr(self, "_embed_fn"):
            self._embed_fn = self.jax.jit(
                lambda t, i: t[i].astype(self.jnp.float32))
        return self._embed_fn(table, toks)

    def _head(self, h, g, w, first, quant):
        key = ("head", quant)
        if key not in self._layer:
            jax, jnp, s, n = self.jax, self.jnp, self.s, self.window

            def head(h, g, w, first):
                _, mm, _, _, rms = _ops(jax, jnp, quant)
                hs = jax.lax.dynamic_slice_in_dim(h, first, n, axis=0)
                return mm(rms(hs, g, s.rms_eps), w)
            self._layer[key] = jax.jit(head)
        return self._layer[key](h, g, w, first)


def greedy_gaps(jax, jnp, logits, served: np.ndarray):
    """Per position: how far the served token's logit lies below the best
    logit; ``served`` (window,) with -1 past the request's end."""
    n = len(served)
    best = jnp.max(logits, axis=-1)
    tok = jnp.asarray(np.maximum(served, 0))
    got = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    gap = np.asarray(best - got)[:n]
    return gap[served >= 0]


def row_errors(jnp, ref_rows, rows) -> np.ndarray:
    """Per position: the L2 distance of a logit row from the reference's,
    relative to the reference row's norm.  Both (n, V)."""
    r = jnp.asarray(ref_rows, jnp.float32)
    d = jnp.asarray(rows, jnp.float32) - r
    return np.asarray(jnp.linalg.norm(d, axis=-1)
                      / jnp.linalg.norm(r, axis=-1))


def nucleus(jnp, logits, temperature: float, top_p: float):
    """(probabilities at ``temperature``, mask of the top-``top_p``
    nucleus): a token is in it iff the mass of the tokens placed before it
    (by probability, ties by index) is under ``top_p``."""
    import jax
    probs = jax.nn.softmax(jnp.asarray(logits, jnp.float32) / temperature,
                           axis=-1)
    order = jnp.argsort(-probs, axis=-1, stable=True)
    ps = jnp.take_along_axis(probs, order, axis=-1)
    keep = (jnp.cumsum(ps, axis=-1) - ps) < top_p
    inv = jnp.argsort(order, axis=-1)
    return probs, jnp.take_along_axis(keep, inv, axis=-1)


def nucleus_miss(jnp, logits, served: np.ndarray, temperature: float,
                 top_p: float) -> np.ndarray:
    """Per served token (``served`` (window,), -1 past the request's end):
    whether it lies outside the reference's nucleus at its position."""
    _, keep = nucleus(jnp, logits, temperature, top_p)
    tok = jnp.asarray(np.maximum(served, 0))
    inside = np.asarray(jnp.take_along_axis(keep, tok[:, None], axis=-1)[:, 0])
    return ~inside[:len(served)][served >= 0]


def draw(jax, jnp, key, logits, temperature: float, top_p: float):
    """One token per row from the top-``top_p`` nucleus at
    ``temperature``."""
    probs, keep = nucleus(jnp, logits, temperature, top_p)
    return jax.random.categorical(key, jnp.log(jnp.where(keep, probs, 0.0)),
                                  axis=-1)
