"""Plain float32 reference of a Llama-architecture decoder (Yi is one).

It imports nothing of the program under test.  The weights are drawn again
from the run's seed by the law the configuration is served with (bf16
values of truncated-normal fan-in draws, keys split in the order below),
so the reference and the served model hold the same numbers without the
reference reading any array the program made.

The block, as published for Llama/Yi: RMSNorm (scale 1 + w, w = 0 at
init) -> rotary GQA attention (rotate-half convention, query head h reads
KV head h // (H / Hkv), softmax scale dh^-0.5) -> residual -> RMSNorm ->
SwiGLU MLP -> residual; a final RMSNorm and an untied output head.

``gaps`` runs the reference over each prompt with the tokens that were
served for it and returns, per served token, how far that token's logit
lies below the reference's best logit at that position.  ``precision``
"fp8" is the control: every projection's weights and activations pass
through float8 e4m3 with one absmax scale per tensor, and the gap read is
that of the token the fp8 path puts first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 32          # the output head's rows are padded to this multiple
BUCKET = 512            # sequences are padded to a multiple of this length
Q_BLOCK = 512           # attention is computed this many query rows at a time
FP8_MAX = 448.0         # largest finite float8 e4m3fn value


def padded_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def _draw(key, shape, std, dtype):
    """One weight as served: truncated normal on [-2, 2], times std, held in
    the served dtype, computed on in float32."""
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std
    return w.astype(dtype).astype(jnp.float32)


def _split(key):
    key, sub = jax.random.split(key)
    return key, sub


class Dims:
    def __init__(self, spec: Dict):
        self.d = int(spec["hidden_size"])
        self.h = int(spec["num_attention_heads"])
        self.hkv = int(spec["num_key_value_heads"])
        self.dh = int(spec.get("head_dim") or self.d // self.h)
        self.f = int(spec["intermediate_size"])
        self.v = int(spec["vocab_size"])
        self.layers = int(spec["num_hidden_layers"])
        self.theta = float(spec["rope_theta"])
        self.eps = float(spec["rms_norm_eps"])
        self.dtype = str(spec["torch_dtype"])

    def key(self):
        return (self.d, self.h, self.hkv, self.dh, self.f, self.v,
                self.layers, self.theta, self.eps, self.dtype)


def weight_keys(seed: int, layers: int):
    """(embed key, per-layer keys, head key) in the order they are drawn."""
    key = jax.random.PRNGKey(seed)
    key, k_embed = _split(key)
    key, k_blocks = _split(key)
    layer_keys = jax.random.split(k_blocks, layers)
    key, k_head = _split(key)
    return k_embed, layer_keys, k_head


@functools.partial(jax.jit, static_argnums=0)
def _layer_weights(dk, key):
    d, h, hkv, dh, f, dt = dk[0], dk[1], dk[2], dk[3], dk[4], dk[9]
    shapes = [("wq", (d, h, dh), d), ("wk", (d, hkv, dh), d),
              ("wv", (d, hkv, dh), d), ("wo", (h, dh, d), h * dh),
              ("wg", (d, f), d), ("wu", (d, f), d), ("wd", (f, d), f)]
    w = {}
    for name, shape, fan_in in shapes:
        key, k = _split(key)
        w[name] = _draw(k, shape, fan_in ** -0.5, dt)
    return w


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _table(rows, d, dtype, key, std):
    return _draw(key, (rows, d), std, dtype)


def _fp8(x):
    """Round a tensor through float8 e4m3 under one absmax scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(eq, x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(dk, w, x, fp8: bool):
    """One decoder layer over one padded sequence x: (T, D) float32."""
    d, h, hkv, dh, theta, eps = dk[0], dk[1], dk[2], dk[3], dk[7], dk[8]
    t = x.shape[0]
    g = h // hkv
    pos = jnp.arange(t)
    a = _rms(x, eps)
    q = _rope(_mm("td,dhk->thk", a, w["wq"], fp8), pos, theta)
    k = _rope(_mm("td,dhk->thk", a, w["wk"], fp8), pos, theta)
    v = _mm("td,dhk->thk", a, w["wv"], fp8)
    q = q.reshape(t, hkv, g, dh) * dh ** -0.5
    outs = []
    for q0 in range(0, t, Q_BLOCK):
        qb = q[q0: q0 + Q_BLOCK]
        s = jnp.einsum("qhgd,khd->hgqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST)
        causal = jnp.arange(t)[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hgqk,khd->qhgd", p, v,
                               precision=jax.lax.Precision.HIGHEST))
    o = jnp.concatenate(outs, 0).reshape(t, h, dh)
    x = x + _mm("thk,hkd->td", o, w["wo"], fp8)
    m = _rms(x, eps)
    gate = _mm("td,df->tf", m, w["wg"], fp8)
    up = _mm("td,df->tf", m, w["wu"], fp8)
    return x + _mm("tf,fd->td", jax.nn.silu(gate) * up, w["wd"], fp8)


@functools.partial(jax.jit, static_argnums=0)
def _served_gaps(dk, head, x, served):
    """Per position of one padded sequence: how far the served token's
    logit lies below the best float32 logit."""
    logits = _mm("td,vd->tv", _rms(x, dk[8]), head, False)[:, :dk[5]]
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return logits.max(-1) - got


@functools.partial(jax.jit, static_argnums=0)
def _control_gaps(dk, head, x, x8):
    """Per position: how far the token the fp8 path puts first lies below
    the best float32 logit."""
    logits = _mm("td,vd->tv", _rms(x, dk[8]), head, False)[:, :dk[5]]
    pick = _mm("td,vd->tv", _rms(x8, dk[8]), head, True)[:, :dk[5]].argmax(-1)
    got = jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
    return logits.max(-1) - got


def logits(spec: Dict, seed: int, tokens: Sequence[int]) -> np.ndarray:
    """The reference's logits at every position of one sequence."""
    dims = Dims(spec)
    dk = dims.key()
    k_embed, layer_keys, k_head = weight_keys(seed, dims.layers)
    vpad = padded_vocab(dims.v)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(_table(vpad, dims.d, dims.dtype, k_embed, 1.0),
                     jnp.asarray(tokens, jnp.int32), axis=0)
        for layer in range(dims.layers):
            x = _layer(dk, _layer_weights(dk, layer_keys[layer]), x, False)
        head = _table(vpad, dims.d, dims.dtype, k_head, vpad ** -0.5)
        out = _mm("td,vd->tv", _rms(x, dims.eps), head, False)[:, :dims.v]
    return np.asarray(out)


def gaps(spec: Dict, seed: int, seqs: Sequence[Tuple[Sequence[int],
                                                      Sequence[int]]],
         precision: str = "f32") -> List[np.ndarray]:
    """Widest-gap readings for served requests.

    seqs: (prompt tokens, served tokens) per request.  Returns, per request,
    an array over its served tokens.  With ``precision="f32"`` each entry is
    how far the served token's logit lies below the reference's best.  With
    ``"fp8"`` (the control) each entry is how far the token that the fp8
    path puts first lies below the float32 reference's best.
    """
    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision must be f32 or fp8, got {precision!r}")
    dims = Dims(spec)
    dk = dims.key()
    k_embed, layer_keys, k_head = weight_keys(seed, dims.layers)
    vpad = padded_vocab(dims.v)
    inputs, targets, spans = [], [], []
    for prompt, served in seqs:
        toks = list(prompt) + list(served[:-1])
        t = -(-len(toks) // BUCKET) * BUCKET
        ids = np.zeros(t, np.int32)
        ids[: len(toks)] = toks
        tgt = np.zeros(t, np.int32)
        tgt[len(prompt) - 1: len(toks)] = served
        inputs.append(ids)
        targets.append(tgt)
        spans.append(slice(len(prompt) - 1, len(toks)))
    with jax.default_matmul_precision("highest"):
        table = _table(vpad, dims.d, dims.dtype, k_embed, 1.0)
        xs = {"f32": [jnp.take(table, jnp.asarray(i), axis=0) for i in inputs]}
        if precision == "fp8":
            xs["fp8"] = list(xs["f32"])
        del table
        for layer in range(dims.layers):
            w = _layer_weights(dk, layer_keys[layer])
            for path in xs:
                xs[path] = [_layer(dk, w, x, path == "fp8") for x in xs[path]]
            del w
        head = _table(vpad, dims.d, dims.dtype, k_head, vpad ** -0.5)
        out = []
        for i, sl in enumerate(spans):
            if precision == "f32":
                g = _served_gaps(dk, head, xs["f32"][i], jnp.asarray(targets[i]))
            else:
                g = _control_gaps(dk, head, xs["f32"][i], xs["fp8"][i])
            out.append(np.asarray(g)[sl])
    return out
