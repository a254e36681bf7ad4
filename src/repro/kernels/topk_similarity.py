"""Pallas TPU kernel: cosine-similarity top-k over a sharded corpus.

The recommender benchmark's hot spot (paper §IV-B2): the similarity corpus
lives on the shard ("drive"); a query block streams corpus tiles through
VMEM, maintaining a running top-k in scratch.  Only (k scores, k ids) per
query leave the kernel — the 58k-movie matrix never does.

  grid = (num_q_blocks, num_corpus_tiles)    corpus innermost (arbitrary)
  scratch: top_s (qb, k) f32, top_i (qb, k) i32

Inputs are expected L2-normalized (ops.py normalizes) so the tile compute
is a pure MXU matmul; merging is k iterations of max-extract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, c_ref, s_out, i_out, top_s, top_i, *, k: int, nt: int,
            n_corpus: int):
    ti = pl.program_id(1)
    qb = q_ref.shape[0]
    ct = c_ref.shape[0]

    @pl.when(ti == 0)
    def _init():
        top_s[...] = jnp.full_like(top_s, NEG_INF)
        top_i[...] = jnp.full_like(top_i, -1)

    q = q_ref[...].astype(jnp.float32)                  # (qb, D)
    c = c_ref[...].astype(jnp.float32)                  # (ct, D)
    sims = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    ids = ti * ct + jax.lax.broadcasted_iota(jnp.int32, (qb, ct), 1)
    sims = jnp.where(ids < n_corpus, sims, NEG_INF)

    # merge tile into running top-k: k rounds of max-extract over the union
    merged_s = jnp.concatenate([top_s[...], sims], axis=1)       # (qb, k+ct)
    merged_i = jnp.concatenate([top_i[...], ids], axis=1)

    def extract(j, carry):
        ms, mi, outs, outi = carry
        best = ms.max(axis=1, keepdims=True)                     # (qb,1)
        am = jnp.argmax(ms, axis=1)                              # (qb,)
        bi = jnp.take_along_axis(mi, am[:, None], axis=1)        # (qb,1)
        outs = jax.lax.dynamic_update_slice(outs, best, (0, j))
        outi = jax.lax.dynamic_update_slice(outi, bi, (0, j))
        # knock out the winner
        hit = jax.lax.broadcasted_iota(jnp.int32, ms.shape, 1) == am[:, None]
        ms = jnp.where(hit, NEG_INF, ms)
        return ms, mi, outs, outi

    outs0 = jnp.zeros((qb, k), jnp.float32)
    outi0 = jnp.zeros((qb, k), jnp.int32)
    _, _, outs, outi = jax.lax.fori_loop(
        0, k, extract, (merged_s, merged_i, outs0, outi0))
    top_s[...] = outs
    top_i[...] = outi

    @pl.when(ti == nt - 1)
    def _finish():
        s_out[...] = top_s[...]
        i_out[...] = top_i[...]


def topk_similarity(queries, corpus, k: int, *, q_block: int = 128,
                    corpus_tile: int = 512, interpret: bool = False):
    """queries: (Q, D); corpus: (N, D).  Returns (scores (Q,k), ids (Q,k))."""
    qn = queries.astype(jnp.float32)
    qn = qn / jnp.maximum(jnp.linalg.norm(qn, axis=-1, keepdims=True), 1e-9)
    cn = corpus.astype(jnp.float32)
    cn = cn / jnp.maximum(jnp.linalg.norm(cn, axis=-1, keepdims=True), 1e-9)

    Q, D = qn.shape
    N, _ = cn.shape
    qb = min(q_block, Q)
    ct = min(corpus_tile, N)
    pad_q = (-Q) % qb
    pad_n = (-N) % ct
    if pad_q:
        qn = jnp.pad(qn, ((0, pad_q), (0, 0)))
    if pad_n:
        cn = jnp.pad(cn, ((0, pad_n), (0, 0)))
    nq = qn.shape[0] // qb
    nt = cn.shape[0] // ct

    kernel = functools.partial(_kernel, k=k, nt=nt, n_corpus=N)
    scores, ids = pl.pallas_call(
        kernel,
        grid=(nq, nt),
        in_specs=[
            pl.BlockSpec((qb, D), lambda qi, ti: (qi, 0)),
            pl.BlockSpec((ct, D), lambda qi, ti: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((qb, k), lambda qi, ti: (qi, 0)),
            pl.BlockSpec((qb, k), lambda qi, ti: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((qn.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, k), jnp.float32),
            pltpu.VMEM((qb, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qn, cn)
    return scores[:Q], ids[:Q]
