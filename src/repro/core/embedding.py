"""ISP-sharded vocabulary embedding and cross-entropy.

The vocabulary table is the "drive": it stays sharded over the model axis.
Lookups ship token *indexes* (4 bytes each) to every shard; each shard
gathers the rows it owns (`isp_gather`, zero elsewhere) and only activation
rows are reduced back — the table itself never moves.  The RecSSD-style
baseline (all-gather the table; XLA's default for a plain ``take``) is kept
as ``gather_baseline`` for the paper's host-vs-ISP comparison.

The loss head is the same idea in reverse: per-shard logits + psum'd
logsumexp scalars — the full (tokens × vocab) logits tensor never exists
unsharded, and only per-token scalars cross the link (the paper's "1.2 MB
of output text" effect).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.kernels import ops as kops
from repro.models.layers import KeyGen, dense_init

VOCAB_PAD = 32   # table rows padded to a multiple of this (e.g. hymba's 32001)


def padded_vocab(vocab_size: int) -> int:
    return -(-vocab_size // VOCAB_PAD) * VOCAB_PAD


def embed_params(cfg: ModelConfig, kg: KeyGen, dtype) -> jax.Array:
    return dense_init(kg(), (padded_vocab(cfg.vocab_size), cfg.d_model), dtype,
                      scale=1.0)


def _sharded(plan) -> bool:
    return plan is not None and plan.mesh is not None and plan.model_axis is not None


def gather_baseline(table, tokens):
    """Host-style path: XLA will all-gather the table shard(s) to serve the
    gather — the 'ship data to compute' baseline from the paper."""
    return jnp.take(table, tokens, axis=0)


def embed_lookup(table, tokens, plan, seq_sharded=None):
    """tokens: (B, S) int32 -> (B, S, D).  ISP path when sharded.

    Preferred plan (sequence-parallel): the *indexes* are all-gathered over
    the vocab shards (4 bytes/token — the paper's protocol verbatim), each
    shard gathers the rows it owns, and a reduce-scatter returns each
    sequence shard its rows.  Wire bytes: tiny + rows·(g-1)/g — half of the
    psum fallback, and the output arrives S-sharded for the SP residual
    stream.  Falls back to psum when S doesn't divide the model axis.
    """
    if not _sharded(plan):
        return gather_baseline(table, tokens)
    tp = plan.model_axis
    fs = plan.fsdp_axis
    b_axes = plan.batch_axes or None
    v_pad = table.shape[0]
    tp_size = plan.plan.axis_size(tp)
    if v_pad % tp_size:
        return gather_baseline(table, tokens)
    table_spec = P(tp, fs) if fs else P(tp)
    if seq_sharded is None:
        seq_sharded = tokens.shape[1] % tp_size == 0 and tp_size > 1
    seq_sharded = seq_sharded and tokens.shape[1] % tp_size == 0

    def gather_local(table_l, tokens_l):
        if fs:
            # FSDP storage gather: the fs axis shards the token batch too, so
            # row *fragments* cannot be all-gathered after lookup (they would
            # mix different tokens).  Restore full row width first.
            table_l = jax.lax.all_gather(table_l, fs, axis=1, tiled=True)
        v_loc = table_l.shape[0]
        off = jax.lax.axis_index(tp) * v_loc
        return kops.isp_gather(table_l, tokens_l, shard_offset=off)

    if seq_sharded:
        def local(table_l, tokens_l):
            toks = jax.lax.all_gather(tokens_l, tp, axis=1, tiled=True)
            rows = gather_local(table_l, toks)
            return jax.lax.psum_scatter(rows, tp, scatter_dimension=1,
                                        tiled=True)

        fn = jax.shard_map(local, mesh=plan.mesh,
                           in_specs=(table_spec, P(b_axes, tp)),
                           out_specs=P(b_axes, tp), check_vma=False)
        return fn(table, tokens)

    def local(table_l, tokens_l):
        rows = gather_local(table_l, tokens_l)
        return jax.lax.psum(rows, tp)          # activation rows, not the table

    fn = jax.shard_map(local, mesh=plan.mesh,
                       in_specs=(table_spec, P(b_axes)),
                       out_specs=P(b_axes), check_vma=False)
    return fn(table, tokens)


# ---------------------------------------------------------------------------
# Sharded cross-entropy
# ---------------------------------------------------------------------------


def _dense_chunked_xent(x, w_head, labels, vocab_size: int, chunk: int):
    """Unsharded-vocab xent without materializing (tokens × vocab) logits:
    token-chunked scan with per-chunk remat (same trick as the sharded path;
    essential for pure-DP layouts where the vocab axis is unsharded)."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    lf = labels.reshape(t)
    c = min(chunk, t)
    pad = (-t) % c
    xf = jnp.pad(xf, ((0, pad), (0, 0)))
    lf = jnp.pad(lf, ((0, pad),), constant_values=0)
    n = xf.shape[0] // c

    @jax.checkpoint
    def body(_, xs):
        x_c, l_c = xs
        logits = jnp.einsum("td,vd->tv", x_c, w_head,
                            preferred_element_type=jnp.float32)
        mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(mask[None], logits, -1e30)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, l_c[:, None], axis=1)[:, 0]
        return None, lse - ll

    _, losses = jax.lax.scan(body, None, (xf.reshape(n, c, d), lf.reshape(n, c)))
    return losses.reshape(-1)[:t].reshape(b, s)


def _xent_local(w_l, x_l, labels_l, *, tp, fs, vocab_size, chunk):
    """Per-shard chunked xent.  w_l: (V_loc, D[/fs]); x_l: (B_loc,S,D);
    labels_l: (B_loc,S).  Returns per-token loss (B_loc, S) fp32."""
    if fs:
        w_l = jax.lax.all_gather(w_l, fs, axis=1, tiled=True)      # FSDP gather
    v_loc = w_l.shape[0]
    off = jax.lax.axis_index(tp) * v_loc
    b, s, d = x_l.shape
    t = b * s
    xf = x_l.reshape(t, d)
    lf = labels_l.reshape(t)
    c = min(chunk, t)
    pad = (-t) % c
    xf = jnp.pad(xf, ((0, pad), (0, 0)))
    lf = jnp.pad(lf, ((0, pad),), constant_values=0)
    n = xf.shape[0] // c

    @jax.checkpoint
    def body(_, xs):
        x_c, l_c = xs                                              # (c,D), (c,)
        logits = jnp.einsum("td,vd->tv", x_c, w_l,
                            preferred_element_type=jnp.float32)
        lmax = jax.lax.pmax(jax.lax.stop_gradient(logits).max(-1), tp)
        se = jax.lax.psum(jnp.exp(logits - lmax[:, None]).sum(-1), tp)
        loc = l_c - off
        ok = (loc >= 0) & (loc < v_loc)
        ll = jnp.take_along_axis(logits, jnp.clip(loc, 0, v_loc - 1)[:, None],
                                 axis=1)[:, 0]
        lab_logit = jax.lax.psum(jnp.where(ok, ll, 0.0), tp)
        return None, jnp.log(se) + lmax - lab_logit

    _, losses = jax.lax.scan(body, None,
                             (xf.reshape(n, c, d), lf.reshape(n, c)))
    return losses.reshape(-1)[:t].reshape(b, s)


def sharded_xent(x, w_head, labels, plan, cfg: ModelConfig,
                 chunk: int = 4096, seq_sharded=None):
    """Cross-entropy over a vocab-sharded head.  x: (B,S,D); w_head: (V,D);
    labels: (B,S).  Returns per-token loss (B,S) fp32 (caller masks/means).
    """
    if not _sharded(plan) or w_head.shape[0] % plan.plan.axis_size(plan.model_axis):
        return _dense_chunked_xent(x, w_head, labels, cfg.vocab_size, chunk)

    tp = plan.model_axis
    fs = plan.fsdp_axis
    b_axes = plan.batch_axes or None
    w_spec = P(tp, fs) if fs else P(tp)
    # the per-token loss is independent across tokens, so the sequence can
    # stay sharded over the model axis (SP) — each shard handles its slice
    # against its vocab shard, with only scalar psums crossing the link
    tp_size = plan.plan.axis_size(tp)
    if seq_sharded is None:
        seq_sharded = x.shape[1] % tp_size == 0 and tp_size > 1
    seq_sharded = seq_sharded and x.shape[1] % tp_size == 0

    import functools
    local = functools.partial(_xent_local, tp=tp, fs=fs,
                              vocab_size=cfg.vocab_size, chunk=chunk)
    if seq_sharded:
        # every vocab shard must see every token (the psum'd logsumexp spans
        # vocab shards), so gather the hidden slice in, slice the loss out.
        def local_seq(w_l, x_l, labels_l):
            s_loc = x_l.shape[1]
            x_all = jax.lax.all_gather(x_l, tp, axis=1, tiled=True)
            lab_all = jax.lax.all_gather(labels_l, tp, axis=1, tiled=True)
            losses = local(w_l, x_all, lab_all)
            i = jax.lax.axis_index(tp)
            return jax.lax.dynamic_slice_in_dim(losses, i * s_loc, s_loc, axis=1)

        fn = jax.shard_map(local_seq, mesh=plan.mesh,
                           in_specs=(w_spec, P(b_axes, tp), P(b_axes, tp)),
                           out_specs=P(b_axes, tp), check_vma=False)
        return fn(w_head, x, labels)
    fn = jax.shard_map(local, mesh=plan.mesh,
                       in_specs=(w_spec, P(b_axes), P(b_axes)),
                       out_specs=P(b_axes), check_vma=False)
    return fn(w_head, x, labels)


def sharded_logits_last(x_last, w_head, plan, cfg: ModelConfig):
    """Full logits for the last position (decode sampling).  x_last: (B, D).

    Returns (B, V) fp32 — pad columns masked to -inf.
    """
    if not _sharded(plan) or w_head.shape[0] % plan.plan.axis_size(plan.model_axis):
        logits = jnp.einsum("bd,vd->bv", x_last, w_head,
                            preferred_element_type=jnp.float32)
        return logits[:, : cfg.vocab_size]

    tp = plan.model_axis
    fs = plan.fsdp_axis
    b_axes = plan.batch_axes or None
    w_spec = P(tp, fs) if fs else P(tp)

    def local(w_l, x_l):
        if fs:
            w_l = jax.lax.all_gather(w_l, fs, axis=1, tiled=True)
        logits = jnp.einsum("bd,vd->bv", x_l, w_l,
                            preferred_element_type=jnp.float32)
        return logits

    fn = jax.shard_map(local, mesh=plan.mesh,
                       in_specs=(w_spec, P(b_axes)),
                       out_specs=P(b_axes, tp), check_vma=False)
    logits = fn(w_head, x_last)
    v_pad = w_head.shape[0]
    mask = jnp.arange(v_pad) < cfg.vocab_size
    return jnp.where(mask[None], logits, -jnp.inf)


def greedy_sample(x_last, w_head, plan, cfg: ModelConfig):
    """ISP greedy sampling: each vocab shard proposes its local argmax; only
    (value, id) pairs cross the link — the winning *token id* is the entire
    inter-shard payload, the paper's 1.2 MB-of-text effect at its sharpest.
    """
    if not _sharded(plan) or w_head.shape[0] % plan.plan.axis_size(plan.model_axis):
        return jnp.argmax(sharded_logits_last(x_last, w_head, plan, cfg), axis=-1)

    tp = plan.model_axis
    fs = plan.fsdp_axis
    b_axes = plan.batch_axes or None
    w_spec = P(tp, fs) if fs else P(tp)

    def local(w_l, x_l):
        if fs:
            w_l = jax.lax.all_gather(w_l, fs, axis=1, tiled=True)
        v_loc = w_l.shape[0]
        off = jax.lax.axis_index(tp) * v_loc
        logits = jnp.einsum("bd,vd->bv", x_l, w_l,
                            preferred_element_type=jnp.float32)
        ok = (off + jnp.arange(v_loc)) < cfg.vocab_size
        logits = jnp.where(ok[None], logits, -jnp.inf)
        val = logits.max(-1)
        idx = logits.argmax(-1) + off
        best = jax.lax.pmax(val, tp)
        # ship only the winning id: psum of the (masked) local winner
        win = jnp.where(val == best, idx, 0)
        return jax.lax.pmax(win, tp).astype(jnp.int32)

    fn = jax.shard_map(local, mesh=plan.mesh,
                       in_specs=(w_spec, P(b_axes)),
                       out_specs=P(b_axes), check_vma=False)
    return fn(w_head, x_last)
