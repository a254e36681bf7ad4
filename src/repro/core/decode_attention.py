"""ISP decode attention: flash-decoding over sequence-sharded KV caches.

The paper's core move — ship the small thing (here: the per-step query
vector) to where the big thing lives (the KV span resident on each shard),
compute locally, and return only tiny partials:

    per shard and head:  (acc: d_v floats, l: 1 float, m: 1 float)

The KV cache bytes never cross a link.  The combine is the standard
numerically-stable flash-decoding merge, done with pmax/psum over the
sequence-sharding axes.  This also makes decode sharding independent of
head-count divisibility (any GQA layout works on any mesh).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops
from repro.kernels import ref


def _per_slot_layout(kpos, cur_pos, b_axes, s_axes):
    """Shard specs + normalized cur argument for the two kpos layouts:
    shared (S,) track with scalar cur_pos, or per-slot (B,S) tracks with
    (B,) cur_pos (continuous batching)."""
    per_slot = kpos.ndim == 2
    kpos_spec = P(b_axes, s_axes) if per_slot else P(s_axes)
    cur_spec = P(b_axes) if per_slot else P()
    cur = cur_pos.astype(jnp.int32) if per_slot \
        else cur_pos[None].astype(jnp.int32)
    return per_slot, kpos_spec, cur_spec, cur


def _combine(acc, l, m, axes):
    """Stable merge of per-shard partials via collectives over ``axes``."""
    m_glob = m
    for ax in axes:
        m_glob = jax.lax.pmax(m_glob, ax)
    w = jnp.exp(m - m_glob)
    acc = jax.lax.psum(acc * w[..., None], axes)
    l = jax.lax.psum(l * w, axes)
    l = jnp.where(l == 0, 1.0, l)
    return acc / l[..., None]


def decode_attention(q, k_cache, v_cache, kpos, cur_pos, *, window: Optional[int],
                     plan, scale: Optional[float] = None):
    """q: (B, H, dh); k/v_cache: (B, S, Hkv, dh); kpos: (S,); cur_pos scalar.

    Per-slot serving layout: kpos (B, S) with cur_pos (B,) — each batch slot
    masks and advances on its own timeline (continuous batching).

    Returns (B, H, dhv).  ``plan`` is a ShardingRecipe; with a mesh and
    non-empty seq_axes the KV span stays sharded and only partials move.
    """
    if plan is None or plan.mesh is None or not plan.seq_axes:
        acc, l, m = kops.decode_partial(q, k_cache, v_cache, kpos, cur_pos,
                                        window=window, scale=scale)
        return ref.combine_partials(acc[None], l[None], m[None], axis=0).astype(q.dtype)

    b_axes = plan.batch_axes or None
    s_axes = plan.seq_axes
    per_slot, kpos_spec, cur_spec, cur = _per_slot_layout(
        kpos, cur_pos, b_axes, s_axes)

    def local(q_l, k_l, v_l, kpos_l, cur):
        acc, l, m = kops.decode_partial(q_l, k_l, v_l, kpos_l,
                                        cur if per_slot else cur[0],
                                        window=window, scale=scale)
        return _combine(acc, l, m, s_axes).astype(q_l.dtype)

    fn = jax.shard_map(
        local, mesh=plan.mesh,
        in_specs=(P(b_axes), P(b_axes, s_axes), P(b_axes, s_axes), kpos_spec,
                  cur_spec),
        out_specs=P(b_axes),
        check_vma=False)
    return fn(q, k_cache, v_cache, kpos, cur)


def paged_decode_attention(q, kpool, vpool, pages, cur_pos, *,
                           window: Optional[int], plan,
                           scale: Optional[float] = None):
    """Decode attention over a paged KV pool (the serve engine's layout).

    q: (B, H, dh); kpool/vpool: (P(+scratch), page_size, Hkv, dh); pages:
    (B, maxp) int32 per-slot page tables; cur_pos: (B,) int32 per-slot
    positions.  Returns (B, H, dhv).

    Local execution runs the fused ragged kernel (Pallas on TPU, jnp
    reference elsewhere) — one pass over exactly the pages each slot owns.
    With a sequence-sharded mesh the pool is gathered into the strip view
    and delegated to the sharded strip path (``decode_attention``): the
    page table is replicated host state, so sharding the *pool* would
    shard pages, not positions — the strip view keeps the ISP partial
    combine exact while paged allocation still governs memory.
    """
    if plan is None or plan.mesh is None or not plan.seq_axes:
        acc, l, m = kops.paged_decode_partial(q, kpool, vpool, pages, cur_pos,
                                              window=window, scale=scale)
        return ref.combine_partials(acc[None], l[None], m[None],
                                    axis=0).astype(q.dtype)

    from repro.core import kv_pages
    ps = kpool.shape[1]
    k, v, kpos = kv_pages.pages_to_strips((kpool, vpool), pages, ps)
    cur = jnp.asarray(cur_pos, jnp.int32)
    if cur.ndim == 0:
        cur = jnp.broadcast_to(cur, (q.shape[0],))
    return decode_attention(q, k, v, kpos, cur, window=window, plan=plan,
                            scale=scale)


def chunk_prefill_attention(q, kpool, vpool, pages, qpos, *, plan,
                            scale: Optional[float] = None):
    """Chunked-prefill attention over a paged KV pool.

    q: (B, C, H, dh) chunk queries; kpool/vpool: (P(+scratch), page_size,
    Hkv, dh); pages: (B, maxp) int32 page tables; qpos: (B, C) int32 query
    positions (-1 = pad row).  The chunk's rows are already scattered into
    the pool, so gathering the slot's pages into the strip view gives
    prefix + chunk in one span; ``kops.chunk_prefill_attention`` masks it
    causally per row.  Like ``paged_decode_attention``, a sequence-sharded
    mesh would shard pages rather than positions, so the gathered view is
    also what a sharded caller gets (chunk prefill is admission-path work —
    one chunk per engine tick — not the per-token hot loop).
    """
    from repro.core import kv_pages
    ps = kpool.shape[1]
    k, v, kpos = kv_pages.pages_to_strips((kpool, vpool), pages, ps)
    return kops.chunk_prefill_attention(q, k, v, kpos, qpos, scale=scale)


def mla_decode_attention(q_nope, q_rope, ckv, krope, kpos, cur_pos, wk_b, *,
                         scale: float, plan):
    """Absorbed-MLA decode over the compressed cache.

    q_nope: (B,H,n); q_rope: (B,H,r); ckv: (B,S,R); krope: (B,S,r);
    wk_b: (R,H,n).  Returns probability-weighted ckv context (B,H,R) fp32 —
    the caller applies wv_b.  The 576-float/token compressed cache is the
    only resident state; partials are (R + 2) floats per head per shard.
    """
    q_eff = jnp.einsum("bhn,rhn->bhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))

    if plan is None or plan.mesh is None or not plan.seq_axes:
        acc, l, m = ref.mla_decode_scores_partial(
            q_eff, q_rope, ckv, krope, kpos, cur_pos, scale=scale)
        return ref.combine_partials(acc[None], l[None], m[None], axis=0)

    b_axes = plan.batch_axes or None
    s_axes = plan.seq_axes
    per_slot, kpos_spec, cur_spec, cur = _per_slot_layout(
        kpos, cur_pos, b_axes, s_axes)

    def local(q_eff_l, q_rope_l, ckv_l, krope_l, kpos_l, cur):
        acc, l, m = ref.mla_decode_scores_partial(
            q_eff_l, q_rope_l, ckv_l, krope_l, kpos_l,
            cur if per_slot else cur[0], scale=scale)
        return _combine(acc, l, m, s_axes)

    fn = jax.shard_map(
        local, mesh=plan.mesh,
        in_specs=(P(b_axes), P(b_axes), P(b_axes, s_axes), P(b_axes, s_axes),
                  kpos_spec, cur_spec),
        out_specs=P(b_axes),
        check_vma=False)
    return fn(q_eff, q_rope, ckv, krope, kpos, cur)
