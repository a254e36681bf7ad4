"""Pallas TPU kernel: fused ragged decode attention over a paged KV pool.

The continuous-batching serve engine keeps KV in fixed-size pages
(``core.kv_pages``): each batch slot owns a page table mapping logical
pages to physical pool pages, and slots sit at different positions.  One
grid step serves one slot; inside it a loop walks only that slot's live
pages, ``pages_per_block`` of them at a time, and computes the slot's
masked attention for every query head in one online-softmax pass:

  grid = (B,)
  scalar prefetch: pages (B, maxp') int32, cur (B,), first/end (B,) blocks
  q block (H, dh); the pools stay in HBM in their stored layout
  (P + 1, ps, Hkv, dh), read as (P + 1, ps * Hkv, dh) rows
  scratch: K and V double buffers (2, pages_per_block * ps * Hkv, dh),
  filled page by page by DMA while the previous block computes
  out: acc (B, H, dh), l/m (B, H, 1)

The loop's trip count is ``end - first`` (``block_range``): the blocks
that hold positions ``0 .. cur`` (from ``cur - window + 1`` with a
window), none for a slot that owns no page.  Within a block only pages
that are allocated and hold a position the slot attends are fetched; the
V rows of the rest are zeroed and every row of theirs is masked, so a dead
page costs neither a DMA nor a term in the math.  A block's rows hold all
Hkv heads of its tokens, so each query head's scores are taken against
every row and the rows of other heads masked: no strided load and no
per-call transpose of the pool.  Scores and ``p . V`` accumulate in f32.

The jnp reference (``paged_decode_partial_ref``) materializes the gathered
view and reuses ``ref.decode_partial_masked`` — the oracle the per-slot
strip path also uses, which is what makes paged decode token-identical to
strip decode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kv_pages
from repro.kernels import ref

NEG_INF = -1e30


def pages_per_block(page_size: int, num_kv_heads: int, head_dim: int,
                    max_pages: int) -> int:
    """Pages one step of a slot's walk fetches: about 256 tokens, fewer
    where a block's K (or V) in f32 would pass 1 MiB, at most 16 pages (a
    block's page mask is one int32 of bits) and at most a slot's pages."""
    tokens = min(256, (1 << 18) // (num_kv_heads * head_dim))
    return max(1, min(16, max_pages, tokens // page_size))


def block_range(pages, cur, page_size: int, ppb: int,
                window: Optional[int] = None, xp=np):
    """Blocks of ``ppb`` logical pages each slot's walk covers: ``[first,
    end)``, those holding positions ``max(0, cur - window + 1) .. cur``;
    ``end == first == 0`` for a slot that owns no page.  ``xp`` is ``np``
    (the engine's page-walk counter) or ``jnp`` (the kernel's trip count).

    pages: (B, maxp) int32 (-1 = unallocated); cur: (B,) int32.
    """
    tpb = ppb * page_size
    last = xp.minimum(cur, pages.shape[1] * page_size - 1)
    end = xp.where((pages >= 0).any(axis=1), last // tpb + 1, 0)
    if window is None:
        return xp.zeros_like(end), end
    first = xp.maximum(cur - window + 1, 0) // tpb
    return xp.minimum(first, end), end


def paged_decode_partial_ref(q, kpool, vpool, pages, cur_pos, *,
                             window: Optional[int] = None,
                             scale: Optional[float] = None):
    """Pure-jnp oracle: gather the paged pool into the per-slot strip view
    and run the strip-path reference partial on it.

    q: (B, H, dh); kpool/vpool: (P(+scratch), ps, Hkv, dh);
    pages: (B, maxp) int32; cur_pos: (B,) or scalar int32.
    Returns (acc (B,H,dhv) f32, l (B,H) f32, m (B,H) f32).
    """
    ps = kpool.shape[1]
    k, v, kpos = kv_pages.pages_to_strips((kpool, vpool), pages, ps)
    cur = jnp.asarray(cur_pos, jnp.int32)
    if cur.ndim == 0:
        cur = jnp.broadcast_to(cur, (q.shape[0],))
    return ref.decode_partial_masked(q, k, v, kpos, cur, window=window,
                                     scale=scale)


def _kernel(pages_ref, cur_ref, first_ref, end_ref, q_ref, k_hbm, v_hbm,
            acc_ref, l_ref, m_ref, kbuf, vbuf, sem, *,
            scale: float, window: Optional[int], ps: int, ppb: int,
            hkv: int):
    b = pl.program_id(0)
    cur, first, end = cur_ref[b], first_ref[b], end_ref[b]
    rpp = ps * hkv                                   # rows a page holds
    rows = ppb * rpp
    H, dh = q_ref.shape[1], q_ref.shape[2]
    g = H // hkv

    def page(blk, i):
        """Whether logical page ``i`` of block ``blk`` is live (allocated,
        holding a position the slot attends), and where it lies."""
        lp = blk * ppb + i
        pid = pages_ref[b, lp]
        live = (pid >= 0) & (lp * ps <= cur)
        if window is not None:
            live &= (lp + 1) * ps > cur - window + 1
        return live, jnp.maximum(pid, 0)

    def rows_of(i):
        return pl.ds(pl.multiple_of(i * rpp, rpp), rpp)

    def copies(src, i, slot):
        return (pltpu.make_async_copy(k_hbm.at[src], kbuf.at[slot, rows_of(i)],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[src], vbuf.at[slot, rows_of(i)],
                                      sem.at[1, slot]))

    def start(blk, slot):
        def one(i, carry):
            live, src = page(blk, i)

            @pl.when(live)
            def _():
                for c in copies(src, i, slot):
                    c.start()
            return carry
        jax.lax.fori_loop(0, ppb, one, 0)

    @pl.when(first < end)
    def _():
        start(first, 0)

    # row r of a block: token r // hkv, kv head r % hkv, page r // rpp
    r = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    tok, page_of = r // hkv, r // rpp
    same_head = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0) // g \
        == r % hkv

    def body(blk, carry):
        m_prev, l_prev, acc_prev = carry
        slot = (blk - first) % 2

        @pl.when(blk + 1 < end)
        def _():
            start(blk + 1, 1 - slot)

        def wait(i, live_bits):
            live, src = page(blk, i)

            @pl.when(live)
            def _():
                for c in copies(src, i, slot):
                    c.wait()

            # a page not fetched holds stale VMEM: zero its V rows so that
            # p = 0 cannot meet a NaN there
            @pl.when(jnp.logical_not(live))
            def _():
                vbuf[slot, rows_of(i), :] = jnp.zeros((rpp, dh), vbuf.dtype)
            return live_bits | (live.astype(jnp.int32) << i)
        live_bits = jax.lax.fori_loop(0, ppb, wait, jnp.int32(0))

        pos = blk * (ppb * ps) + tok
        valid = ((live_bits >> page_of) & 1) == 1
        valid &= pos <= cur
        if window is not None:
            valid &= pos > cur - window
        valid = valid & same_head                              # (H, rows)

        s = jax.lax.dot_general(q_ref[0], kbuf[slot],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        # HIGHEST: the MXU takes p in full f32, not rounded to bf16
        acc_new = acc_prev * alpha + jax.lax.dot_general(
            p, vbuf[slot].astype(jnp.float32), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, dh), jnp.float32))
    m, l, acc = jax.lax.fori_loop(first, end, body, init)
    acc_ref[0] = acc
    l_ref[0] = l
    m_ref[0] = m


def paged_decode_partial(q, kpool, vpool, pages, cur_pos, *,
                         window: Optional[int] = None,
                         scale: Optional[float] = None,
                         interpret: bool = False):
    """q: (B,H,dh); kpool/vpool: (P(+scratch), ps, Hkv, dh); pages: (B,maxp)
    int32 physical page ids (-1 = unallocated); cur_pos: (B,) int32 per-slot
    current positions (scalar broadcasts).

    Returns (acc (B,H,dh) f32, l (B,H) f32, m (B,H) f32) — the same
    combinable partials as ``isp_decode.decode_partial``.
    """
    B, H, dh = q.shape
    P, ps, Hkv, _ = kpool.shape
    maxp = pages.shape[1]
    scale = dh ** -0.5 if scale is None else scale
    ppb = pages_per_block(ps, Hkv, dh, maxp)

    pages = pages.astype(jnp.int32)
    cur = jnp.asarray(cur_pos, jnp.int32)
    if cur.ndim == 0:
        cur = jnp.broadcast_to(cur, (B,))
    first, end = block_range(pages, cur, ps, ppb, window, xp=jnp)
    if maxp % ppb:      # whole blocks: the last one's tail is unallocated
        pages = jnp.pad(pages, ((0, 0), (0, -maxp % ppb)),
                        constant_values=-1)
    # (ps, Hkv, dh) -> (ps * Hkv, dh) rows: the same bytes in HBM
    k_rows = kpool.reshape(P, ps * Hkv, dh)
    v_rows = vpool.reshape(P, ps * Hkv, dh)

    rows = ppb * ps * Hkv
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, dh), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, H, dh), lambda b, *_: (b, 0, 0)),
            # l/m carry a trailing unit axis: the TPU compiler tiles the
            # last two block dims, and a (H,) tail of (B, H) is refused
            pl.BlockSpec((1, H, 1), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, *_: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, rows, dh), kpool.dtype),
            pltpu.VMEM((2, rows, dh), vpool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               ps=ps, ppb=ppb, hkv=Hkv)
    acc, l, m = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(pages, cur, first, end, q, k_rows, v_rows)
    return acc, l.reshape(B, H), m.reshape(B, H)
