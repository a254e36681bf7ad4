"""Pallas TPU kernel: fused ragged decode attention over a paged KV pool.

The continuous-batching serve engine keeps KV in fixed-size pages
(``core.kv_pages``): each batch slot owns a page table mapping logical
pages to physical pool pages, and slots sit at different positions.  This
kernel walks the page table — the physical page id is read from a
scalar-prefetch argument inside the BlockSpec index map, so only the pages
a slot actually owns are streamed through VMEM — and computes each slot's
masked attention in one pass:

  grid = (B, Hkv, max_logical_pages)
  scalar prefetch: pages (B, maxp) int32, cur (B,) int32
  q block (G, dh); k/v block (page_size, dh) — one physical page
  scratch: acc (G, dh) f32, m (G, 1), l (G, 1)
  out: acc (B, Hkv, G, dh), l/m (B, Hkv, G, 1) — reshaped to (B, H)

Unallocated logical pages (table entry -1) are clamped to physical page 0
for the DMA and masked out by position validity, so the grid shape stays
static while the *useful* work tracks live tokens.  The jnp reference
(``paged_decode_partial_ref``) materializes the gathered view and reuses
``ref.decode_partial_masked`` — the oracle the per-slot strip path also
uses, which is what makes paged decode token-identical to strip decode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kv_pages
from repro.kernels import ref

NEG_INF = -1e30


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


def _kernel(pages_ref, cur_ref, q_ref, k_ref, v_ref,
            acc_ref, l_ref, m_ref, acc_s, m_s, l_s, *,
            scale: float, window: Optional[int], ps: int, nk: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)                 # (ps, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    page = pages_ref[b, ki]
    cur = cur_ref[b]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ki * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    valid = (page >= 0) & (pos <= cur)
    if window is not None:
        valid &= pos > cur - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    l_s[...] = l_s[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        acc_ref[0, 0] = acc_s[...]
        l_ref[0, 0] = l_s[...]
        m_ref[0, 0] = m_s[...]


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
    g = H // Hkv
    scale = dh ** -0.5 if scale is None else scale

    q3 = q.reshape(B, Hkv, g, dh)
    k4 = kpool.transpose(2, 0, 1, 3)                    # (Hkv, P, ps, dh)
    v4 = vpool.transpose(2, 0, 1, 3)
    pages = pages.astype(jnp.int32)
    cur = jnp.asarray(cur_pos, jnp.int32)
    if cur.ndim == 0:
        cur = jnp.broadcast_to(cur, (B,))

    def page_idx(b, h, ki, pages_ref, cur_ref):
        # unallocated -> page 0 (masked in-kernel); keeps the DMA in range
        return (h, jnp.maximum(pages_ref[b, ki], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda b, h, ki, pages_ref, cur_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, dh), page_idx),
            pl.BlockSpec((1, 1, ps, dh), page_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda b, h, ki, pages_ref, cur_ref: (b, h, 0, 0)),
            # l/m carry a trailing unit axis: the TPU compiler tiles the
            # last two block dims, and a (g,) tail of (B, Hkv, g) is refused
            pl.BlockSpec((1, 1, g, 1),
                         lambda b, h, ki, pages_ref, cur_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1),
                         lambda b, h, ki, pages_ref, cur_ref: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, dh), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               ps=ps, nk=maxp)
    acc, l, m = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, g, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(pages, cur, q3, k4, v4)
    return (acc.reshape(B, H, dh), l.reshape(B, H), m.reshape(B, H))
