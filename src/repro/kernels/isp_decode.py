"""Pallas TPU kernel: single-query flash-decoding partial over a KV span.

This is the per-shard compute unit of ISP decode attention (DESIGN.md §2):
the shard owns a KV span resident in HBM; the query is tiny.  We stream KV
blocks through VMEM, maintain an online-softmax state, and emit the
(acc, l, m) partial that the cross-shard combine psums.

  grid = (B, Hkv, num_kv_blocks)
  q block (G, dh); k/v block (kc, dh); kpos block (kc,)
  scratch: acc (G, dh) f32, m (G, 1), l (G, 1)

Ring buffers are handled by the explicit ``kpos`` slot-position array —
masking is data-driven, so the same kernel serves full, sliding-window and
ring-buffer caches.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(cur_ref, q_ref, k_ref, v_ref, kpos_ref,
            acc_ref, l_ref, m_ref, acc_s, m_s, l_s, *,
            scale: float, window: Optional[int], nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)                 # (kc, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    kpos = kpos_ref[...]                                # (kc,)
    cur = cur_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = (kpos >= 0) & (kpos <= cur)
    if window is not None:
        valid &= kpos > cur - window
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(valid[None, :], p, 0.0)
    l_s[...] = l_s[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        acc_ref[0, 0] = acc_s[...]
        l_ref[0, 0] = l_s[..., 0]
        m_ref[0, 0] = m_s[..., 0]


def decode_partial(q, k, v, kpos, cur_pos, *, window: Optional[int] = None,
                   scale: Optional[float] = None, kv_block: int = 128,
                   interpret: bool = False):
    """q: (B,H,dh); k/v: (B,S,Hkv,dh); kpos: (S,); cur_pos: scalar int32.

    Returns (acc (B,H,dh) f32, l (B,H) f32, m (B,H) f32).
    """
    B, H, dh = q.shape
    _, S, Hkv, _ = k.shape
    g = H // Hkv
    scale = dh ** -0.5 if scale is None else scale
    kc = min(kv_block, S)
    pad = (-S) % kc
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, (0, pad), constant_values=-1)
    nk = (S + pad) // kc

    q3 = q.reshape(B, Hkv, g, dh)
    k4 = k.transpose(0, 2, 1, 3)                        # (B, Hkv, S, dh)
    v4 = v.transpose(0, 2, 1, 3)
    cur = jnp.asarray(cur_pos, jnp.int32).reshape(1)

    kernel = functools.partial(_kernel, scale=scale, window=window, nk=nk)
    acc, l, m = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, dh), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, kc, dh), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, kc, dh), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((kc,), lambda b, h, ki: (ki,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, g), lambda b, h, ki: (b, h, 0)),
            pl.BlockSpec((1, 1, g), lambda b, h, ki: (b, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, g, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, g), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, g), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, dh), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cur, q3, k4, v4, kpos)
    return (acc.reshape(B, H, dh), l.reshape(B, H), m.reshape(B, H))
