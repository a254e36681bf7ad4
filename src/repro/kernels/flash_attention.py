"""Pallas TPU flash attention (forward).

Blocked causal/windowed attention with online softmax, tiled for VMEM:
  grid = (B, Hkv, G, num_q_blocks, num_kv_blocks)
  q block  (qc, dh)   VMEM        k/v block (kc, dh)  VMEM
  scratch: acc (qc, dh) f32, m (qc, 1) f32, l (qc, 1) f32 — persisted
  across the kv grid dimension ("arbitrary" semantics, innermost).

GQA is handled in the index maps (kv head = grid h, q head = (h, g)) so the
KV tiles are fetched once per kv head, not per q head.  MXU alignment: pick
qc/kc multiples of 128 at scale; tests sweep small interpret-mode shapes.

The backward pass reuses the reference flash backward (custom_vjp) — the
forward kernel is the serving hot spot; training uses the jnp chunked path
whose math is identical.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int],
            q_offset: int, kv_valid: int, kc_total: int):
    qi = pl.program_id(3)
    ki = pl.program_id(4)
    qc = q_ref.shape[-2]
    kc = k_ref.shape[-2]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32)             # (qc, dh)
    k = k_ref[0, 0].astype(jnp.float32)                # (kc, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = q_offset + qi * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
    kpos = ki * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
    mask = kpos < kv_valid
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (qc, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == kc_total - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, scale: Optional[float] = None,
                    q_block: int = 128, kv_block: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, H, dh); k/v: (B, Skv, Hkv, dh).  Forward only."""
    B, Sq, H, dh = q.shape
    _, Skv, Hkv, dhv = v.shape
    assert dh == k.shape[-1] and dhv == dh, "pallas kernel: uniform head dims"
    g = H // Hkv
    scale = dh ** -0.5 if scale is None else scale
    qc = min(q_block, Sq)
    kc = min(kv_block, Skv)

    def pad_to(x, mult, axis):
        pad = (-x.shape[axis]) % mult
        if pad:
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, pad)
            x = jnp.pad(x, widths)
        return x

    qp = pad_to(q, qc, 1)
    kp = pad_to(k, kc, 1)
    vp = pad_to(v, kc, 1)
    nq = qp.shape[1] // qc
    nk = kp.shape[1] // kc

    # (B, S, H, dh) -> (B, Hkv, G, S, dh) / (B, Hkv, S, dh) for blocked access
    q5 = qp.reshape(B, nq * qc, Hkv, g, dh).transpose(0, 2, 3, 1, 4)
    k4 = kp.transpose(0, 2, 1, 3)
    v4 = vp.transpose(0, 2, 1, 3)

    grid = (B, Hkv, g, nq, nk)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, kv_valid=Skv, kc_total=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, qc, dh), lambda b, h, gg, qi, ki: (b, h, gg, qi, 0)),
            pl.BlockSpec((1, 1, kc, dh), lambda b, h, gg, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, kc, dh), lambda b, h, gg, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, qc, dh),
                               lambda b, h, gg, qi, ki: (b, h, gg, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, nq * qc, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qc, dh), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
            pltpu.VMEM((qc, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q5, k4, v4)

    out = out.transpose(0, 3, 1, 2, 4).reshape(B, nq * qc, H, dh)
    return out[:, :Sq]
