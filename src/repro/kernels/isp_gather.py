"""Pallas TPU kernel: ISP embedding gather (+ fused pooling).

The per-shard unit of the paper's "send indexes, not data": the local table
shard stays in place; for a block of global indices we fetch only the rows
this shard owns (zeros elsewhere — the cross-shard psum completes the
lookup).

Tiling: grid = (num_index_blocks, num_d_blocks).  The table is tiled along
D so each kernel instance holds a (V_local, dblk) panel in VMEM (e.g.
16384 × 128 × 2B = 4 MB for gemma3's 262k vocab over 16 shards) and rows
are fetched with dynamic VMEM addressing — the TPU-native analogue of the
CSD's flash-to-ISP path.

``isp_gather_pool`` fuses RecSSD-style segment-sum aggregation: pooled
embedding-bag outputs leave the kernel instead of raw rows, cutting the
result bytes by the pooling factor (the paper's data-transfer reduction).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(off_ref, idx_ref, table_ref, w_ref, out_ref, *, ib: int,
                   weighted: bool):
    v_loc = table_ref.shape[0]
    off = off_ref[0]

    def body(i, _):
        idx = idx_ref[i] - off
        ok = (idx >= 0) & (idx < v_loc)
        safe = jnp.clip(idx, 0, v_loc - 1)
        row = table_ref[safe, :].astype(jnp.float32)
        scale = jnp.where(ok, 1.0, 0.0)
        if weighted:
            scale = scale * w_ref[i]
        out_ref[i, :] = (row * scale).astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, ib, body, 0)


def isp_gather(table, indices, *, shard_offset=0, weights=None,
               idx_block: int = 256, d_block: int = 512,
               interpret: bool = False):
    """table: (V_local, D); indices: (...,) int32 global ids.

    Returns (..., D) rows (zero outside [shard_offset, shard_offset+V_local)).
    """
    shape = indices.shape
    idx = indices.reshape(-1)
    n = idx.shape[0]
    v_loc, d = table.shape
    ib = min(idx_block, max(n, 1))
    db = min(d_block, d)
    pad_n = (-n) % ib
    if pad_n:
        idx = jnp.pad(idx, (0, pad_n), constant_values=-1)
    w = weights.reshape(-1).astype(jnp.float32) if weights is not None else \
        jnp.ones((1,), jnp.float32)
    if weights is not None and pad_n:
        w = jnp.pad(w, (0, pad_n))
    pad_d = (-d) % db
    if pad_d:
        table = jnp.pad(table, ((0, 0), (0, pad_d)))
    ni = idx.shape[0] // ib
    nd = table.shape[1] // db
    off = jnp.asarray(shard_offset, jnp.int32).reshape(1)

    kernel = functools.partial(_gather_kernel, ib=ib,
                               weighted=weights is not None)
    out = pl.pallas_call(
        kernel,
        grid=(ni, nd),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((ib,), lambda i, j: (i,)),
            pl.BlockSpec((v_loc, db), lambda i, j: (0, j)),
            pl.BlockSpec((ib,), lambda i, j: (i,)) if weights is not None
            else pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((ib, db), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], table.shape[1]), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(off, idx, table, w)
    return out[:n, :d].reshape(shape + (d,))


def _gather_pool_kernel(off_ref, idx_ref, seg_ref, table_ref, w_ref, out_ref, *,
                        ib: int, weighted: bool, n_seg: int):
    v_loc = table_ref.shape[0]
    off = off_ref[0]
    i_blk = pl.program_id(0)

    @pl.when(i_blk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(i, _):
        idx = idx_ref[i] - off
        seg = seg_ref[i]
        ok = (idx >= 0) & (idx < v_loc) & (seg >= 0) & (seg < n_seg)
        safe = jnp.clip(idx, 0, v_loc - 1)
        seg_safe = jnp.clip(seg, 0, n_seg - 1)
        row = table_ref[safe, :].astype(jnp.float32)
        scale = jnp.where(ok, 1.0, 0.0)
        if weighted:
            scale = scale * w_ref[i]
        out_ref[seg_safe, :] = out_ref[seg_safe, :] + row * scale
        return 0

    jax.lax.fori_loop(0, ib, body, 0)


def isp_gather_pool(table, indices, segment_ids, num_segments: int, *,
                    shard_offset=0, weights=None, idx_block: int = 256,
                    d_block: int = 512, interpret: bool = False):
    """Fused gather + segment-sum (RecSSD embedding-bag offload).

    indices/segment_ids: (N,).  Returns (num_segments, D) fp32.
    Grid iterates index blocks sequentially (accumulation), D in parallel.
    """
    idx = indices.reshape(-1)
    seg = segment_ids.reshape(-1)
    n = idx.shape[0]
    v_loc, d = table.shape
    ib = min(idx_block, max(n, 1))
    db = min(d_block, d)
    pad_n = (-n) % ib
    if pad_n:
        idx = jnp.pad(idx, (0, pad_n), constant_values=-1)
        seg = jnp.pad(seg, (0, pad_n), constant_values=-1)
    w = weights.reshape(-1).astype(jnp.float32) if weights is not None else \
        jnp.ones((1,), jnp.float32)
    if weights is not None and pad_n:
        w = jnp.pad(w, (0, pad_n))
    pad_d = (-d) % db
    if pad_d:
        table = jnp.pad(table, ((0, 0), (0, pad_d)))
    ni = idx.shape[0] // ib
    nd = table.shape[1] // db
    off = jnp.asarray(shard_offset, jnp.int32).reshape(1)

    kernel = functools.partial(_gather_pool_kernel, ib=ib,
                               weighted=weights is not None, n_seg=num_segments)
    out = pl.pallas_call(
        kernel,
        grid=(ni, nd),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((ib,), lambda i, j: (i,)),
            pl.BlockSpec((ib,), lambda i, j: (i,)),
            pl.BlockSpec((v_loc, db), lambda i, j: (0, j)),
            pl.BlockSpec((ib,), lambda i, j: (i,)) if weights is not None
            else pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((num_segments, db), lambda i, j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((num_segments, table.shape[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel")),
        interpret=interpret,
    )(off, idx, seg, table, w)
    return out[:, :d]
