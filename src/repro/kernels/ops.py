"""jit-friendly dispatch wrappers around the Pallas kernels.

Every op has three implementations:
  "pallas"  — the TPU kernel (``pl.pallas_call`` + BlockSpec).  On CPU it runs
              in interpret mode (tests); on TPU it compiles natively.
  "jnp"     — the scalable pure-jnp path (chunked scans) from ``ref.py``;
              identical math, used for CPU dry-runs and as the XLA fallback.
  "auto"    — "pallas" on TPU backends, "jnp" elsewhere.

The FLOP/byte structure of the jnp path matches the kernel tiling, so
roofline terms derived from the dry-run HLO are representative of the TPU
execution (see DESIGN.md §7).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

_FORCE_IMPL: Optional[str] = None


def set_default_impl(impl: Optional[str]) -> None:
    """Force an implementation globally (tests / benchmarks)."""
    global _FORCE_IMPL
    _FORCE_IMPL = impl


def _resolve(impl: str) -> str:
    if _FORCE_IMPL is not None:
        return _FORCE_IMPL
    if impl != "auto":
        return impl
    platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "jnp"


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, scale: Optional[float] = None,
                    q_chunk: int = 512, kv_chunk: int = 512, impl: str = "auto"):
    """Chunked causal attention.  q: (B,Sq,H,dh); k/v: (B,Skv,Hkv,dh[v])."""
    which = _resolve(impl)
    if which == "pallas":
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, scale=scale,
                                  interpret=jax.default_backend() != "tpu")
    return ref.chunked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, scale=scale,
                                 q_chunk=q_chunk, kv_chunk=kv_chunk)


def decode_partial(q, k, v, kpos, cur_pos, *, window: Optional[int] = None,
                   scale: Optional[float] = None, impl: str = "auto"):
    """Per-shard flash-decoding partial.  q: (B,H,dh); k/v: (B,S,Hkv,dh).

    kpos: (S,) global positions of cache slots (-1 = empty); cur_pos: scalar.
    Per-slot layouts — kpos (B,S) with cur_pos (B,) from the continuous-
    batching engine — run the jnp path (the Pallas kernel keeps the uniform
    single-position layout).
    Returns (acc fp32 (B,H,dhv), l (B,H), m (B,H)).
    """
    which = _resolve(impl)
    if kpos.ndim == 2 or jnp.ndim(cur_pos) == 1:
        which = "jnp"
    if which == "pallas":
        from repro.kernels import isp_decode
        return isp_decode.decode_partial(q, k, v, kpos, cur_pos, window=window,
                                         scale=scale,
                                         interpret=jax.default_backend() != "tpu")
    return ref.decode_partial_masked(q, k, v, kpos, cur_pos, window=window, scale=scale)


def paged_decode_partial(q, kpool, vpool, pages, cur_pos, *,
                         window: Optional[int] = None,
                         scale: Optional[float] = None, impl: str = "auto"):
    """Ragged decode partial over a paged KV pool (continuous batching).

    q: (B,H,dh); kpool/vpool: (P(+scratch), page_size, Hkv, dh); pages:
    (B,maxp) int32 per-slot page tables (-1 = unallocated); cur_pos: (B,)
    per-slot positions.  Unlike ``decode_partial``, the per-slot layout IS
    the Pallas layout here — the kernel walks each slot's live pages from
    the scalar-prefetched page table, so the serve engine's ragged batches
    get the fused path.
    Returns (acc fp32 (B,H,dh), l (B,H), m (B,H)).
    """
    from repro.kernels import paged_decode
    which = _resolve(impl)
    if which == "pallas":
        return paged_decode.paged_decode_partial(
            q, kpool, vpool, pages, cur_pos, window=window, scale=scale,
            interpret=jax.default_backend() != "tpu")
    return paged_decode.paged_decode_partial_ref(
        q, kpool, vpool, pages, cur_pos, window=window, scale=scale)


def chunk_prefill_attention(q, k, v, kpos, qpos, *,
                            scale: Optional[float] = None, impl: str = "auto"):
    """Chunked-prefill attention: chunk queries at explicit positions over a
    cached span (the serve engine's incremental prefill continuation).

    q: (B,C,H,dh); k/v: (B,S,Hkv,dh[v]); kpos: (B,S) (-1 = empty row);
    qpos: (B,C) (-1 = pad row).  One chunk runs per engine tick (admission-
    path work, not the per-token hot loop), so every backend takes the jnp
    oracle — the dispatch hook exists so a fused kernel can slot in without
    touching callers.
    """
    del impl  # no fused kernel yet; the oracle is the only implementation
    return ref.chunk_attention_masked(q, k, v, kpos, qpos, scale=scale)


def isp_gather(table, indices, *, shard_offset=0, shard_rows=None, weights=None,
               impl: str = "auto"):
    """Masked local gather of table rows for global indices (ISP primitive)."""
    which = _resolve(impl)
    if which == "pallas":
        from repro.kernels import isp_gather as ig
        return ig.isp_gather(table, indices, shard_offset=shard_offset,
                             weights=weights,
                             interpret=jax.default_backend() != "tpu")
    return ref.isp_gather(table, indices, shard_offset=shard_offset,
                          shard_rows=shard_rows, weights=weights)


def isp_gather_pool(table, indices, segment_ids, num_segments, *,
                    shard_offset=0, weights=None, impl: str = "auto"):
    which = _resolve(impl)
    if which == "pallas":
        from repro.kernels import isp_gather as ig
        return ig.isp_gather_pool(table, indices, segment_ids, num_segments,
                                  shard_offset=shard_offset, weights=weights,
                                  interpret=jax.default_backend() != "tpu")
    return ref.isp_gather_pool(table, indices, segment_ids, num_segments,
                               shard_offset=shard_offset, weights=weights)


def topk_similarity(queries, corpus, k: int, *, impl: str = "auto"):
    which = _resolve(impl)
    if which == "pallas":
        from repro.kernels import topk_similarity as tk
        return tk.topk_similarity(queries, corpus, k,
                                  interpret=jax.default_backend() != "tpu")
    return ref.topk_similarity(queries, corpus, k)
