"""LM assembly: embedding → scanned block groups → head.

Layers are scanned in groups (``cfg.group_size``) with stacked parameters so
HLO size is O(group) not O(depth); remat policy per config.  Entry points:

  loss_fn       (params, batch, cfg, plan) -> (loss, metrics)     [train]
  prefill_fn    (params, batch, cfg, plan) -> (next_token, caches)
  decode_fn     (params, caches, token, pos, cfg, plan) -> (token, caches)
  input_specs   (cfg, shape) -> pytree of ShapeDtypeStruct (dry-run stand-ins)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.core import embedding as emb
from repro.models import blocks as blk
from repro.models.layers import KeyGen, rms_norm, dense_init
from repro.sharding import ParallelPlan, ShardingRecipe

LOCAL = ShardingRecipe(plan=ParallelPlan(), batch_axes=(), seq_axes=())


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def group_pattern(cfg: ModelConfig) -> Tuple[str, ...]:
    return cfg.layer_pattern[: cfg.group_size]


def num_groups(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.group_size


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Random params drawn from ``key``, as one jitted program: each float32
    draw fuses into its cast to the model dtype, so no whole float32 weight
    is ever held on the device (eager init would, once per weight, at full
    model widths)."""
    dtype = _dtype(cfg)
    kg = KeyGen(key)
    params: Dict[str, Any] = {"embed": {"table": emb.embed_params(cfg, kg, dtype)}}
    blocks: Dict[str, Any] = {}
    for j, kind in enumerate(group_pattern(cfg)):
        keys = jax.random.split(kg(), num_groups(cfg))
        blocks[f"b{j}"] = jax.vmap(
            lambda k, kind=kind: blk.block_params(cfg, kind, k, dtype))(keys)
    params["blocks"] = blocks
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["head"] = {"w_head": dense_init(
            kg(), (emb.padded_vocab(cfg.vocab_size), cfg.d_model), dtype)}
    return params


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStructs for params — no allocation (dry-run / spec building)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


@functools.lru_cache(maxsize=None)
def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    import math
    shapes = abstract_params(cfg)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = math.prod(leaf.shape)
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if active_only and name.startswith("we_") and cfg.moe:
            n = n * cfg.moe.top_k // cfg.moe.num_experts
        total += n
    return total


@functools.lru_cache(maxsize=None)
def count_flops_params(cfg: ModelConfig, active_only: bool = True) -> int:
    """Params entering the 6ND estimate (excludes embedding table & head)."""
    import math
    shapes = abstract_params(cfg)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [str(p.key) for p in path if hasattr(p, "key")]
        if "embed" in names or "head" in names:
            continue
        n = math.prod(leaf.shape)
        if active_only and names[-1].startswith("we_") and cfg.moe:
            n = n * cfg.moe.top_k // cfg.moe.num_experts
        total += n
    return total


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _remat_policy(cfg: ModelConfig):
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def _sp_constraint(x, cfg: ModelConfig, plan):
    """Sequence parallelism: keep the residual stream sharded over the model
    axis between blocks (Megatron-SP).  The saved scan carry — the dominant
    activation residency under remat — shrinks by the TP degree; XLA turns
    the surrounding TP all-reduces into reduce-scatter + all-gather pairs
    (same wire bytes, 16x less HBM)."""
    if plan is None or plan.mesh is None or plan.model_axis is None:
        return x
    tp = plan.plan.axis_size(plan.model_axis)
    if tp <= 1 or x.ndim != 3 or x.shape[1] % tp:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    b = plan.batch_axes or None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(plan.mesh, P(b, plan.model_axis, None)))


def run_blocks(params, x, positions, cfg: ModelConfig, plan, caches=None,
               mode: str = "train", write_mask=None):
    """x: (B,S,D).  Returns (x, new_caches, aux_total)."""
    gpat = group_pattern(cfg)
    use_sp = mode in ("train", "prefill") and blk.sp_enabled(
        cfg, plan, x.shape[1], mode)

    def body(carry, xs):
        x, aux = carry
        gparams, gcache = xs
        new_gc = {}
        for j, kind in enumerate(gpat):
            c = None if gcache is None else gcache.get(f"b{j}")
            # blocks keep the residual S-sharded internally (Megatron-SP);
            # see blocks.sp_gather / sp_scatter
            x, nc, a = blk.apply_block(gparams[f"b{j}"], x, positions, cfg,
                                       kind, plan, c, mode,
                                       write_mask=write_mask)
            aux = aux + a
            if nc is not None:
                new_gc[f"b{j}"] = nc
        return (x, aux), new_gc

    if mode == "train" and cfg.remat != "none":
        body = jax.checkpoint(body, policy=_remat_policy(cfg),
                              prevent_cse=False)

    if use_sp:
        x = _sp_constraint(x, cfg, plan)
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                        (params["blocks"], caches))
    return x, new_caches, aux


def _head_table(params, cfg: ModelConfig):
    return params["embed"]["table"] if cfg.tie_embeddings else params["head"]["w_head"]


def _embed_input(params, batch, cfg: ModelConfig, plan, mode: str = "train"):
    if "embeddings" in batch:          # modality frontend stub output
        return batch["embeddings"].astype(_dtype(cfg))
    sp = blk.sp_enabled(cfg, plan, batch["tokens"].shape[1], mode)
    return emb.embed_lookup(params["embed"]["table"], batch["tokens"], plan,
                            seq_sharded=sp)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def loss_fn(params, batch, cfg: ModelConfig, plan=LOCAL):
    """batch: {tokens|embeddings, labels}.  Returns (loss, metrics)."""
    x = _embed_input(params, batch, cfg, plan, "train")
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, aux = run_blocks(params, x, positions, cfg, plan, None, "train")
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    labels = batch["labels"]
    per_tok = emb.sharded_xent(x, _head_table(params, cfg), jnp.maximum(labels, 0),
                               plan, cfg,
                               seq_sharded=blk.sp_enabled(cfg, plan, S, "train"))
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    xent = (per_tok * mask).sum() / denom
    aux_coef = cfg.moe.aux_loss_coef if cfg.moe else 0.0
    aux = aux / max(cfg.num_layers // cfg.group_size, 1)
    loss = xent + aux_coef * aux
    return loss, {"xent": xent, "aux": aux, "tokens": denom}


def prefill_fn(params, batch, cfg: ModelConfig, plan=LOCAL):
    """Full-sequence prefill.  Returns (next_token (B,), caches).

    With ``batch["lengths"]`` (B,) the prompts are right-padded to a common
    S and each row samples its next token at position ``lengths[i] - 1``
    (pad tokens only ever attend causally *forward*, so the first
    ``lengths[i]`` cache entries are exact — the serve engine masks the
    rest via kpos).
    """
    x = _embed_input(params, batch, cfg, plan, "prefill")
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x, caches, _ = run_blocks(params, x, positions, cfg, plan, _abstract_none(cfg),
                              "prefill")
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if "lengths" in batch:
        last = x[jnp.arange(B), batch["lengths"].astype(jnp.int32) - 1]
    else:
        last = x[:, -1]
    nxt = emb.greedy_sample(last, _head_table(params, cfg), plan, cfg)
    return nxt, caches


def _abstract_none(cfg: ModelConfig):
    """Scan xs placeholder when caches don't exist yet (prefill builds them)."""
    return None


def decode_fn(params, caches, token, pos, cfg: ModelConfig, plan=LOCAL,
              write_mask=None):
    """One decode step.  token: (B,1) int32; pos: () int32 (uniform batch
    pos) or (B,) int32 per-slot positions against ``per_slot`` caches (the
    continuous-batching serve layout).

    ``write_mask`` (B,) bool gates cache writes per slot — the fused
    K-step decode block keeps finished slots inert while the rest of the
    pool keeps stepping.  Returns (next_token (B,), new_caches).
    """
    x = emb.embed_lookup(params["embed"]["table"], token, plan)
    positions = pos[None].astype(jnp.int32) if pos.ndim == 0 else pos
    x, new_caches, _ = run_blocks(params, x, positions, cfg, plan, caches,
                                  "decode", write_mask=write_mask)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    nxt = emb.greedy_sample(x[:, -1], _head_table(params, cfg), plan, cfg)
    return nxt, new_caches


def decode_block_fn(params, caches, tokens, positions, alive, remaining,
                    cfg: ModelConfig, plan=LOCAL, *, k_steps: int,
                    eos_id: Optional[int], max_len: int):
    """Device-resident fused decode loop: up to ``k_steps`` greedy decode
    steps in ONE jitted dispatch — sampling, per-slot position increments,
    EOS / max-new / max-len termination masks and KV writes all stay on
    device; the host reads back one (K, B) token block per call instead of
    one token per step.

    tokens: (B,) int32 current input token per slot; positions: (B,) int32
    next cache position; alive: (B,) bool decode-active slots; remaining:
    (B,) int32 tokens each slot may still emit.  The loop exits early once
    every slot is done (no wasted steps when a whole block finishes).

    Returns (out (K, B) int32 — -1 where a slot emitted nothing that step,
    n_steps executed, tokens, positions, alive, remaining, caches).  Slot
    state evolves exactly as the K=1 host reference loop
    (``train.serve_loop._decode_step`` + ``_push_token``): a slot's step
    emits ``next``, advances its position, then finishes on EOS, cache-full
    (pos reaching ``max_len - 1``) or its max-new budget; finished slots
    are frozen via the decode ``write_mask`` so their caches stay inert.
    """
    B = tokens.shape[0]

    def cond(state):
        i, _, _, _, alive, _, _ = state
        return (i < k_steps) & alive.any()

    def body(state):
        i, out, tok, pos, alive, rem, caches = state
        nxt, caches = decode_fn(params, caches, tok[:, None], pos, cfg, plan,
                                write_mask=alive)
        nxt = nxt.astype(jnp.int32)
        out = out.at[i].set(jnp.where(alive, nxt, -1))
        pos = jnp.where(alive, pos + 1, pos)
        rem = jnp.where(alive, rem - 1, rem)
        eos = (nxt == eos_id) if eos_id is not None \
            else jnp.zeros((B,), bool)
        done = eos | (pos >= max_len - 1) | (rem <= 0)
        tok = jnp.where(alive, nxt, tok)
        alive = alive & ~done
        return i + 1, out, tok, pos, alive, rem, caches

    state = (jnp.int32(0), jnp.full((k_steps, B), -1, jnp.int32),
             tokens.astype(jnp.int32), positions.astype(jnp.int32),
             alive, remaining.astype(jnp.int32), caches)
    i, out, tok, pos, alive, rem, caches = jax.lax.while_loop(cond, body,
                                                              state)
    return out, i, tok, pos, alive, rem, caches


def prefill_chunk_fn(params, caches, tokens, qpos, last_idx,
                     cfg: ModelConfig, plan=LOCAL):
    """One chunk of an incremental (chunked) prefill for a single slot.

    tokens: (1, C) int32 chunk token ids (pad rows 0); qpos: (1, C) int32
    logical positions of each row (-1 = pad); last_idx: (1,) int32 index of
    the chunk's final real row (where the next token samples — only the
    last chunk's sample is consumed).  ``caches`` is a pool-view pytree
    whose ``pages`` leaves are the target slot's page-table row
    ((num_groups, 1, maxp)) over the shared kp/vp pools, so the chunk
    splices into the paged pool without touching other slots.

    Returns (next_token (1,), updated caches).  Requires a pure paged
    full-attention stack (the engine gates chunking on that).
    """
    x = emb.embed_lookup(params["embed"]["table"], tokens, plan)
    x, new_caches, _ = run_blocks(params, x, qpos.astype(jnp.int32), cfg,
                                  plan, caches, "chunk")
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[jnp.arange(x.shape[0]), last_idx.astype(jnp.int32)]
    nxt = emb.greedy_sample(last, _head_table(params, cfg), plan, cfg)
    return nxt, new_caches


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                per_slot: bool = False, paged: bool = False,
                page_size: int = 16, num_pages: Optional[int] = None):
    """Stacked decode caches: leaves (num_groups, ...).

    ``per_slot=True`` gives every batch slot its own kpos track
    ((num_groups, batch, S) instead of (num_groups, S)) so slots can sit at
    different positions — required by the continuous-batching serve engine.

    ``paged=True`` (implies per-slot) replaces the dense per-slot strips of
    full-attention layers with paged KV pools: ``num_pages`` physical pages
    of ``page_size`` token rows each (+1 scratch page) and a per-slot page
    table, managed by ``core.kv_pages.PageAllocator`` in the engine.  The
    default ``num_pages`` covers the dense worst case; size it down to cap
    KV memory at expected live tokens (admission backpressures on
    exhaustion).  Window/ring and recurrent layers keep dense state.
    """
    dtype = _dtype(cfg)
    gpat = group_pattern(cfg)
    ng = num_groups(cfg)
    if paged:
        per_slot = True
        if num_pages is None:
            from repro.core.kv_pages import pages_for
            num_pages = batch * pages_for(max_len, page_size)
    out = {}
    for j, kind in enumerate(gpat):
        one = blk.init_block_cache(cfg, kind, batch, max_len, dtype,
                                   paged=paged, num_pages=num_pages or 0,
                                   page_size=page_size)
        out[f"b{j}"] = jax.tree.map(
            lambda l: jnp.zeros((ng,) + l.shape, l.dtype) if l.dtype != jnp.int32
            else jnp.broadcast_to(l, (ng,) + l.shape).copy(), one)
    # kpos slots must start empty (-1), zeros would alias position 0
    def fix_kpos(path, leaf):
        names = [str(p.key) for p in path if hasattr(p, "key")]
        if names and names[-1] == "kpos":
            shape = (leaf.shape[0], batch) + leaf.shape[1:] if per_slot \
                else leaf.shape
            return jnp.full(shape, -1, jnp.int32)
        return leaf
    return jax.tree_util.tree_map_with_path(fix_kpos, out)


def abstract_caches(cfg: ModelConfig, batch: int, max_len: int,
                    per_slot: bool = False, paged: bool = False,
                    page_size: int = 16, num_pages: Optional[int] = None):
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_len, per_slot,
                                              paged, page_size, num_pages))


# ---------------------------------------------------------------------------
# Input specs (dry-run stand-ins)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStructs for every model input of this (arch, shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        if cfg.frontend:
            return {"embeddings": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                       _dtype(cfg)),
                    "labels": jax.ShapeDtypeStruct((B, S), i32)}
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32)}
    if shape.kind == "prefill":
        if cfg.frontend:
            return {"embeddings": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                       _dtype(cfg))}
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    # decode: one new token against a cache of S
    return {
        "token": jax.ShapeDtypeStruct((B, 1), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
        "caches": abstract_caches(cfg, B, S),
    }
